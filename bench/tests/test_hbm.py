"""The bytes each scope of the decode step must move (``harness/hbm.py``),
against a count by hand at qwen1.5-4b's widths."""
from __future__ import annotations

import json

import numpy as np

from conftest import BENCH
from harness import hbm

QWEN = json.loads((BENCH / "configs" / "qwen1.5-4b-n8.json").read_text())

# 40 layers, d 2560, 20 heads of 128 (20 of them for K and V), d_ff 6912,
# bf16 weights and cache.
QKV_O = 40 * (2560 * 60 * 128 + 60 * 128 + 20 * 128 * 2560) * 2
MLP = 40 * 3 * 2560 * 6912 * 2
KV_POSITION = 40 * 2 * 20 * 128 * 2


def test_hand_count_at_qwen_widths():
    assert QKV_O == 2_097_766_400
    assert MLP == 4_246_732_800
    assert KV_POSITION == 409_600
    mask = np.zeros((8, 8), bool)
    mask[0, :3] = True            # slot 0: 3 live lanes at position 99
    mask[5, 7] = True             # slot 5: one live lane at position 9
    pos = np.array([99, 40, 0, 7, 3, 9, 500, 12])   # dead slots' positions
    assert hbm.step(QWEN, mask, pos) == {"attention": QKV_O + KV_POSITION * (100 + 10),
                   "mlp": MLP}


def test_a_step_with_no_live_lane_needs_nothing():
    assert hbm.step(QWEN, np.zeros((8, 8), bool), np.arange(8)) == {
        "attention": 0, "mlp": 0}

"""The FLOP counter against a count by hand at a tiny size."""
from __future__ import annotations

import numpy as np

from harness import flops


def test_dense_step_by_hand():
    config = {"mux": {"n": 2},
              "model": {"n_layers": 1, "d_model": 4, "n_heads": 2,
                        "n_kv_heads": 1, "head_dim": 2, "d_ff": 8,
                        "vocab": 10, "gated_mlp": True}}
    mask = np.array([[1, 1], [1, 0], [0, 0]])
    pos = np.array([3, 0, 7])
    # slot 0 (2 live lanes, 4 keys): mux 2*2*4 = 16
    #   q 2*4*4 = 32, k and v 2*4*2 each = 32, o 2*4*4 = 32   -> 96
    #   QK and PV: 2 * 2*heads(2)*hd(2)*keys(4) = 64
    #   MLP: 3 * 2*4*8 = 192                                  -> 352
    #   per lane: demux 2*(8*8 + 8*4) = 192, head 2*4*10 = 80 -> 272 each
    slot0 = 16 + 96 + 64 + 192 + 2 * 272
    # slot 1 (1 lane, 1 key): attention 2 * 2*2*2*1 = 16
    slot1 = 16 + 96 + 16 + 192 + 272
    assert flops.step(config, mask, pos) == slot0 + slot1     # slot 2 idle


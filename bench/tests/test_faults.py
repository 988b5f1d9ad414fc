"""The check must catch a broken timed path, and the control must fail it.

Each fault is planted in the program inside the child process, under the
harness, and the whole run is driven with the chip check skipped: it must
come out ``correct: false``.  The cells are served on one chip, so there is
no exchange between chips to leave out.
"""
from __future__ import annotations

import json

import pytest

from conftest import TINY_LIMIT, last_json, run_cell

# A token altered where it is produced: every third request's tokens after
# its first are shifted by one in the vocabulary.
ALTERED_TOKEN = """
from repro.serving import policies
_select = policies.LaneSampling.select
def select(self, req, logits):
    tok = _select(self, req, logits)
    if req.rid % 3 == 0 and req.output:
        tok = (tok + 1) % len(logits)
    return tok
policies.LaneSampling.select = select
"""

# A step that returns its state unchanged: the logits are computed, the
# cache the step wrote is dropped for a copy of the one it was given (the
# step donates its input cache).
STATE_UNCHANGED = """
import dataclasses
import jax, jax.numpy as jnp
from repro.serving import engine
_step = engine.Engine.step
def step(self, state, tokens, **kw):
    kept = jax.tree.map(jnp.copy, state.cache)
    logits, new = _step(self, state, tokens, **kw)
    return logits, dataclasses.replace(new, cache=kept)
engine.Engine.step = step
"""

# Half of the batch left out: every other lane of each slot is masked out
# of the step, so the mixed stream is the mean over the rest and the masked
# lanes are served from empty logits.
HALF_THE_BATCH = """
import numpy as np
from repro.serving import engine
_step = engine.Engine.step
def step(self, state, tokens, lane_mask=None, **kw):
    m = np.array(lane_mask, np.float32)
    m[:, 1::2] = 0
    return _step(self, state, tokens, lane_mask=m, **kw)
engine.Engine.step = step
"""

FAULTS = {"altered_token": ALTERED_TOKEN, "state_unchanged": STATE_UNCHANGED,
          "half_the_batch": HALF_THE_BATCH}


@pytest.mark.parametrize("cell", ["tiny-chat", "tiny-batch"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(checkout, cell, fault):
    out = last_json(run_cell(checkout, "--workload", cell, "--seed", "31",
                             "--seconds", "2", "--trace", "0",
                             patch=FAULTS[fault]))
    assert out["correct"] is False, out["check"]


@pytest.mark.parametrize("cell", ["tiny-chat", "tiny-batch"])
def test_control_fails_the_limit(checkout, cell):
    """Through the comparison that decides a run's ``correct``, the
    reference computed with float8 matmul operands in the program's place
    comes out not correct on three seeds, and the program correct."""
    proc = run_cell(checkout, "control", "--workload", cell, "--seeds",
                    "41,42,43", "--seconds", "2", script="calibrate.py")
    rows = [json.loads(r) for r in proc.stdout.splitlines()
            if r.startswith("{")]
    assert len(rows) == 3, proc.stderr[-3000:]
    for row in rows:
        program, control = row["program"], row["control"]
        assert program["correct"] is True, row
        assert control["correct"] is False, row
        assert control["check"]["logit_gap"]["limit"] == TINY_LIMIT
        assert control["check"]["logit_gap"]["value"] > TINY_LIMIT, row

"""The traffic generator: one seed, one trace; every seed, the same sizes."""
from __future__ import annotations

import json
from types import SimpleNamespace

import numpy as np
import pytest

from harness import readers, traffic
from conftest import BENCH

FILES = sorted((BENCH / "traffic").glob("*.json"))
BIG_SEED = 2**31 + 12345


def _load(path):
    return json.loads(path.read_text())


@pytest.mark.parametrize("path", FILES, ids=[p.stem for p in FILES])
def test_same_seed_same_trace(path):
    t = _load(path)
    a = traffic.generate(t, seed=BIG_SEED, vocab=151936, seconds=40)
    b = traffic.generate(t, seed=BIG_SEED, vocab=151936, seconds=40)
    assert [(x.due_s, x.max_new, x.prompt.tolist()) for x in a] == \
        [(x.due_s, x.max_new, x.prompt.tolist()) for x in b]


@pytest.mark.parametrize("path", FILES, ids=[p.stem for p in FILES])
def test_seeds_share_sizes_in_another_order(path):
    t = _load(path)
    a = traffic.generate(t, seed=1, vocab=50304, seconds=40)
    b = traffic.generate(t, seed=BIG_SEED, vocab=50304, seconds=40)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]


@pytest.mark.parametrize("path", FILES, ids=[p.stem for p in FILES])
def test_length_distributions(path):
    t = _load(path)
    items = traffic.generate(t, seed=3, vocab=1000, seconds=40)
    for key, lens in (("prompt", [len(x.prompt) for x in items]),
                      ("output", [x.max_new for x in items])):
        d = t[key]
        assert min(lens) >= d["min"] and max(lens) <= d["max"]
        assert abs(np.median(lens) - d["median"]) <= 1
        # lognormal: the 84th percentile sits sigma above the median in
        # log space (unless clipped)
        hi = np.percentile(lens, 84.13)
        want = min(d["median"] * np.exp(d["sigma"]), d["max"])
        assert abs(hi - want) / want < 0.05
    assert all(0 <= x.prompt.min() and x.prompt.max() < 1000 for x in items)


POISSON = {"arrival": "poisson", "rate_per_s": 5.0, "warmup_s": 10,
           "drain_s": 20,
           "prompt": {"median": 64, "sigma": 0.7, "min": 8, "max": 256},
           "output": {"median": 48, "sigma": 0.7, "min": 4, "max": 192}}


def test_poisson_rate_and_segments():
    items = traffic.generate(POISSON, seed=9, vocab=100, seconds=30)
    assert len(items) == traffic.n_requests(POISSON, 30) == 50 + 150 + 100
    due = [x.due_s for x in items]
    assert due == sorted(due)
    # each segment is filled exactly and holds its own arrivals: the
    # warm-up's 50 lie in (0, 10), the window's 150 in (10, 40)
    assert 0 < due[0] and due[49] < 10.0 < due[50]
    assert due[199] < 40.0 < due[200] and due[-1] < 60.0


def test_every_seed_has_the_same_window():
    def window(seed):
        items = traffic.generate(POISSON, seed=seed, vocab=100, seconds=30)
        return [x for x in items if 10.0 < x.due_s <= 40.0]
    a, b = window(1), window(BIG_SEED)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]


def test_every_backlog_block_holds_the_same_mix():
    t = _load(BENCH / "traffic" / "chat-backlog.json")
    k = t["block"]
    items = traffic.generate(t, seed=BIG_SEED, vocab=100, seconds=40)
    blocks = [items[i:i + k] for i in range(0, len(items), k)]
    for b in blocks[1:]:
        assert sorted(len(x.prompt) for x in b) == \
            sorted(len(x.prompt) for x in blocks[0])
        assert sorted(x.max_new for x in b) == \
            sorted(x.max_new for x in blocks[0])


def test_backlog_is_due_at_once():
    t = _load(BENCH / "traffic" / "chat-backlog.json")
    items = traffic.generate(t, seed=4, vocab=100, seconds=40)
    assert len(items) == t["requests"]
    assert {x.due_s for x in items} == {0.0}


def test_the_window_holds_its_segment_whatever_step_opens_it():
    """The window opens and closes on step ends, which may lag the
    traffic's window segment by a step; its requests are the segment's all
    the same, so every run and every seed count the same sizes."""
    items = traffic.generate(POISSON, seed=BIG_SEED, vocab=100, seconds=30)
    base = 1000.0
    late = SimpleNamespace(due={x.rid: base + x.due_s for x in items},
                           due_window=(base + 10.0, base + 40.0),
                           window=(base + 10.5, base + 40.5))
    segment = [x.rid for x in items if 10.0 < x.due_s < 40.0]
    assert len(segment) == 150
    assert sorted(readers.window_due(late)) == segment

"""One general traffic generator, driven by a traffic file.

Adapted from the program's ``scheduler.poisson_trace``: arrivals on the wall
clock in seconds instead of scheduler steps, and heavy-tailed (lognormal)
prompt and output lengths instead of uniform and geometric ones.

Every seed gets the same sizes and gaps, in another order, so the seed
changes which request is long and when, and not how much work a run holds:
  * poisson: the warm-up, the window and the drain are each a segment of
    round(rate x seconds) arrivals holding the length distribution's
    quantiles at (i + 0.5) / n and the exponential's gaps at the same points,
    shuffled by the seed; the gaps are scaled to fill the segment exactly,
    each arrival halfway through its gap.
    So every seed's window holds the very same sizes.
  * backlog: the queue comes in blocks of ``block`` requests, each holding
    the ``block`` quantiles, shuffled; the window takes a prefix of the
    queue, so it holds nearly the same sizes.
Token ids are uniform over the vocabulary, drawn from the seed.

Traffic file keys:
  arrival      "poisson" (open loop at ``rate_per_s``) or "backlog"
               (``requests`` queued at time 0)
  block        requests per block of the fixed size mix (backlog)
  prompt       {"median", "sigma", "min", "max"}: lognormal prompt length
  output       the same for the number of generated tokens
  warmup_s     seconds served before the window opens
  drain_s      seconds past the window's close allowed for the window's
               requests to reach their first token (poisson)
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Item:
    rid: int
    due_s: float           # seconds after the traffic's start
    prompt: np.ndarray     # (Lp,) int32
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(dist: dict, n: int) -> np.ndarray:
    z = np.array([_NORMAL.inv_cdf(q) for q in _quantiles(n)])
    raw = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(raw), dist["min"], dist["max"]).astype(np.int64)


def _segments(traffic: dict, seconds: float) -> list:
    """(start, duration, arrivals) of each segment of a poisson trace."""
    out, t = [], 0.0
    for dur in (traffic["warmup_s"], seconds, traffic["drain_s"]):
        n = int(round(traffic["rate_per_s"] * dur))
        if n:
            out.append((t, dur, n))
        t += dur
    return out


def n_requests(traffic: dict, seconds: float) -> int:
    if traffic["arrival"] == "backlog":
        k = traffic["block"]
        return -(-int(traffic["requests"]) // k) * k
    return sum(n for *_, n in _segments(traffic, seconds))


def _shuffled(rng, values: np.ndarray, reps: int) -> np.ndarray:
    return np.concatenate([rng.permutation(values) for _ in range(reps)])


def generate(traffic: dict, *, seed: int, vocab: int,
             seconds: float) -> list[Item]:
    rng = np.random.default_rng(seed)
    if traffic["arrival"] == "poisson":
        prompts, outputs, due = [], [], []
        for start, dur, n in _segments(traffic, seconds):
            prompts.append(rng.permutation(
                lognormal_lengths(traffic["prompt"], n)))
            outputs.append(rng.permutation(
                lognormal_lengths(traffic["output"], n)))
            gaps = rng.permutation(-np.log1p(-_quantiles(n)))
            # each arrival halfway through its gap: strictly inside
            due.append(start + (np.cumsum(gaps) - gaps / 2)
                       * (dur / gaps.sum()))
        prompts, outputs, due = (np.concatenate(x)
                                 for x in (prompts, outputs, due))
    elif traffic["arrival"] == "backlog":
        k = traffic["block"]
        reps = n_requests(traffic, seconds) // k
        prompts = _shuffled(rng, lognormal_lengths(traffic["prompt"], k), reps)
        outputs = _shuffled(rng, lognormal_lengths(traffic["output"], k), reps)
        due = np.zeros(len(prompts))
    else:
        raise ValueError(f"unknown arrival {traffic['arrival']!r}")
    return [Item(rid=i, due_s=float(due[i]),
                 prompt=rng.integers(0, vocab, int(prompts[i]),
                                     dtype=np.int64).astype(np.int32),
                 max_new=int(outputs[i]))
            for i in range(len(prompts))]

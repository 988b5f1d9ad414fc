"""Arithmetic shared by the metric readers in ``bench/metrics``.

Every reader takes the run's context and returns a number, or None when
the run holds nothing for it to read (then the metric is left out).
"""
from __future__ import annotations

import numpy as np


def window_due(served) -> list:
    """The requests of the traffic's window segment: due in the window's
    scheduled seconds, whatever step the window opened and closed on."""
    lo, hi = served.due_window
    return [r for r, d in served.due.items() if lo <= d < hi]


def ttft_ms(served) -> list:
    """Due time to the host holding the first token, per request due in the
    window; one with no first token counts at its wait to the run's end."""
    out = []
    for r in window_due(served):
        ts = served.tok_times.get(r)
        end = ts[0] if ts else served.run_end
        out.append((end - served.due[r]) * 1e3)
    return out


def itl_ms(served) -> list:
    """Gaps between consecutive tokens of one request, whose later token
    came inside the window."""
    t0, t1 = served.window
    out = []
    for ts in served.tok_times.values():
        for a, b in zip(ts, ts[1:]):
            if t0 < b <= t1:
                out.append((b - a) * 1e3)
    return out


def percentile(values: list, q: float):
    return float(np.percentile(values, q)) if values else None


def roofline_share(ctx, scope: str):
    """Percent of the chip's HBM peak that a scope of the decode step
    reaches: the bytes it must move per traced step (``hbm.step``, averaged
    over the traced steps) over its device seconds per step
    (``scopes.scope_ms``) times the peak."""
    from harness import drive, hbm, scopes
    if ctx.peak_hbm is None:
        return None
    ms = scopes.scope_ms(ctx, scope)
    if not ms:
        return None
    served = ctx.served
    lo, hi = served.traced_steps
    got = [hbm.step(served.config, inp.mask, inp.pos)[scope]
           for inp in (drive.input_at(served, t) for t in range(lo, hi))
           if inp is not None]
    if not got or not sum(got):
        return None
    return (sum(got) / len(got) / (ms * 1e-3 * ctx.peak_hbm * ctx.n_chips)
            * 100.0)

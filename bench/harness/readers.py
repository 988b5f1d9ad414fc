"""Arithmetic shared by the metric readers in ``bench/metrics``.

Every reader takes the run's context and returns a number, or None when
the run holds nothing for it to read (then the metric is left out).
"""
from __future__ import annotations

import numpy as np


def window_due(served) -> list:
    t0, t1 = served.window
    return [r for r, d in served.due.items() if t0 <= d < t1]


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


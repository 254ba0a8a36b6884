"""Helpers the metric readers share (not a metric: no BENCHMARK.json entry
names it)."""
from __future__ import annotations

import numpy as np


def window_tokens(run, decode_only=False):
    """(count, mean context) of the tokens the host saw in the window;
    ``decode_only`` leaves out each request's first token (its prefill)."""
    ws, we = run.window
    n, ctx = 0, 0.0
    for r in run.records:
        seen = 0
        for t, k in r.events:
            first = seen == 0
            seen += k
            if ws < t <= we:
                m = k - (1 if decode_only and first else 0)
                n += m
                ctx += m * (len(r.req.prompt) + seen - k / 2.0)
    return n, (ctx / n if n else 0.0)


def slotted_context(run):
    """(mean total context, mean rows) of the requests holding a slot at
    the steps inside the window."""
    ws, we = run.window
    s = [(c, b) for t, c, b in run.row_tokens if ws < t <= we]
    if not s:
        return 0.0, 0.0
    a = np.asarray(s, np.float64)
    return float(a[:, 0].mean()), float(a[:, 1].mean())


def median(values):
    return float(np.median(values)) if len(values) else None

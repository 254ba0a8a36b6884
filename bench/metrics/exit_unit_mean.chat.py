"""Mean layers run per decoded token: the exit point SpecEE recorded for
each token served in the window, plus one (full depth where none fired)."""
import numpy as np


def read(run):
    E, ws, we = run.exit_points, run.window[0], run.window[1]
    units = []
    for r in run.records:
        if r.handle is None:
            continue
        exits = r.handle.exit_points
        seen = 0
        for t, k in r.events:
            for j in range(seen, seen + k):
                if ws < t <= we and 1 <= j <= len(exits):
                    e = int(exits[j - 1])
                    units.append(e + 1 if e < E else E)
            seen += k
    return float(np.mean(units)) if units else None

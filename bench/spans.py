"""The program's own spans, counters and programs in a profiler trace.

The serving loop marks its layers with ``serve.*`` host spans
(``repro.runtime.telemetry``): ``serve.step`` holds ``serve.dispatch``,
``serve.finish``, ``serve.collect`` and ``serve.admit``, which holds
``serve.admit.chunk`` and ``serve.admit.finish``. ``serve.collect``
carries, as its metadata, the counters it added (``ticks``, ``units_run``,
``head_passes``, ``decoded_tokens``). The jitted programs carry names
(``jit_decode_megatick``, ``jit_decode_step``, ``jit_prefill_extend``).
A trace of a program that has none of these reduces to empty tables.

``reduce(pd)`` reads, inside the ``bench.window`` span:

- ``counters``: the sums of what the ``serve.collect`` spans that open in
  the window carry;
- ``span_s``: host time per span name, clipped to the window;
- ``program_s``: device time per program (the ``XLA Modules`` line), its
  ``(<hash>)`` suffix stripped, averaged over the device planes;
- ``idle_s``: each idle gap of the device put down to the innermost
  ``bench.*`` or ``serve.*`` span open over it, else ``host.other``. The
  gaps and busy time are those of ``bench.trace``, so the idle times sum
  to the window less its busy time.

    python3 -m bench.spans <trace dir>

prints that reduction of the newest trace under the directory, with the
calls of the LM-head kernels beside the counted passes, as one JSON line.
"""
from __future__ import annotations

import heapq
import json
import os
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from bench import trace

PREFIXES = ("bench.", "serve.")
COLLECT = "serve.collect"
DECODE_PROGRAMS = ("jit_decode_megatick", "jit_decode_step")
HEAD_KERNELS = ("argmax_verify", "topk_verify")
_HASH = re.compile(r"\(\d+\)$")


@dataclass
class Program:
    window_s: float
    busy_s: float
    counters: Dict[str, int] = field(default_factory=dict)
    span_s: Dict[str, float] = field(default_factory=dict)
    program_s: Dict[str, float] = field(default_factory=dict)
    idle_s: Dict[str, float] = field(default_factory=dict)


def program_name(module: str) -> str:
    """``jit_decode_megatick(1234)`` -> ``jit_decode_megatick``."""
    return _HASH.sub("", module)


def innermost(spans: List[Tuple[int, int, str]]
              ) -> List[Tuple[int, int, str]]:
    """Possibly nested spans flattened into pieces that do not overlap,
    each named by the innermost span open over it (the last to open)."""
    points = sorted({a for a, _, _ in spans} | {b for _, b, _ in spans})
    by_start = sorted(spans)
    open_: List[Tuple[int, int, str]] = []      # (-start, end, name)
    out: List[Tuple[int, int, str]] = []
    i = 0
    for x, y in zip(points, points[1:]):
        while i < len(by_start) and by_start[i][0] <= x:
            a, b, n = by_start[i]
            heapq.heappush(open_, (-a, b, n))
            i += 1
        while open_ and open_[0][1] <= x:
            heapq.heappop(open_)
        if open_:
            name = open_[0][2]
            if out and out[-1][1] == x and out[-1][2] == name:
                out[-1] = (out[-1][0], y, name)
            else:
                out.append((x, y, name))
    return out


def attribute_idle(busy: List[Tuple[int, int]],
                   pieces: List[Tuple[int, int, str]], lo: int, hi: int,
                   idle: Dict[str, float]) -> None:
    """Each idle gap between the (sorted, disjoint) busy intervals within
    [lo, hi), split among the pieces of ``innermost``; what no piece covers
    is ``host.other``."""
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    j = 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        covered, k = 0, j
        while k < len(pieces) and pieces[k][0] < b:
            c = trace.clip(pieces[k][0], pieces[k][1], a, b)
            idle[pieces[k][2]] += c
            covered += c
            k += 1
        idle["host.other"] += (b - a) - covered


def reduce(pd) -> Program:
    """Reduce a ``jax.profiler.ProfileData`` (see the module docstring)."""
    spans: List[Tuple[int, int, str]] = []
    counters: Dict[str, int] = defaultdict(int)
    collects = []
    for plane in pd.planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    a = int(e.start_ns)
                    spans.append((a, a + int(e.duration_ns), e.name))
                    if e.name == COLLECT:
                        collects.append((a, dict(e.stats)))
    wins = [(a, b) for a, b, n in spans if n == trace.WINDOW]
    if not wins:
        raise ValueError(f"the trace has no {trace.WINDOW} span")
    lo, hi = wins[0]
    for a, stats in collects:
        if lo <= a < hi:
            for k, v in stats.items():
                counters[k] += int(v)
    inside = [(max(a, lo), min(b, hi), n) for a, b, n in spans
              if n != trace.WINDOW and b > lo and a < hi]
    span_s: Dict[str, float] = defaultdict(float)
    for a, b, n in inside:
        span_s[n] += (b - a) * 1e-9
    pieces = innermost(inside)
    devices = [p for p in pd.planes
               if p.name.startswith(trace.DEVICE_PREFIX)]
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    program_s: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    busy_total = 0
    for plane in devices:
        ops = []
        for line in plane.lines:
            if line.name == trace.OPS_LINE:
                ops += [e for e in trace._events(line, names=True)
                        if e[0].rsplit(" ", 1)[-1] not in trace.CONTAINERS]
            elif line.name == trace.MODULES_LINE:
                for name, a, b in trace._events(line):
                    program_s[program_name(name)] += trace.clip(a, b, lo, hi)
        busy = trace.union([(a, b) for _, a, b in ops if b > lo and a < hi])
        busy = [(max(a, lo), min(b, hi)) for a, b in busy]
        busy_total += sum(b - a for a, b in busy)
        attribute_idle(busy, pieces, lo, hi, idle)
    n = len(devices)
    return Program(window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / n,
                   counters=dict(counters), span_s=dict(span_s),
                   program_s={k: v * 1e-9 / n for k, v in program_s.items()},
                   idle_s={k: v * 1e-9 / n for k, v in idle.items()})


_cache: Dict[tuple, Program] = {}


def reduce_file(path: str) -> Program:
    """``reduce`` of one ``.xplane.pb``, read once per file version."""
    from jax.profiler import ProfileData
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        _cache[key] = reduce(ProfileData.from_file(path))
    return _cache[key]


def of_run(run) -> Optional[Program]:
    """The reduction of a traced run's trace, or None for a run that was
    not traced."""
    if run.trace is None:
        return None
    from bench.harness import Tracer
    path = trace.find_xplane(Tracer().dir)
    return reduce_file(path) if path else None


def _longest_first(d: Dict[str, float]) -> Dict[str, float]:
    return dict(sorted(d.items(), key=lambda kv: -kv[1]))


def main(directory: str) -> int:
    from jax.profiler import ProfileData
    path = trace.find_xplane(directory)
    if path is None:
        print(f"no .xplane.pb under {directory}", file=sys.stderr)
        return 1
    pd = ProfileData.from_file(path)
    s, p = trace.reduce(pd), reduce(pd)
    calls = {k: s.kernel(k)[1] for k in HEAD_KERNELS}
    print(json.dumps({"window_s": p.window_s, "busy_s": p.busy_s,
                      "counters": p.counters,
                      "head_kernel_calls": calls,
                      "span_s": _longest_first(p.span_s),
                      "program_s": _longest_first(p.program_s),
                      "idle_s": _longest_first(p.idle_s)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

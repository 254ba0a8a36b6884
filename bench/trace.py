"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, time per device operation, kernel time and
calls, the device time of the programs that run paged decode attention,
and the device's idle gaps attributed to the host span that was open
during each.

The window is the host span ``bench.window``. Device planes are
``/device:TPU:<n>``, their operations the events of the line
``XLA Ops`` (less the control flow that holds them) and their programs
those of ``XLA Modules``; the host's spans are on the plane ``/host:CPU``.
Times are clipped to the window.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
DEVICE_PREFIX = "/device:TPU:"
WINDOW = "bench.window"
SPAN_PREFIX = "bench."
DECODE_KERNEL = "paged_decode_attention"
TOP = 10
# control flow that holds other operations: its event spans its body's
# operations and the gaps between them, so it counts neither as an
# operation nor as busy time
CONTAINERS = ("while", "conditional", "call")


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(a: int, b: int, lo: int, hi: int) -> int:
    return max(0, min(b, hi) - max(a, lo))


@dataclass
class Summary:
    window_s: float
    busy_s: float                     # averaged over the device planes
    chips: int
    op_s: Dict[str, float] = field(default_factory=dict)
    op_calls: Dict[str, int] = field(default_factory=dict)
    idle_s: Dict[str, float] = field(default_factory=dict)
    decode_program_s: float = 0.0     # programs that run paged attention

    def kernel(self, key: str) -> Tuple[float, int]:
        """(seconds, calls) of the device operations whose name holds
        ``key``."""
        s = sum(v for k, v in self.op_s.items() if key in k)
        n = sum(v for k, v in self.op_calls.items() if key in k)
        return s, n

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        idle = sorted(self.idle_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def short(text: str) -> str:
    """An operation's name, result shape and opcode, from the HLO text the
    trace carries ("%fusion.3 = bf16[8,4096]{1,0:T(8,128)} fusion(...)" ->
    "fusion.3 bf16[8,4096] fusion"). Operands are dropped: a name held in
    them must not count as the operation."""
    if " = " not in text:
        return text
    name, rest = text.split(" = ", 1)
    shape = re.search(r"[a-z0-9]+\[[\d,]*\]", rest)
    op = re.search(r"\s([a-z][\w\-]*)\(", rest)
    return " ".join(x for x in (name.lstrip("%"),
                                shape.group(0) if shape else "",
                                op.group(1) if op else "") if x)


def _events(line, names=None):
    for e in line.events:
        yield (short(e.name) if names else e.name), int(e.start_ns), \
            int(e.start_ns + e.duration_ns)


def reduce(pd) -> Summary:
    """Reduce a ``jax.profiler.ProfileData``."""
    host = [p for p in pd.planes if p.name == HOST_PLANE]
    spans: List[Tuple[int, int, str]] = []
    for plane in host:
        for line in plane.lines:
            for name, a, b in _events(line):
                if name.startswith(SPAN_PREFIX):
                    spans.append((a, b, name))
    wins = [(a, b) for a, b, n in spans if n == WINDOW]
    if not wins:
        raise ValueError(f"the trace has no {WINDOW} span")
    lo, hi = wins[0]
    spans = sorted((a, b, n) for a, b, n in spans
                   if n != WINDOW and b > lo and a < hi)
    devices = [p for p in pd.planes if p.name.startswith(DEVICE_PREFIX)]
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    op_s: Dict[str, float] = defaultdict(float)
    op_calls: Dict[str, int] = defaultdict(int)
    idle: Dict[str, float] = defaultdict(float)
    busy_total, decode_total = 0.0, 0.0
    for plane in devices:
        ops, modules = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops += [e for e in _events(line, names=True)
                        if e[0].rsplit(" ", 1)[-1] not in CONTAINERS]
            elif line.name == MODULES_LINE:
                modules += list(_events(line))
        busy = union([(a, b) for _, a, b in ops if b > lo and a < hi])
        busy = [(max(a, lo), min(b, hi)) for a, b in busy]
        busy_total += sum(b - a for a, b in busy)
        for name, a, b in ops:
            c = clip(a, b, lo, hi)
            if c:
                op_s[name] += c * 1e-9
                op_calls[name] += 1
        kern = sorted((a, b) for n, a, b in ops if DECODE_KERNEL in n)
        for _, a, b in modules:
            if any(ka < b and kb > a for ka, kb in _overlapping(kern, a, b)):
                decode_total += clip(a, b, lo, hi)
        _attribute_idle(busy, spans, lo, hi, idle)
    n = len(devices)
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / n,
                   chips=n, op_s=dict(op_s), op_calls=dict(op_calls),
                   idle_s={k: v * 1e-9 / n for k, v in idle.items()},
                   decode_program_s=decode_total * 1e-9 / n)


def _overlapping(sorted_iv, a, b):
    import bisect
    i = bisect.bisect_left(sorted_iv, (a, a))
    j = max(0, i - 1)
    while j < len(sorted_iv) and sorted_iv[j][0] < b:
        yield sorted_iv[j]
        j += 1


def _attribute_idle(busy, spans, lo, hi, idle) -> None:
    """Each idle gap of the device, split among the host spans open during
    it; what no span covers is ``host.other``."""
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    j = 0
    for a, b in gaps:
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        covered, k = 0, j
        while k < len(spans) and spans[k][0] < b:
            c = clip(spans[k][0], spans[k][1], a, b)
            idle[spans[k][2]] += c
            covered += c
            k += 1
        idle["host.other"] += max(0, (b - a) - covered)


def find_xplane(directory: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


def options():
    """Profiler options of a traced run: user spans and the device, no
    Python call tracing (its events would flood the host buffer over a
    window of tens of seconds)."""
    from jax.profiler import ProfileOptions
    o = ProfileOptions()
    o.host_tracer_level = 1
    o.python_tracer_level = 0
    return o


def reduce_dir(directory: str) -> Summary:
    from jax.profiler import ProfileData
    path = find_xplane(directory)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return reduce(ProfileData.from_file(path))

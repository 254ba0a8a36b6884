"""The serving loop's spans, compile accounting and work counters.

Spans are ``jax.profiler.TraceAnnotation``s: under a running profiler they
land in its trace on the same clock as the device's events; otherwise each
costs one inactive ``TraceMe``. Spans sit at layer boundaries only (a
serving step, its dispatch, finish, collect and admission), never inside a
per-row or per-token loop. Keyword arguments become the span's metadata in
the trace: ``serve.collect`` carries the counters it adds, so a trace of any
window can sum them.

``compiles`` maps the innermost span open on the compiling thread (or
``other``) to ``[count, seconds]`` of backend compiles, from one
process-wide ``jax.monitoring`` listener.

``Counters`` are plain host integers that the serving engine adds from
values the host already reads; ``snapshot``/``since`` give a window's share.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from dataclasses import dataclass
from typing import Dict, List

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_local = threading.local()
_lock = threading.Lock()
compiles: Dict[str, List] = {}     # span -> [count, seconds]


def _stack() -> List[str]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """``with span("serve.step"): ...`` — a profiler annotation that also
    names the innermost open span for the compile table."""
    __slots__ = ("name", "_tm")

    def __init__(self, name: str, **meta):
        self.name = name
        self._tm = jax.profiler.TraceAnnotation(name, **meta)

    def __enter__(self):
        _stack().append(self.name)
        self._tm.__enter__()
        return self

    def __exit__(self, *exc):
        self._tm.__exit__(*exc)
        _stack().pop()


def spanned(name: str):
    """Decorator: the whole call runs inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def _on_compile(event: str, duration: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    stack = _stack()
    name = stack[-1] if stack else "other"
    with _lock:
        entry = compiles.setdefault(name, [0, 0.0])
        entry[0] += 1
        entry[1] += float(duration)


jax.monitoring.register_event_duration_secs_listener(_on_compile)


def compile_table() -> str:
    """The compile table, one ``span: count (seconds)`` per line."""
    with _lock:
        rows = sorted(compiles.items(), key=lambda kv: -kv[1][1])
    return "\n".join(f"{k}: {n} ({s:.2f} s)" for k, (n, s) in rows)


@dataclass
class Counters:
    """Work the serving loop ran, counted on the host.

    ``ticks``, ``units_run`` and ``head_passes`` sum the device's own counts
    over every megatick result (replayed ticks included: they are device
    work); ``decoded_tokens`` the tokens those results kept; ``prefill_*``
    the admission chunks run and the prompt tokens they covered."""
    ticks: int = 0
    units_run: int = 0
    head_passes: int = 0
    decoded_tokens: int = 0
    prefill_chunks: int = 0
    prefill_tokens: int = 0

    def add(self, **counts: int) -> Dict[str, int]:
        """Add ``counts`` to the named counters; returns ``counts``."""
        for name, n in counts.items():
            setattr(self, name, getattr(self, name) + n)
        return counts

    def snapshot(self) -> "Counters":
        return dataclasses.replace(self)

    def since(self, snap: "Counters") -> "Counters":
        return Counters(**{f.name: getattr(self, f.name) -
                           getattr(snap, f.name)
                           for f in dataclasses.fields(self)})

"""The reduction of the program's spans, counters and programs
(bench/spans.py): on a synthetic trace with nested spans, on the recorded
v5e trace of the serving loop (record_serve_trace.py), and on the older
recorded trace of a program that has none of them, where the readers of
the new metrics read nothing."""
import gzip
import os
from collections import defaultdict

import pytest
from jax.profiler import ProfileData

from bench import harness, spans, trace

DATA = os.path.join(os.path.dirname(__file__), "data")


class Event:
    def __init__(self, name, start, end, **stats):
        self.name, self.start_ns, self.duration_ns = name, start, end - start
        self.stats = list(stats.items())


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class Profile:
    def __init__(self, planes):
        self.planes = planes


def _synthetic():
    """Window [0, 100) ns. Nested host spans bench.step > serve.step >
    serve.admit > serve.admit.finish, a serve.collect and a bench.wait;
    device operations at [0,10) [22,24) [36,44) [60,70) [95,105)."""
    host = [Event("bench.window", 0, 100),
            Event("bench.step", 10, 60), Event("serve.step", 12, 58),
            Event("serve.admit", 20, 40),
            Event("serve.admit.finish", 25, 35),
            Event("serve.collect", 45, 50, ticks=4, units_run=50,
                  head_passes=9),
            Event("bench.wait", 70, 90),
            Event("serve.collect", 120, 125, ticks=1, units_run=14,
                  head_passes=3)]
    ops = [Event(f"op{i}", a, b) for i, (a, b) in
           enumerate([(0, 10), (22, 24), (36, 44), (60, 70), (95, 105)])]
    modules = [Event("jit_decode_megatick(123)", 0, 10),
               Event("jit_prefill_extend(456)", 22, 24),
               Event("jit_concatenate(7)", 36, 44),
               Event("jit_decode_megatick(123)", 60, 70),
               Event("jit_decode_megatick(123)", 95, 105)]
    return Profile([Plane(trace.HOST_PLANE, [Line("python3", host)]),
                    Plane(trace.DEVICE_PREFIX + "0",
                          [Line(trace.MODULES_LINE, modules),
                           Line(trace.OPS_LINE, ops)])])


def test_idle_goes_to_the_innermost_span():
    pd = _synthetic()
    p, s = spans.reduce(pd), trace.reduce(pd)
    ns = {k: round(v * 1e9) for k, v in p.idle_s.items()}
    assert ns == {"bench.step": 4, "serve.step": 17, "serve.admit": 4,
                  "serve.admit.finish": 10, "serve.collect": 5,
                  "bench.wait": 20, "host.other": 5}
    # the same idle time as bench/trace.py puts down to bench.* spans
    assert sum(p.idle_s.values()) == pytest.approx(sum(s.idle_s.values()))
    assert p.busy_s == pytest.approx(s.busy_s) == pytest.approx(35e-9)
    assert p.window_s == pytest.approx(s.window_s)
    idle_share = harness.load_reader("idle_share.chat")
    assert 1 - p.busy_s / p.window_s == pytest.approx(0.65)
    assert idle_share(type("R", (), {"trace": s})()) == pytest.approx(65.0)


def test_counters_and_programs_in_the_window():
    p = spans.reduce(_synthetic())
    # the collect after the window closes does not count
    assert p.counters == {"ticks": 4, "units_run": 50, "head_passes": 9}
    ns = {k: round(v * 1e9) for k, v in p.program_s.items()}
    assert ns == {"jit_decode_megatick": 25, "jit_prefill_extend": 2,
                  "jit_concatenate": 8}
    assert round(p.span_s["serve.admit"] * 1e9) == 20


@pytest.mark.parametrize("module,name", [
    ("jit_decode_megatick(14530554794882571194)", "jit_decode_megatick"),
    ("jit__lambda(1)", "jit__lambda"),
    ("jit_f(x)", "jit_f(x)"),
])
def test_program_name_strips_the_hash(module, name):
    assert spans.program_name(module) == name


def test_innermost_of_overlapping_spans():
    # spans of two threads that overlap without nesting: the later to open
    # names the overlap
    got = spans.innermost([(0, 10, "a"), (5, 15, "b"), (7, 8, "c")])
    assert got == [(0, 5, "a"), (5, 7, "b"), (7, 8, "c"), (8, 15, "b")]
    idle = defaultdict(float)
    spans.attribute_idle([(2, 6)], got, 0, 20, idle)
    assert dict(idle) == {"a": 2, "b": 8, "c": 1, "host.other": 5}


def _readers(monkeypatch, program):
    monkeypatch.setattr(spans, "of_run", lambda run: program)
    return {n: harness.load_reader(n) for n in (
        "units_per_tick.chat", "head_passes_per_tick.chat",
        "admission_device_share.chat", "admission_device_share.batch")}


def test_readers_on_the_synthetic_trace(monkeypatch):
    r = _readers(monkeypatch, spans.reduce(_synthetic()))
    assert r["units_per_tick.chat"](None) == pytest.approx(12.5)
    assert r["head_passes_per_tick.chat"](None) == pytest.approx(2.25)
    for k in ("admission_device_share.chat", "admission_device_share.batch"):
        assert r[k](None) == pytest.approx(100 * 10 / 35)


def test_readers_read_nothing_without_the_program_spans(monkeypatch):
    """The older recorded trace names its programs jit__lambda and has no
    serve.* span: every new reader returns None, none raises."""
    old = spans.reduce(ProfileData.from_file(
        os.path.join(DATA, "tiny.xplane.pb")))
    assert old.counters == {} and "jit__lambda" in old.program_s
    for read in _readers(monkeypatch, old).values():
        assert read(None) is None
    for read in _readers(monkeypatch, None).values():
        assert read(None) is None


@pytest.fixture(scope="module")
def served():
    with gzip.open(os.path.join(DATA, "serve.xplane.pb.gz")) as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    return spans.reduce(pd), trace.reduce(pd)


def test_recorded_serving_trace_names_spans_and_programs(served):
    p, s = served
    for name in ("serve.step", "serve.dispatch", "serve.finish",
                 "serve.collect", "serve.admit", "serve.admit.chunk",
                 "serve.admit.finish"):
        assert p.span_s.get(name, 0) > 0, name
    assert p.program_s["jit_decode_megatick"] > 0
    assert p.program_s["jit_prefill_extend"] > 0
    assert sum(p.idle_s.values()) == pytest.approx(p.window_s - p.busy_s)
    assert sum(p.idle_s.values()) == pytest.approx(sum(s.idle_s.values()))
    assert p.busy_s == pytest.approx(s.busy_s)


def test_recorded_serving_trace_counts_match_the_kernels(served):
    """Every LM-head pass the program counts runs one of the two streaming
    head kernels: a megatick's passes are the draft's top-k, each verify
    and the final emit, one kernel call each."""
    p, s = served
    c = p.counters
    assert c["ticks"] > 0 and c["units_run"] >= c["ticks"]
    calls = sum(s.kernel(k)[1] for k in spans.HEAD_KERNELS)
    assert c["head_passes"] == calls
    assert s.kernel("topk_verify")[1] == c["ticks"]

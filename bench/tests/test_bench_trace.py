"""The trace reduction on a small trace recorded on a TPU v5e
(tests/record_trace.py): three steps of a matmul program and the paged
decode attention kernel inside a bench.window span, a bench.wait between
them. On that trace the device's clock reads about 1.3 ms behind the
host's, so the first step's device work falls before the window opens and
two of the three kernel calls count."""
import os

import pytest
from jax.profiler import ProfileData

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(ProfileData.from_file(DATA))


def test_window_busy_and_idle(summary):
    assert summary.chips == 1
    assert summary.window_s == pytest.approx(9757779e-9)
    # two steps' operations: 180,776 ns each, their union 361,552 ns
    assert summary.busy_s == pytest.approx(361552e-9)
    idle = summary.idle_s
    assert sum(idle.values()) == pytest.approx(summary.window_s
                                               - summary.busy_s)
    assert idle["bench.wait"] > idle["bench.step"] > idle["host.other"]


def test_kernel_time_and_calls(summary):
    seconds, calls = summary.kernel(trace.DECODE_KERNEL)
    assert calls == 2 and seconds == pytest.approx(2 * 65313e-9, rel=1e-3)
    # the two programs that ran the kernel, 79,375 and 79,133 ns
    assert summary.decode_program_s == pytest.approx(158508e-9)
    # a copy that takes the kernel's output as an operand is not the kernel
    assert any(k.startswith("copy_bitcast_fusion.1") for k in summary.op_s)


def test_breakdown_shape(summary):
    b = summary.breakdown()
    assert len(b["device_ops"]) <= trace.TOP
    assert b["device_ops"][0][0] == "convolution_reduce_fusion bf16[] fusion"
    assert [k for k, _ in b["idle_gaps"]][:2] == ["bench.wait", "bench.step"]


def test_short_names():
    text = ("%specee_paged_decode_attention.1 = bf16[4,8,1,128]{3,2,1,0:"
            "T(2,128)(2,1)S(1)} custom-call(s32[4]{0:T(128)S(1)} %b)")
    assert trace.short(text) == \
        "specee_paged_decode_attention.1 bf16[4,8,1,128] custom-call"
    assert trace.short("jit__lambda(1)") == "jit__lambda(1)"


def test_idle_split_among_spans():
    idle = {}
    from collections import defaultdict
    idle = defaultdict(float)
    busy = [(10, 20), (40, 50)]
    spans = [(0, 15, "bench.step"), (25, 35, "bench.wait")]
    trace._attribute_idle(busy, spans, 0, 60, idle)
    # gaps [0,10) [20,40) [50,60): step covers 10, wait 10, the rest other
    assert dict(idle) == {"bench.step": 10, "bench.wait": 10,
                          "host.other": 20}

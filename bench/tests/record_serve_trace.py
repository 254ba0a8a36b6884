#!/usr/bin/env python3
"""Record the small TPU trace of the serving loop that
test_bench_spans.py reduces.

    python3 bench/tests/record_serve_trace.py <out-dir>

On a TPU it builds the benchmark's DeepSeek-7B configuration cut to two
layers (batch 2, page 128, chunks of 128, megatick 2, SpecEE with the
``chat`` mix's pinned exits), warms up every shape, then traces, inside a
``bench.window`` span with ``bench.step`` around each engine step, one
request decoding while a second is admitted in three chunks, until both
finish. It leaves the ``.xplane.pb`` under ``<out-dir>``, and a gzip of
it, ``<out-dir>/serve.xplane.pb.gz``, which is the file the test reads from
``bench/tests/data/``; it prints the reductions of bench/trace.py and
bench/spans.py.
"""
import gzip
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

LAYERS = 2
SERVING = dict(max_batch=2, max_seq_len=512, page_size=128,
               prefill_chunk=128, megatick=2)
SHORT, LONG = 100, 300          # prompt lengths: one chunk, three chunks
SEED = 5


def _serve(se, prompts, news, step):
    """Submit the first prompt, step twice, submit the second, step until
    both finish."""
    reqs = [se.submit(prompts[0], max_new_tokens=news[0])]
    for _ in range(2):
        step()
    reqs.append(se.submit(prompts[1], max_new_tokens=news[1]))
    while se.busy:
        step()
    return reqs


def main(out: str) -> int:
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    from bench import sut, traffic, weights
    from bench import spans, trace
    from jax.profiler import ProfileData
    cfg = weights.load_config("deepseek-7b-l14")
    cfg = dict(cfg, num_hidden_layers=LAYERS,
               serving=dict(cfg["serving"], **SERVING))
    arch = weights.Arch(cfg)
    mix = traffic.load("chat")
    model, params, sw, _ = sut.build(arch, SEED, mix["exits"])
    se = sut.engine(model, params, sw, "specee", SERVING["megatick"])
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, arch.vocab, n).astype(np.int32)
               for n in (SHORT, LONG)]
    _serve(se, prompts, (12, 4), se.step)        # warm-up: every shape

    def step():
        with jax.profiler.TraceAnnotation("bench.step"):
            se.step()
    jax.profiler.start_trace(out, profiler_options=trace.options())
    with jax.profiler.TraceAnnotation("bench.window"):
        reqs = _serve(se, prompts, (12, 4), step)
    jax.profiler.stop_trace()
    assert all(r.done for r in reqs)
    path = trace.find_xplane(out)
    with open(path, "rb") as src, \
            gzip.GzipFile(os.path.join(out, "serve.xplane.pb.gz"), "wb",
                          mtime=0) as dst:
        shutil.copyfileobj(src, dst)
    pd = ProfileData.from_file(path)
    s, p = trace.reduce(pd), spans.reduce(pd)
    print("xplane", path, os.path.getsize(path), "bytes")
    print("trace", s.window_s, s.busy_s, s.breakdown())
    print("spans", p.counters, p.program_s, p.idle_s)
    print("engine", se.counters)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

#!/usr/bin/env python3
"""Record the small TPU trace that tests/test_bench_trace.py reduces.

    python3 bench/tests/record_trace.py <out-dir>

On a TPU it traces a few jitted steps (a matmul program and the program's
paged decode attention kernel) inside a ``bench.window`` span, with
``bench.step`` and ``bench.wait`` spans around them, and leaves the
``.xplane.pb`` under ``<out-dir>``. It also prints, for each plane, its
lines and the first few event names, which is how bench/trace.py was
checked against the real naming.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "src"))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    from repro.kernels.decode_attention.ops import paged_decode_attention_raw
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    a = jax.random.normal(k[0], (2048, 2048), jnp.bfloat16)
    mm = jax.jit(lambda x: (x @ x).sum())
    B, P, ps, H, hd = 4, 8, 128, 8, 128
    q = jax.random.normal(k[1], (B, 1, H, hd), jnp.bfloat16)
    kv = jax.random.normal(k[2], (B * P + 1, ps, H, hd), jnp.bfloat16)
    table = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
    lens = jnp.full((B,), 600, jnp.int32)
    att = jax.jit(lambda q, kv: paged_decode_attention_raw(q, kv, kv, table,
                                                           lens))
    jax.block_until_ready((mm(a), att(q, kv)))
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                jax.block_until_ready((mm(a), att(q, kv)))
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    from bench.trace import find_xplane, reduce
    from jax.profiler import ProfileData
    path = find_xplane(out)
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print("plane", plane.name)
        for line in plane.lines:
            names = [e.name for e in list(line.events)[:4]]
            print("   line", repr(line.name), names)
    s = reduce(pd)
    print("xplane", path, os.path.getsize(path), "bytes")
    print("summary", s.window_s, s.busy_s, s.breakdown(),
          s.kernel("paged_decode_attention"), s.decode_program_s)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

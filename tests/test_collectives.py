"""Tests for ``repro.runtime.collectives`` on a real (forced-host-device)
mesh: the overlapped collective matmul must equal the plain matmul
bit-for-bit in fp32, and the int8 error-feedback all-reduce must track the
exact fp32 psum within the quantization bound per step while the feedback
keeps the ACCUMULATED sum from drifting.

Both primitives are shard_map bodies, so the tests run in a subprocess with
``--xla_force_host_platform_device_count`` set before jax initializes
(tests/conftest.py pins the main process to one device).
"""
import os
import subprocess
import sys


def _run(script: str) -> str:
    # pin cpu explicitly: with libtpu installed, an unset JAX_PLATFORMS
    # makes the child spin in TPU-client discovery instead of running
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", script],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=900)
    return r.stdout + r.stderr


_COLLECTIVE_MATMUL = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.runtime import collectives as coll
from jax.sharding import AxisType

mesh = jax.make_mesh((4,), ("model",), axis_types=(AxisType.Auto,))
for rows, K, N in [(8, 16, 12), (4, 32, 32)]:
    x = jax.random.normal(jax.random.PRNGKey(0), (rows * 4, K), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
    # x arrives row-sharded; w replicated; out replicated on every device
    # (each device assembles the full (rows*4, N) product via the ring, so
    # there is no replication certificate — check_vma must be off)
    fn = jax.shard_map(
        lambda xs, ws: coll.collective_matmul_ag(xs, ws, "model"),
        mesh=mesh, check_vma=False, in_specs=(P("model", None), P(None, None)),
        out_specs=P(None, None))
    got = np.asarray(fn(x, w))
    want = np.asarray(jnp.dot(x, w, preferred_element_type=jnp.float32))
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.array_equal(got, want), np.abs(got - want).max()
print("CMATMUL-OK")
"""


def test_collective_matmul_matches_plain_matmul():
    """Ring all-gather × GEMM ≡ plain X @ w, bit-identical in fp32 (each
    row block is one un-reassociated dot either way), rows in source-rank
    order, on a 4-device 'model' ring."""
    out = _run(_COLLECTIVE_MATMUL)
    assert "CMATMUL-OK" in out, out


_COMPRESSED_PSUM = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.runtime import collectives as coll
from jax.sharding import AxisType

mesh = jax.make_mesh((4,), ("model",), axis_types=(AxisType.Auto,))
DEG, T, D = 4, 24, 64
fn = jax.shard_map(
    lambda xs, es: coll.compressed_psum(xs, "model", es),
    mesh=mesh, in_specs=(P("model", None), P("model", None)),
    out_specs=(P(None, None), P("model", None)))

key = jax.random.PRNGKey(3)
err = jnp.zeros((DEG, D), jnp.float32)
acc_q = np.zeros(D, np.float64)     # accumulated compressed reduction
acc_f = np.zeros(D, np.float64)     # accumulated exact fp32 reduction
max_amax = 0.0
for t in range(T):
    key, k = jax.random.split(key)
    x = jax.random.normal(k, (DEG, D), jnp.float32)
    # the shared scale comes from the PRE-quantization target x + err_in,
    # so capture amax before fn overwrites err with the new residual
    amax = float(np.abs(np.asarray(x) + np.asarray(err)).max())
    out, err = fn(x, err)
    out = np.asarray(out)[0]
    exact = np.asarray(jnp.sum(x, axis=0))
    max_amax = max(max_amax, amax)
    # vs plain sum(x) one step carries BOTH the fresh quantization error
    # (<= P*scale/2) and the fed-back incoming residual (<= P*scale_prev/2):
    # bound with the running-max scale. The feedback telescopes these away
    # in the accumulated sum below.
    step_bound = DEG * (max_amax / 127.0) + 1e-5
    assert np.abs(out - exact).max() <= step_bound, (
        t, np.abs(out - exact).max(), step_bound)
    acc_q += out
    acc_f += exact
# error feedback: the ACCUMULATED drift stays bounded by the single-step
# bound (residuals re-enter the next quantization instead of compounding),
# so T steps do NOT accumulate T times the error
final_bound = 2.0 * DEG * (max_amax / 127.0) + 1e-5
drift = np.abs(acc_q - acc_f).max()
assert drift <= final_bound, (drift, final_bound)
print("DRIFT", drift, "BOUND", final_bound)
print("CPSUM-OK")
"""


def test_compressed_psum_error_feedback_converges():
    """Int8 all-reduce with error feedback on a 4-device mesh: every step's
    reduction is within the quantization bound of the exact fp32 psum, and
    the accumulated sum over 24 steps drifts by O(one step's bound), not
    O(T) — the error-feedback convergence property."""
    out = _run(_COMPRESSED_PSUM)
    assert "CPSUM-OK" in out, out

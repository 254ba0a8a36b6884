"""Sharding-policy tests: spec validity (divisibility-aware fallbacks) and an
end-to-end small-mesh compile of the launch path (subprocess, 4 CPU devices).
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models.model import build_model


def _mesh11():
    from jax.sharding import AxisType
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


@pytest.mark.parametrize("arch,policy", [
    ("llama2-7b", "tp_dp"), ("command-r-plus-104b", "tp2d"),
    ("qwen3-moe-235b-a22b", "tp2d"), ("minicpm-2b", "fsdp_tp"),
    ("recurrentgemma-9b", "tp_dp"), ("mamba2-130m", "tp_dp"),
])
def test_param_specs_are_valid(arch, policy):
    """Every leaf gets a PartitionSpec whose sharded dims divide the mesh
    extent (checked against the REAL production shapes via eval_shape)."""
    from repro.sharding import param_specs
    run = get_config(arch)
    model = build_model(run)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    # fake a 16x16 mesh purely for extent lookups
    class FakeMesh:
        shape = {"data": 16, "model": 16}
    specs = param_specs(model, FakeMesh(), policy, shapes)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    flat_l = jax.tree_util.tree_leaves(shapes)
    assert len(flat_s) == len(flat_l)
    for spec, leaf in zip(flat_s, flat_l):
        assert isinstance(spec, P)
        assert len(spec) <= leaf.ndim
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is None:
                continue
            ext = int(np.prod([FakeMesh.shape[a] for a in
                               (ax if isinstance(ax, tuple) else (ax,))]))
            assert dim % ext == 0, f"{arch}: {leaf.shape} vs {spec}"


def test_odd_vocab_falls_back_to_replicated():
    """minicpm's 122753 vocab divides nothing — embedding must not shard V."""
    from repro.sharding import param_specs
    run = get_config("minicpm-2b")
    model = build_model(run)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    class FakeMesh:
        shape = {"data": 16, "model": 16}
    specs = param_specs(model, FakeMesh(), "tp_dp", shapes)
    assert tuple(specs["embed"]["tok"])[0] is None


_SMALL_MESH_COMPILE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models.model import build_model, ModelFlags
from repro.launch.specs import input_specs
from repro.launch.dryrun import step_fn_for
from repro.config import ShapeCell

from jax.sharding import AxisType

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
run = get_config("llama2-7b").smoke()
for cell, kind in [(ShapeCell("train_4k", "train", 32, 4), "train"),
                   (ShapeCell("decode_32k", "decode", 64, 4), "decode")]:
    model = build_model(run, ModelFlags(act_batch_axes="data",
                                        act_batch_extent=2))
    args, specs = input_specs(model, cell, mesh)
    fn = step_fn_for(model, run, cell, data_extent=2,
                     param_pspec=specs[0] if kind == "train" else None)
    in_sh = jax.tree_util.tree_map(
        lambda s: jax.NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    with mesh:
        compiled = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
    print(kind, "COMPILED")
print("SMALL-MESH-OK")
"""


def test_small_mesh_launch_path_compiles():
    """The dryrun flow (specs -> shardings -> lower -> compile) on a 2x2 CPU
    mesh with the smoke config — CI coverage for the at-scale path."""
    # pin cpu explicitly: with libtpu installed, an unset JAX_PLATFORMS
    # makes the child spin in TPU-client discovery instead of running
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", _SMALL_MESH_COMPILE],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=900)
    assert "SMALL-MESH-OK" in r.stdout, r.stdout + r.stderr


_SHARDED_VERIFY = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.sharding.ctx import ShardCtx
from repro.kernels.exit_gate import ops as gate_lib

key = jax.random.PRNGKey(0)
for degree in (2, 4):
    mesh = jax.make_mesh((1, degree), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.devices()[:degree])
    shard = ShardCtx.from_mesh(mesh)
    for V in (512, 509, 500):       # divisible / odd / pad-needed vocabs
        for impl in ("ref", "xla", "kernel"):
            kb, kh = jax.random.split(jax.random.fold_in(key, V))
            hn = jax.random.normal(kb, (3, 64), jnp.float32)
            w = jax.random.normal(kh, (64, V), jnp.float32)
            t0, v0 = gate_lib.verify_argmax(hn, w, impl=impl)
            t1, v1 = gate_lib.verify_argmax(hn, w, impl=impl, shard=shard)
            assert np.array_equal(np.asarray(t0), np.asarray(t1)), \\
                (degree, V, impl)
            assert np.array_equal(np.asarray(v0), np.asarray(v1)), \\
                (degree, V, impl)
            i0, x0 = gate_lib.verify_topk(hn, w, 4, impl=impl)
            i1, x1 = gate_lib.verify_topk(hn, w, 4, impl=impl, shard=shard)
            assert np.array_equal(np.asarray(i0), np.asarray(i1)), \\
                (degree, V, impl)
            assert np.array_equal(np.asarray(x0), np.asarray(x1)), \\
                (degree, V, impl)
    print("degree", degree, "OK")
# tie-break: duplicated columns force equal maxima on BOTH shards — the
# merge must still pick the lowest global id (jnp.argmax first-occurrence
# contract) for argmax and lower-index-first ordering for top-k
hn = jnp.ones((2, 8), jnp.float32)
w = jnp.tile(jax.random.normal(key, (8, 16), jnp.float32), (1, 2))
mesh = jax.make_mesh((1, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2,
                     devices=jax.devices()[:2])
shard = ShardCtx.from_mesh(mesh)
t0, _ = gate_lib.verify_argmax(hn, w, impl="ref")
t1, _ = gate_lib.verify_argmax(hn, w, impl="ref", shard=shard)
assert np.array_equal(np.asarray(t0), np.asarray(t1))
i0, _ = gate_lib.verify_topk(hn, w, 6, impl="ref")
i1, _ = gate_lib.verify_topk(hn, w, 6, impl="ref", shard=shard)
assert np.array_equal(np.asarray(i0), np.asarray(i1))
print("SHARD-VERIFY-OK")
"""


def _run_subprocess(script: str) -> str:
    # pin cpu explicitly: with libtpu installed, an unset JAX_PLATFORMS
    # makes the child spin in TPU-client discovery instead of running
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", script],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=900)
    return r.stdout + r.stderr


def test_sharded_verify_unit_parity():
    """Sharded exit-gate verify (vocab-split partial (max, argmax) / top-k +
    merge) is bit-identical — tokens AND values — to the unsharded kernels
    for every impl × TP degree {2, 4} × vocab {512, 509, 500} (509/500 force
    the padded-shard masked path), including forced cross-shard ties."""
    out = _run_subprocess(_SHARDED_VERIFY)
    assert "SHARD-VERIFY-OK" in out, out


_SHARDED_DECODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import jax
import numpy as np
from repro.api import Engine
from repro.configs import get_config
from repro.core import engine as eng
from repro.models.model import build_model
from jax.sharding import AxisType

DEGREES = %s


def build(vocab=None):
    run = get_config("llama2-7b").smoke()
    if vocab is not None:
        run = dataclasses.replace(
            run, model=dataclasses.replace(run.model, vocab_size=vocab))
    m = build_model(run)
    params = m.init(jax.random.PRNGKey(0))
    sw = eng.init_specee(m, jax.random.PRNGKey(1))
    return run, m, params, sw


def decode(run, m, params, sw, strategy, cache, mesh, K=2):
    e = Engine.create(m, params, sw, strategy=strategy, mesh=mesh)
    s = e.new_session(batch=2, max_seq=48, cache=cache)
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(7), (2, 8), 0, run.model.vocab_size))
    toks = [[s.prefill_row(b, prompts[b], max_new_tokens=10)]
            for b in range(2)]
    while not s.all_done():
        res = s.step(num_ticks=K)
        for b in range(2):
            toks[b].extend(res.row_tokens(b))
    return toks


run, m, params, sw = build()
for deg in DEGREES:
    mesh = jax.make_mesh((1, deg), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.devices()[:deg])
    for strategy in ("dense", "specee", "tree"):
        for cache in ("dense", "paged"):
            ref = decode(run, m, params, sw, strategy, cache, mesh=None)
            got = decode(run, m, params, sw, strategy, cache, mesh=mesh)
            assert got == ref, (deg, strategy, cache, got, ref)
            print("OK", deg, strategy, cache)
# non-divisible vocab (509, indivisible by any degree): padded-shard verify
# inside a real decode loop
run, m, params, sw = build(vocab=509)
mesh = jax.make_mesh((1, DEGREES[0]), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2,
                     devices=jax.devices()[:DEGREES[0]])
ref = decode(run, m, params, sw, "specee", "paged", mesh=None)
got = decode(run, m, params, sw, "specee", "paged", mesh=mesh)
assert got == ref, (got, ref)
print("ODD-VOCAB-OK")
print("SHARD-DECODE-OK")
"""


@pytest.mark.parametrize("degrees", [(2,), (4,)])
def test_sharded_decode_token_parity(degrees):
    """End-to-end TP decode parity (acceptance): a sharded Engine emits
    token-identical megatick output vs the single-device reference across
    dense/specee/tree × dense/paged, plus a non-divisible-vocab (509)
    config, at model-parallel degree 2 and 4 on forced host devices."""
    out = _run_subprocess(_SHARDED_DECODE % repr(tuple(degrees)))
    assert "SHARD-DECODE-OK" in out, out
    assert "ODD-VOCAB-OK" in out, out


_KERNELS_UNDER_MESH = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
import numpy as np
from repro.api import Engine
from repro.configs import get_config
from repro.core import engine as eng
from repro.launch.mesh import make_replica_meshes
from repro.models.model import ModelFlags, build_model

run = get_config("llama2-7b").smoke()
# every served-path kernel on: fused exit gate, verify, paged attention
m = build_model(run, ModelFlags(exit_gate_kernel=True, exit_gate_impl="kernel",
                                decode_kernel=True))
params = m.init(jax.random.PRNGKey(0))
sw = eng.init_specee(m, jax.random.PRNGKey(1))
prompts = np.asarray(jax.random.randint(
    jax.random.PRNGKey(7), (2, 8), 0, run.model.vocab_size))


def decode(strategy, mesh):
    e = Engine.create(m, params, sw, strategy=strategy, mesh=mesh)
    s = e.new_session(batch=2, max_seq=48, cache="paged")
    toks = [[s.prefill_row(b, prompts[b], max_new_tokens=4)]
            for b in range(2)]
    while not s.all_done():
        res = s.step(num_ticks=2)
        for b in range(2):
            toks[b].extend(res.row_tokens(b))
    return e, s, toks


tp2 = jax.make_mesh((1, 2), ("data", "model"),
                    axis_types=(jax.sharding.AxisType.Auto,) * 2)
for strategy in ("dense", "specee", "tree"):
    _, _, ref = decode(strategy, None)
    _, _, got = decode(strategy, tp2)
    assert got == ref, (strategy, got, ref)
    print("TP-KERNELS-OK", strategy)
# one-chip replica meshes pin each engine to its own device
_, _, ref = decode("specee", None)
for i, mesh in enumerate(make_replica_meshes(2, 1)):
    e, s, toks = decode("specee", mesh)
    state, _ = s.snapshot()
    placed = {d for leaf in jax.tree_util.tree_leaves((e.params, e.sw, state))
              for d in leaf.devices()}
    assert placed == {jax.devices()[i]}, (i, placed)
    assert toks == ref, (i, toks, ref)
print("REPLICA-PLACEMENT-OK")
# parameters drawn straight into a mesh's serving layout: the values of
# model.init, in the layout Engine places them in
from repro.sharding.serving import init_params
drawn = init_params(m, jax.random.PRNGKey(0), tp2)
placed = Engine.create(m, params, sw, strategy="dense", mesh=tp2).params
leaves = jax.tree_util.tree_leaves
for d, p, e in zip(leaves(drawn), leaves(params), leaves(placed)):
    assert d.dtype == p.dtype and np.array_equal(d, p)
    assert d.sharding.is_equivalent_to(e.sharding, d.ndim), (d.sharding,
                                                             e.sharding)
print("INIT-PARAMS-OK")
"""


def test_kernels_under_mesh_and_replica_placement():
    """Under a tensor-parallel mesh every Pallas kernel of the served path
    (fused exit gate, streaming verify, paged decode attention, tree
    predictor) runs inside a shard_map — GSPMD cannot partition a kernel —
    and decodes token-identically to the unsharded engine; a one-chip
    replica mesh keeps its engine's weights and state on its own device;
    ``init_params`` draws ``model.init``'s values in the engine's layout."""
    out = _run_subprocess(_KERNELS_UNDER_MESH)
    for strategy in ("dense", "specee", "tree"):
        assert f"TP-KERNELS-OK {strategy}" in out, out
    assert "REPLICA-PLACEMENT-OK" in out, out
    assert "INIT-PARAMS-OK" in out, out


def test_pool_partition_dims():
    """Paged attention pools shard exactly one trailing dim ('model' on the
    KV-head dim of a 5-D pool when it divides), never the page-indexed
    leading dims; scale planes and non-divisible heads stay replicated."""
    from repro.core.paged import pool_partition_dims
    # (reps, NP+1, ps, KVH, hd): KVH=4 divides 2 -> sharded
    assert pool_partition_dims((2, 9, 16, 4, 32), 2) == \
        (None, None, None, "model", None)
    # KVH=3 does not divide 2 -> hd picks it up
    assert pool_partition_dims((2, 9, 16, 3, 32), 2) == \
        (None, None, None, None, "model")
    # neither divides -> fully replicated
    assert pool_partition_dims((2, 9, 16, 3, 31), 2) == \
        (None,) * 5
    # 4-D non-attention plane: cand dims are (ps, X) but cand >= 3 fails
    # for dim 2 -> only the last dim may shard
    assert pool_partition_dims((2, 9, 16, 8), 2) == \
        (None, None, None, "model")
    # unsharded mesh: all None
    assert pool_partition_dims((2, 9, 16, 4, 32), 1) == (None,) * 5


def test_paged_partition_specs_layout():
    """``PagedKVCache.partition_specs`` shards attention pool leaves on the
    KV-head dim and replicates the page table, lengths, and non-attention
    entries — every shard must resolve the same page indirection."""
    from repro.api.cache import make_cache_manager, CacheSpec
    run = get_config("llama2-7b").smoke()
    model = build_model(run)
    mgr = make_cache_manager(model, 2, 64,
                             CacheSpec.resolve("paged", run.serve))
    cache = mgr.empty_cache()

    class FakeMesh:
        shape = {"data": 1, "model": 2}
    specs = mgr.partition_specs(cache, FakeMesh())
    assert tuple(specs["page_table"]) == ()
    assert tuple(specs["len"]) == ()
    flat, _ = jax.tree_util.tree_flatten_with_path(
        specs["segments"], is_leaf=lambda x: isinstance(x, P))
    sharded = [spec for _, spec in flat if "model" in tuple(spec)]
    assert sharded, "no pool leaf picked up the model axis"
    for _, spec in flat:
        dims = tuple(spec)
        # page-indexed leading dims (reps, pages, page_size) stay whole
        assert all(d is None for d in dims[:3]), dims


def test_hlo_collective_analyzer():
    from repro.launch.hlo_analysis import collective_totals
    txt = """
HloModule test
%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %ar = f32[8]{0} all-reduce(%x), to_apply=%add
  ROOT %t = tuple(...)
}
%cond (p: (s32[], f32[8])) -> pred[] {
  %c = s32[] constant(10)
  ROOT %cmp = pred[] compare(%i, %c), direction=LT
}
ENTRY %main (a: f32[8]) -> f32[8] {
  %ag = bf16[4,8]{1,0} all-gather(%a), dimensions={0}
  %w = (s32[], f32[8]) while(%init), condition=%cond, body=%body
  ROOT %r = f32[8] get-tuple-element(%w), index=1
}
"""
    r = collective_totals(txt, default_trip=99)
    # entry all-gather once (64 B) + loop all-reduce ×10 (32 B each)
    assert r["by_op"]["all-gather"] == 4 * 8 * 2
    assert r["by_op"]["all-reduce"] == 10 * 8 * 4
    assert r["unknown_trips"] == 0

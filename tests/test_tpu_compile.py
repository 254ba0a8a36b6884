"""Compile every served-path Pallas kernel for a TPU v5e that is described,
not attached (``jax.experimental.topologies``), at Llama2-7B widths.

Interpret mode cannot see the Mosaic tiling rules (a block's last two dims
must be multiples of (8, 128) or the array's own extent) nor the VMEM limit;
the TPU compiler installed here can. Nothing runs: each test lowers one
kernel on abstract shapes placed on the described chip and checks that the
compiled program holds a ``tpu_custom_call``. The topology is described only
inside a fixture of this file (never at import), so pytest-xdist workers
collect the same tests and only the worker given this file loads libtpu.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.decode_attention import \
    paged_decode_attention_fwd
from repro.kernels.exit_gate import ops as gate_ops
from repro.kernels.exit_gate.exit_gate import (argmax_verify_fused,
                                               exit_gate_fused,
                                               topk_verify_fused)
from repro.kernels.predictor_mlp.predictor_mlp import (predictor_mlp_fused,
                                                       predictor_mlp_fused_q)
from repro.kernels.spec_head.spec_head import spec_head_logits
from repro.quant import QTensor

# Llama2-7B serving widths (configs/llama2_7b.py): batch 8, hidden 4096,
# vocab 32000, 32 heads of 128, 128-token pages over a 2048-token row;
# SpecEE k=4 speculative tokens, predictor hidden 512, 16 exit points
B, D, V, K, H, E = 8, 4096, 32000, 4, 512, 16
HEADS, HEAD_DIM, PAGE, PAGES_PER_ROW = 32, 128, 128, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_chip(one_chip, monkeypatch):
    """Lower ``fn`` on abstract args placed on the described chip, with the
    kernels in compiled (not interpret) mode and the persistent compile
    cache off (an entry written here could not be read back without a
    chip). Returns the compiled program's text."""
    import repro.kernels
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(repro.kernels, "interpret_default", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *args):
        placed = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), args)
        return jax.jit(fn).lower(*placed).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _head(bits):
    """The (D, V) LM head in bf16, or its int8 / packed-int4 QTensor."""
    if bits is None:
        return _s((D, V), jnp.bfloat16)
    rows = D // 2 if bits == 4 else D
    return QTensor(_s((rows, V), jnp.int8), _s((V,), jnp.float32), bits)


def _bank(bits):
    """Stacked (E, ...) 2-layer predictor bank, fp32 or quantized."""
    def w(din, dout):
        if bits is None:
            return _s((E, din, dout), jnp.float32)
        rows = din // 2 if bits == 4 else din
        return QTensor(_s((E, rows, dout), jnp.int8),
                       _s((E, dout), jnp.float32), bits)
    return {"layers": [{"w": w(3 * K, H), "b": _s((E, H), jnp.float32)},
                       {"w": w(H, 1), "b": _s((E, 1), jnp.float32)}]}


HN = _s((B, D), jnp.bfloat16)
IDS = _s((B, K), jnp.int32)
PROBS = _s((B, K), jnp.float32)


def _assert_kernel(text):
    assert "tpu_custom_call" in text


def test_exit_gate_fp(compile_for_chip):
    fn = functools.partial(exit_gate_fused, block_d=512)
    _assert_kernel(compile_for_chip(
        fn, HN, _head(None), IDS, PROBS, _s((3 * K, H), jnp.float32),
        _s((H,), jnp.float32), _s((H, 1), jnp.float32),
        _s((1,), jnp.float32)))


@pytest.mark.parametrize("bits", [8, 4])
def test_exit_gate_quantized(compile_for_chip, bits):
    """The quantized gate: spec-head kernel + quantized predictor kernel."""
    fn = functools.partial(gate_ops.exit_gate, impl="kernel")
    _assert_kernel(compile_for_chip(fn, HN, _head(bits), IDS, PROBS,
                                    _bank(bits), _s((), jnp.int32)))


@pytest.mark.parametrize("bits", [None, 8, 4])
def test_argmax_verify(compile_for_chip, bits):
    _assert_kernel(compile_for_chip(
        functools.partial(argmax_verify_fused, block_v=512), HN,
        _head(bits)))


@pytest.mark.parametrize("bits", [None, 8, 4])
def test_topk_verify(compile_for_chip, bits):
    _assert_kernel(compile_for_chip(
        functools.partial(topk_verify_fused, k=K, block_v=512), HN,
        _head(bits)))


@pytest.mark.parametrize("bits", [None, 8, 4])
def test_spec_head(compile_for_chip, bits):
    _assert_kernel(compile_for_chip(spec_head_logits, HN, _head(bits), IDS))


def test_predictor_mlp(compile_for_chip):
    _assert_kernel(compile_for_chip(
        predictor_mlp_fused, _s((B, 3 * K), jnp.float32),
        _s((3 * K, H), jnp.float32), _s((H,), jnp.float32),
        _s((H, 1), jnp.float32), _s((1,), jnp.float32)))


@pytest.mark.parametrize("bits", [8, 4])
def test_predictor_mlp_quantized(compile_for_chip, bits):
    layers = _bank(bits)["layers"]
    one = jax.tree_util.tree_map(lambda s: _s(s.shape[1:], s.dtype), layers)
    _assert_kernel(compile_for_chip(
        predictor_mlp_fused_q, _s((B, 3 * K), jnp.float32), one[0]["w"],
        one[0]["b"], one[1]["w"], one[1]["b"]))


@pytest.mark.parametrize("kv_int8", [False, True])
@pytest.mark.parametrize("heads,kv_heads", [(HEADS, HEADS), (48, 4), (8, 8)],
                         ids=["mha32", "gqa48_4", "tp4_shard8"])
def test_paged_decode_attention(compile_for_chip, heads, kv_heads, kv_int8):
    """Llama2-7B's MHA, StarCoder2-15B's GQA (48 query heads on 4 KV
    heads) and one TP=4 shard of the MHA (8 local heads): each block
    layout the served paths use, with bf16 or int8 + scale pools."""
    n_pages = B * PAGES_PER_ROW
    pool = _s((n_pages, PAGE, kv_heads, HEAD_DIM),
              jnp.int8 if kv_int8 else jnp.bfloat16)
    scale = _s((n_pages, PAGE, kv_heads), jnp.float32) if kv_int8 else None

    def fn(q, k_pool, v_pool, table, lens, k_scale, v_scale):
        return paged_decode_attention_fwd(q, k_pool, v_pool, table, lens,
                                          k_scale=k_scale, v_scale=v_scale)

    _assert_kernel(compile_for_chip(
        fn, _s((B, 1, heads, HEAD_DIM), jnp.bfloat16), pool, pool,
        _s((B, PAGES_PER_ROW), jnp.int32), _s((B,), jnp.int32), scale,
        scale))


def test_paged_decode_attention_head_groups(compile_for_chip):
    """Pages too large for one step's VMEM split the KV heads into groups,
    a grid axis: 32 bf16 heads on 512-token pages take two groups."""
    from repro.kernels.decode_attention.decode_attention import _head_groups
    page, pages_per_row = 512, 4
    assert _head_groups(page, HEADS, 1, HEAD_DIM, jnp.bfloat16, jnp.bfloat16,
                        False) == 2
    pool = _s((B * pages_per_row, page, HEADS, HEAD_DIM), jnp.bfloat16)
    _assert_kernel(compile_for_chip(
        paged_decode_attention_fwd, _s((B, 1, HEADS, HEAD_DIM), jnp.bfloat16),
        pool, pool, _s((B, pages_per_row), jnp.int32), _s((B,), jnp.int32)))


@pytest.mark.parametrize("strategy", ["specee", "tree"])
def test_tp4_megatick(topo, compile_for_chip, strategy):
    """The served megatick at TP=4 on the 2x2 mesh, every kernel on (fused
    gate, streaming verify, paged attention, tree predictor): GSPMD cannot
    partition a Pallas kernel, so this compiles only with each kernel inside
    its shard_map. Two Llama2-7B layers — depth does not change the rule."""
    import dataclasses

    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.api import get_strategy
    from repro.api.cache import CacheSpec, make_cache_manager
    from repro.configs import get_config
    from repro.core import engine as eng
    from repro.models.model import ModelFlags, build_model
    from repro.sharding import policies as pol
    from repro.sharding import serving as shard_serving
    from repro.sharding.ctx import ShardCtx

    run = get_config("llama2-7b")
    run = dataclasses.replace(
        run, model=dataclasses.replace(run.model, num_layers=2))
    model = build_model(run, ModelFlags(exit_gate_kernel=True,
                                        exit_gate_impl="kernel",
                                        decode_kernel=True))
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    strat = get_strategy(strategy)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(model.init, key)
    sw = jax.eval_shape(lambda k: eng.init_specee(model, k), key)
    seq = strat.cache_seq_len(model, PAGES_PER_ROW * PAGE)
    mgr = make_cache_manager(model, B, seq, CacheSpec(kind="paged",
                                                      page_size=PAGE))
    state = jax.eval_shape(lambda: strat.empty_state(
        model, sw, B, PAGES_PER_ROW * PAGE, cache=mgr.empty_cache()))
    limits = {k: jax.ShapeDtypeStruct((B,), dt) for k, dt in (
        ("budget", jnp.int32), ("emitted", jnp.int32), ("eos", jnp.int32),
        ("done", jnp.bool_), ("retired", jnp.bool_))}
    ps, ss, _ = shard_serving.engine_shardings(model, mesh, "tp_dp", params,
                                               sw, None)
    st = pol.named(mesh, shard_serving.decode_state_specs(
        model, mesh, "tp_dp", state, cache_mgr=mgr))
    rep = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), limits)

    def placed(tree, shardings):
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, shardings)

    shard = ShardCtx.from_mesh(mesh)
    fn = jax.jit(lambda p, s, stt, lim: strat.megatick(
        model, p, s, stt, lim, 2, shard=shard), donate_argnums=(2,))
    text = fn.lower(placed(params, ps), placed(sw, ss), placed(state, st),
                    placed(limits, rep)).compile().as_text()
    _assert_kernel(text)

"""Weight-only quant subsystem (repro.quant) tests: packing round-trips,
no-mutation guarantees, fused-kernel parity against the dequantized
reference, and the acceptance-criterion token parity — a quantized engine
must emit exactly what a plain engine decoding the dequantized weights
emits, across dense/specee strategies × dense/paged caches."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import quant
from repro.api import Engine
from repro.configs import get_config
from repro.core import engine as eng
from repro.kernels.exit_gate import ops as gate_ops
from repro.kernels.exit_gate import ref as gate_ref
from repro.kernels.predictor_mlp import ops as pm_ops
from repro.kernels.spec_head import ops as sh_ops
from repro.models.model import build_model


@pytest.fixture(scope="module")
def setup():
    run = get_config("llama2-7b").smoke()
    m = build_model(run)
    params = m.init(jax.random.PRNGKey(0))
    sw = eng.init_specee(m, jax.random.PRNGKey(1))
    return run, m, params, sw


def _drain(session, first_res):
    toks = [first_res.row_tokens(b) for b in range(first_res.batch)]
    while not session.all_done():
        res = session.step()
        for b in range(res.batch):
            toks[b].extend(res.row_tokens(b))
    return toks


def _prompts(run, B=2, T=8, seed=4):
    return jax.random.randint(jax.random.PRNGKey(seed), (B, T), 0,
                              run.model.vocab_size)


# ---------------- packing / QTensor layout ----------------
def test_int4_pack_unpack_round_trip():
    codes = jax.random.randint(jax.random.PRNGKey(0), (6, 64, 16), -7, 8)
    packed = quant.pack_int4(codes)
    assert packed.dtype == jnp.int8
    assert packed.shape == (6, 32, 16)
    lo, hi = quant.unpack_int4(packed)
    round_trip = jnp.concatenate([lo, hi], axis=-2)
    np.testing.assert_array_equal(np.asarray(round_trip), np.asarray(codes))


def test_int4_pack_rejects_odd_rows():
    with pytest.raises(ValueError, match="even row count"):
        quant.pack_int4(jnp.zeros((5, 3), jnp.int32))


@pytest.mark.parametrize("bits,qmax", [(8, 127), (4, 7)])
def test_quantize_tensor_error_bound(bits, qmax):
    """Symmetric round-to-nearest: |W - dq(W)| <= scale/2 per column."""
    w = jax.random.normal(jax.random.PRNGKey(2), (64, 48))
    qt = quant.quantize_tensor(w, bits)
    assert qt.bits == bits
    assert qt.shape == w.shape
    err = np.abs(np.asarray(qt.dequantize() - w))
    bound = np.asarray(qt.scale)[None, :] / 2 + 1e-6
    assert (err <= bound).all()


def test_int4_odd_rows_falls_back_to_int8():
    qt = quant.quantize_tensor(jnp.ones((63, 8)), 4)
    assert qt.bits == 8
    assert qt.q.shape == (63, 8)


def test_take_columns_commutes_with_dequant():
    """dequant(gather) == gather(dequant) exactly (per-column scales)."""
    w = jax.random.normal(jax.random.PRNGKey(3), (32, 100))
    ids = jnp.asarray([[3, 97, 0], [50, 50, 11]], jnp.int32)
    for bits in (8, 4):
        qt = quant.quantize_tensor(w, bits)
        got = quant.take_columns(qt, ids)
        want = jnp.take(qt.dequantize(), ids, axis=-1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_qtensor_is_pytree():
    qt = quant.quantize_tensor(jnp.eye(8), 8)
    doubled = jax.tree_util.tree_map(lambda x: x, qt)
    assert isinstance(doubled, quant.QTensor) and doubled.bits == 8
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), qt, qt)
    sliced = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, 1, 0, False), stacked)
    np.testing.assert_array_equal(np.asarray(sliced.dequantize()),
                                  np.asarray(qt.dequantize()))


# ---------------- QuantSpec + params conversion ----------------
def test_quant_spec_resolve():
    assert quant.QuantSpec.resolve(None) is None
    assert quant.QuantSpec.resolve("int8").bits == 8
    assert quant.QuantSpec.resolve("int4").bits == 4
    assert quant.QuantSpec.resolve(4).bits == 4
    spec = quant.QuantSpec(bits=8, proj=False)
    assert quant.QuantSpec.resolve(spec) is spec
    with pytest.raises(ValueError):
        quant.QuantSpec.resolve("int2")
    with pytest.raises(ValueError):
        quant.QuantSpec(bits=16)


def test_quantize_params_never_mutates_originals(setup):
    """The parallel pytree must leave params and sw bit-untouched."""
    run, m, params, sw = setup
    before_p = [np.asarray(x).copy()
                for x in jax.tree_util.tree_leaves(params)]
    before_s = [np.asarray(x).copy() for x in jax.tree_util.tree_leaves(sw)]
    for spec in ("int8", "int4"):
        qw = quant.quantize_params(params, sw, spec)
        assert set(qw) == {"lm_head", "predictors", "proj"}
        assert qw["lm_head"] is not None and qw["proj"] is not None
        # building the dequantized reference must not write back either
        quant.dequantized_reference(params, sw, qw)
    for a, b in zip(before_p, jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, np.asarray(b))
    for a, b in zip(before_s, jax.tree_util.tree_leaves(sw)):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_quantize_params_selection_flags(setup):
    run, m, params, sw = setup
    qw = quant.quantize_params(
        params, sw, quant.QuantSpec(bits=8, lm_head=False, proj=False))
    assert qw["lm_head"] is None and qw["proj"] is None
    assert qw["predictors"] is not None


# ---------------- fused kernel parity vs dequantized oracle ----------------
@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("impl", ["kernel", "xla"])
def test_verify_argmax_quantized_parity(bits, impl):
    hn = jax.random.normal(jax.random.PRNGKey(5), (4, 64))
    w = jax.random.normal(jax.random.PRNGKey(6), (64, 500)) * 0.1
    qt = quant.quantize_tensor(w, bits)
    ref_tok, ref_val = gate_ref.verify_argmax_ref(hn, qt.dequantize())
    tok, val = gate_ops.verify_argmax(hn, qt, impl=impl, block_v=128)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(ref_tok))
    np.testing.assert_allclose(np.asarray(val), np.asarray(ref_val),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("impl", ["kernel", "xla"])
def test_verify_topk_quantized_parity(bits, impl):
    hn = jax.random.normal(jax.random.PRNGKey(7), (3, 64))
    w = jax.random.normal(jax.random.PRNGKey(8), (64, 500)) * 0.1
    qt = quant.quantize_tensor(w, bits)
    ref_ids, ref_vals = gate_ref.verify_topk_ref(hn, qt.dequantize(), 4)
    ids, vals = gate_ops.verify_topk(hn, qt, 4, impl=impl, block_v=128)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ref_ids))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(ref_vals),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bits", [8, 4])
def test_spec_head_quantized_parity(bits):
    hn = jax.random.normal(jax.random.PRNGKey(9), (4, 64))
    w = jax.random.normal(jax.random.PRNGKey(10), (64, 500)) * 0.1
    ids = jax.random.randint(jax.random.PRNGKey(11), (4, 3), 0, 500)
    qt = quant.quantize_tensor(w, bits)
    ref_logits, ref_probs = sh_ops.spec_head(hn, qt.dequantize(), ids)
    logits, probs = sh_ops.spec_head(hn, qt, ids)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(probs), np.asarray(ref_probs),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("B", [8, 16])
def test_quantized_kernels_at_serving_batch(B, bits):
    """The quantized verify, top-k and spec-head kernels block all B rows
    together; at the serving batches 8 and 16 they match the dequantized
    oracle like the small-B cases above."""
    hn = jax.random.normal(jax.random.PRNGKey(15), (B, 256))
    w = jax.random.normal(jax.random.PRNGKey(16), (256, 640)) * 0.1
    ids = jax.random.randint(jax.random.PRNGKey(17), (B, 4), 0, 640)
    qt = quant.quantize_tensor(w, bits)
    wd = qt.dequantize()
    tok, val = gate_ops.verify_argmax(hn, qt, impl="kernel", block_v=128)
    ref_tok, ref_val = gate_ref.verify_argmax_ref(hn, wd)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(ref_tok))
    np.testing.assert_allclose(np.asarray(val), np.asarray(ref_val),
                               rtol=1e-5, atol=1e-5)
    tids, tvals = gate_ops.verify_topk(hn, qt, 4, impl="kernel", block_v=128)
    ref_ids, ref_vals = gate_ref.verify_topk_ref(hn, wd, 4)
    np.testing.assert_array_equal(np.asarray(tids), np.asarray(ref_ids))
    np.testing.assert_allclose(np.asarray(tvals), np.asarray(ref_vals),
                               rtol=1e-5, atol=1e-5)
    logits, _ = sh_ops.spec_head(hn, qt, ids)
    ref_logits, _ = sh_ops.spec_head(hn, wd, ids)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bits", [8, 4])
def test_predictor_mlp_quantized_parity(bits):
    x = jax.random.normal(jax.random.PRNGKey(12), (4, 9))
    p = {"layers": [
        {"w": jax.random.normal(jax.random.PRNGKey(13), (9, 32)) * 0.3,
         "b": jnp.zeros((32,))},
        {"w": jax.random.normal(jax.random.PRNGKey(14), (32, 1)) * 0.3,
         "b": jnp.zeros((1,))}]}
    pq = {"layers": [{"w": quant.quantize_tensor(l["w"], bits), "b": l["b"]}
                     for l in p["layers"]]}
    pref = {"layers": [{"w": l["w"].dequantize(), "b": l["b"]}
                       for l in pq["layers"]]}
    want = pm_ops.predictor_mlp(x, pref)
    got = pm_ops.predictor_mlp(x, pq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# ---------------- engine-level token parity (acceptance criterion) -------
@pytest.mark.parametrize("strategy", ["dense", "specee"])
@pytest.mark.parametrize("cache", ["dense", "paged"])
@pytest.mark.parametrize("spec", ["int8", "int4"])
def test_engine_quant_token_parity(setup, strategy, cache, spec):
    """A quantized engine on (params, qw) decodes token-identically to a
    plain engine on the dequantized weights — across strategies and cache
    layouts. This is the subsystem's end-to-end correctness oracle: any
    drift between the fused int kernels and the fp reference shows up as a
    token mismatch here."""
    run, m, params, sw = setup
    prompts = _prompts(run, seed=21)
    e_q = Engine.create(m, params, sw=sw, strategy=strategy, quant=spec)
    pref, swref = quant.dequantized_reference(params, sw, e_q.qw)
    e_ref = Engine.create(m, pref, sw=swref, strategy=strategy)
    outs = {}
    for name, e in (("quant", e_q), ("ref", e_ref)):
        s = e.new_session(cache=cache)
        res = s.prefill(prompts, max_new_tokens=6)
        outs[name] = _drain(s, res)
    assert outs["quant"] == outs["ref"]
    assert all(len(t) == 6 for t in outs["quant"])


def test_engine_quant_leaves_params_untouched(setup):
    run, m, params, sw = setup
    before = [np.asarray(x).copy()
              for x in jax.tree_util.tree_leaves(params)]
    e = Engine.create(m, params, sw=sw, strategy="specee", quant="int4")
    s = e.new_session()
    res = s.prefill(_prompts(run, seed=22), max_new_tokens=3)
    _drain(s, res)
    for a, b in zip(before, jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_engine_quant_none_has_no_qw(setup):
    run, m, params, sw = setup
    e = Engine.create(m, params, sw=sw, strategy="specee")
    assert e.qw is None and e.quant_spec is None

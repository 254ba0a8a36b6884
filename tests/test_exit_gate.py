"""Fused exit-gate pipeline: kernel-vs-reference parity and engine
equivalence (PR: one Pallas chain for spec-head → predictor → streaming
argmax-verify in the decode hot loop)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import SpecEEConfig
from repro.configs import get_config
from repro.core import engine as eng
from repro.core import predictor as pred_lib
from repro.core.tree import TreeSpec
from repro.kernels.exit_gate import ops as gate_ops
from repro.kernels.exit_gate.ref import exit_gate_ref, verify_argmax_ref
from repro.models.model import ModelFlags, build_model


def _inputs(B, D, V, k, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    hn = jax.random.normal(keys[0], (B, D)).astype(dtype)
    W = (jax.random.normal(keys[1], (D, V)) * 0.05).astype(dtype)
    ids = jax.random.randint(keys[2], (B, k), 0, V)
    prev = jax.nn.softmax(jax.random.normal(keys[3], (B, k)))
    return hn, W, ids, prev


# ---------------- gate kernel vs oracle ----------------
# shapes cover: 128-aligned, non-128-aligned D AND V, k≠4, the tree
# path's B·N row layout (B=2 × N=13 nodes), and the serving batches 8 and
# 16 whose rows the kernels block together
GATE_SHAPES = [(4, 256, 512, 4), (3, 384, 1001, 4), (2, 320, 777, 5),
               (26, 128, 512, 4), (1, 200, 65, 3), (8, 512, 1000, 4),
               (16, 256, 640, 4)]


@pytest.mark.parametrize("B,D,V,k", GATE_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("impl", ["kernel", "xla"])
def test_exit_gate_matches_ref(B, D, V, k, dtype, impl):
    spec = SpecEEConfig(num_speculative=k, predictor_hidden=64)
    bank = pred_lib.init_predictors(spec, 6, jax.random.PRNGKey(7))
    hn, W, ids, prev = _inputs(B, D, V, k, dtype)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    for ep in (0, 5):
        p, probs, logits = gate_ops.exit_gate(hn, W, ids, prev, bank,
                                              jnp.int32(ep), impl=impl)
        pp = jax.tree_util.tree_map(lambda x: x[ep], bank)
        p_r, probs_r, logits_r = exit_gate_ref(hn, W, ids, prev, pp)
        np.testing.assert_allclose(p, p_r, atol=tol, rtol=tol)
        np.testing.assert_allclose(probs, probs_r, atol=tol, rtol=tol)
        np.testing.assert_allclose(logits, logits_r, atol=10 * tol,
                                   rtol=tol)


def test_exit_gate_non_2layer_bank_falls_back():
    """DSE banks (1- or 3-layer predictors) must still work via "kernel"."""
    for layers in (1, 3):
        spec = SpecEEConfig(num_speculative=4, predictor_hidden=32,
                            predictor_layers=layers)
        bank = pred_lib.init_predictors(spec, 3, jax.random.PRNGKey(1))
        hn, W, ids, prev = _inputs(2, 128, 256, 4)
        p, _, _ = gate_ops.exit_gate(hn, W, ids, prev, bank, jnp.int32(1),
                                     impl="kernel")
        pp = jax.tree_util.tree_map(lambda x: x[1], bank)
        p_r, _, _ = exit_gate_ref(hn, W, ids, prev, pp)
        np.testing.assert_allclose(p, p_r, atol=1e-6)


# ---------------- streaming argmax-verify vs oracle ----------------
VERIFY_SHAPES = [(4, 256, 512), (3, 384, 1001), (2, 320, 777),
                 (26, 128, 512), (1, 200, 1300), (8, 512, 1024),
                 (16, 384, 777)]


@pytest.mark.parametrize("B,D,V", VERIFY_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("impl", ["kernel", "xla"])
def test_verify_argmax_matches_ref(B, D, V, dtype, impl):
    hn, W, _, _ = _inputs(B, D, V, 4, dtype, seed=3)
    tok, mx = gate_ops.verify_argmax(hn, W, impl=impl, block_v=256)
    tok_r, mx_r = verify_argmax_ref(hn, W)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(tok_r))
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(mx, mx_r, atol=tol, rtol=tol)


def test_verify_argmax_tie_breaks_to_first():
    """Duplicate LM-head columns ⇒ duplicated max logit; the streaming
    kernel must resolve to the lowest index like jnp.argmax."""
    hn = jnp.ones((2, 128))
    W = jax.random.normal(jax.random.PRNGKey(0), (128, 300)) * 0.1
    peak = jnp.max(hn @ W, axis=-1, keepdims=False)
    # plant the same winning column at 17 and 210 (different vocab tiles)
    col = W[:, jnp.argmax((hn @ W)[0])]
    W = W.at[:, 17].set(col).at[:, 210].set(col)
    for impl in ("kernel", "xla"):
        tok, _ = gate_ops.verify_argmax(hn, W, impl=impl, block_v=128)
        ref_tok = jnp.argmax(hn @ W, axis=-1)
        np.testing.assert_array_equal(np.asarray(tok), np.asarray(ref_tok))


# ---------------- streaming top-k verify vs oracle ----------------
TOPK_SHAPES = [(4, 256, 512, 4), (3, 384, 1001, 4), (2, 320, 777, 5),
               (1, 200, 65, 3), (26, 128, 512, 4), (8, 512, 1024, 4),
               (16, 256, 777, 4)]


@pytest.mark.parametrize("B,D,V,k", TOPK_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("impl", ["kernel", "xla"])
def test_verify_topk_matches_ref(B, D, V, k, dtype, impl):
    """The streaming top-k (draft proposal path) id-matches ``jax.lax.top_k``
    on the materialized logits, including order."""
    from repro.kernels.exit_gate.ref import verify_topk_ref
    hn, W, _, _ = _inputs(B, D, V, k, dtype, seed=11)
    ids, vals = gate_ops.verify_topk(hn, W, k, impl=impl, block_v=256)
    ids_r, vals_r = verify_topk_ref(hn, W, k)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_r))
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(vals, vals_r, atol=tol, rtol=tol)


def test_verify_topk_tie_breaks_to_first():
    """Duplicate LM-head columns across vocab tiles: the running top-k must
    keep the lowest ids in jnp.top_k's order."""
    from repro.kernels.exit_gate.ref import verify_topk_ref
    hn = jnp.ones((2, 128))
    W = jax.random.normal(jax.random.PRNGKey(2), (128, 300)) * 0.1
    col = W[:, jnp.argmax((hn @ W)[0])]
    W = W.at[:, 17].set(col).at[:, 210].set(col)
    ids_r, _ = verify_topk_ref(hn, W, 4)
    for impl in ("kernel", "xla"):
        ids, _ = gate_ops.verify_topk(hn, W, 4, impl=impl, block_v=128)
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_r))


def test_propose_topk_streams_through_gate():
    """propose_topk keeps its historical numerics ("ref" impl) and id-matches
    the streaming impls under the fused flag."""
    run = get_config("llama2-7b").smoke()
    m = build_model(run)
    params = m.init(jax.random.PRNGKey(0))
    from repro.core import draft as draft_lib
    h = jax.random.normal(jax.random.PRNGKey(6), (3, run.model.d_model))
    ids, vals = draft_lib.propose_topk(m, params, h, 4)
    full = m.logits(params, h)
    np.testing.assert_array_equal(np.asarray(ids),
                                  np.asarray(jax.lax.top_k(full, 4)[1]))
    m_fused = build_model(run, ModelFlags(exit_gate_kernel=True,
                                          exit_gate_impl="kernel"))
    ids_f, _ = draft_lib.propose_topk(m_fused, params, h, 4)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_f))


# ---------------- engine equivalence ----------------
@pytest.fixture(scope="module")
def setup():
    run = get_config("llama2-7b").smoke()
    m = build_model(run)
    params = m.init(jax.random.PRNGKey(0))
    sw = eng.init_specee(m, jax.random.PRNGKey(1))
    return run, m, params, sw


def _ar_run(m, params, sw, tokens, thresh, steps):
    T = tokens.shape[1]
    first, st = eng.init_decode_state(m, params, sw, {"tokens": tokens},
                                      T + steps + 1)
    out, exits, exited = [first], [], []
    for _ in range(steps):
        tok, st, info = eng.ar_decode_step(m, params, sw, st,
                                           threshold=thresh)
        out.append(tok)
        exits.append(info.exit_point)
        exited.append(info.exited)
    return (np.asarray(jnp.stack(out, 1)), np.asarray(jnp.stack(exits, 1)),
            np.asarray(jnp.stack(exited, 1)))


@pytest.mark.parametrize("thresh", [1.5, 0.4, -0.1])
def test_ar_fused_bitwise_matches_reference(setup, thresh):
    """Emitted tokens AND exit decisions of the fused gate are identical to
    the reference four-op path (threshold>1 also re-proves the dense-greedy
    invariant under the fused flag)."""
    run, m, params, sw = setup
    m_fused = build_model(run, ModelFlags(exit_gate_kernel=True,
                                          exit_gate_impl="xla"))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 8), 0,
                                run.model.vocab_size)
    t_ref, e_ref, x_ref = _ar_run(m, params, sw, tokens, thresh, 5)
    t_fus, e_fus, x_fus = _ar_run(m_fused, params, sw, tokens, thresh, 5)
    np.testing.assert_array_equal(t_ref, t_fus)
    np.testing.assert_array_equal(e_ref, e_fus)
    np.testing.assert_array_equal(x_ref, x_fus)
    if thresh > 1.0:
        assert not x_ref.any()


def test_ar_fused_kernel_chain_in_engine(setup):
    """The full Pallas chain (interpret mode on CPU) inside the decode
    while_loop emits the same tokens as the reference."""
    run, m, params, sw = setup
    m_ker = build_model(run, ModelFlags(exit_gate_kernel=True,
                                        exit_gate_impl="kernel"))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 8), 0,
                                run.model.vocab_size)
    t_ref, e_ref, _ = _ar_run(m, params, sw, tokens, 0.4, 2)
    t_ker, e_ker, _ = _ar_run(m_ker, params, sw, tokens, 0.4, 2)
    np.testing.assert_array_equal(t_ref, t_ker)
    np.testing.assert_array_equal(e_ref, e_ker)


def _tree_run(m, params, sw, tokens, tree, thresh, steps):
    first, st = eng.init_tree_decode_state(m, params, sw,
                                           {"tokens": tokens}, 48, tree)
    outs = []
    for _ in range(steps):
        out, n, st, info = eng.tree_decode_step(m, params, sw, st, tree,
                                                threshold=thresh)
        outs.append((np.asarray(out), np.asarray(n),
                     np.asarray(info.exit_point)))
    return outs


@pytest.mark.parametrize("thresh", [1.5, 0.3])
def test_tree_fused_matches_reference(setup, thresh):
    run, m, params, sw = setup
    m_fused = build_model(run, ModelFlags(exit_gate_kernel=True,
                                          exit_gate_impl="xla"))
    tree = TreeSpec(depth=2, branch=3)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 8), 0,
                                run.model.vocab_size)
    ref = _tree_run(m, params, sw, tokens, tree, thresh, 3)
    fus = _tree_run(m_fused, params, sw, tokens, tree, thresh, 3)
    for (o1, n1, e1), (o2, n2, e2) in zip(ref, fus):
        np.testing.assert_array_equal(o1, o2)
        np.testing.assert_array_equal(n1, n2)
        np.testing.assert_array_equal(e1, e2)


def test_tree_spec_head_kernel_reachable(setup, monkeypatch):
    """Regression: tree_decode_step used to drop ``use_kernel``, so the
    spec_head Pallas kernel was silently unreachable in tree mode."""
    import repro.kernels.spec_head.ops as sh_ops
    run, m, params, sw = setup
    calls = {"n": 0}
    orig = sh_ops.spec_head

    def counting(*args, **kwargs):
        calls["n"] += 1
        return orig(*args, **kwargs)

    monkeypatch.setattr(sh_ops, "spec_head", counting)
    m_sh = build_model(run, ModelFlags(spec_head_kernel=True))
    tree = TreeSpec(depth=2, branch=3)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 8), 0,
                                run.model.vocab_size)
    _tree_run(m_sh, params, sw, tokens, tree, 0.3, 1)
    assert calls["n"] > 0


def test_banked_predictor_kernel_matches_ref():
    """apply_predictor_banked(use_kernel=True) routes the bank dynamic_index
    through the fused-MLP wrapper with identical numerics, including 3-dim
    (B, P, F) tree-path features."""
    spec = SpecEEConfig(predictor_hidden=64)
    bank = pred_lib.init_predictors(spec, 5, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, spec.feature_dim()))
    for ep in (0, 4):
        got = pred_lib.apply_predictor_banked(bank, jnp.int32(ep), x,
                                              use_kernel=True)
        ref = pred_lib.apply_predictor(
            pred_lib.predictor_at(bank, jnp.int32(ep)), x)
        assert got.shape == ref.shape == (2, 9)
        np.testing.assert_allclose(got, ref, atol=1e-6)

"""Paged KV CacheManager + chunked-prefill scheduler tests.

Property under test (PR 3 acceptance): paged-cache decode is bit-identical
to the dense-cache reference for the same prompts under dense, AR-SpecEE,
and tree strategies (including ``kv_quant``); per-row compaction frees a
retired row's span/pages; chunked prefill never stalls live decode rows for
more than one chunk budget per tick.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import CacheSpec, DenseKVCache, Engine, PagedKVCache
from repro.config import ServeConfig
from repro.configs import get_config
from repro.core import engine as eng
from repro.models.model import ModelFlags, build_model
from repro.serving import ServingEngine


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


def _prompts(run, n=3, seed=0, lo=4, hi=10):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, run.model.vocab_size, int(rng.integers(lo, hi)))
            for _ in range(n)]


def _serve(model, params, sw, prompts, max_new=5, **kw):
    se = ServingEngine(model, params, sw, **kw)
    reqs = [se.submit(p, max_new_tokens=max_new) for p in prompts]
    se.run_to_completion()
    return se, [r.output for r in reqs]


# ---------------- bit-identity: paged vs dense ----------------
@pytest.mark.parametrize("strategy", ["dense", "specee", "tree"])
def test_whole_batch_paged_matches_dense(setup, strategy):
    """Session-level property: the paged layout emits bit-identical tokens
    to the dense reference for every strategy (whole-batch prefill)."""
    run, m, params, sw = setup
    prompts = jax.random.randint(jax.random.PRNGKey(4), (2, 8), 0,
                                 run.model.vocab_size)
    outs = {}
    for cache in ("dense", "paged"):
        session = Engine.create(m, params, sw, strategy=strategy) \
            .new_session(cache=cache)
        outs[cache] = _drain(session,
                             session.prefill(prompts, max_new_tokens=6))
    assert outs["dense"] == outs["paged"]
    assert isinstance(
        Engine.create(m, params, sw, strategy=strategy)
        .new_session(batch=2, cache="paged").cache_mgr, PagedKVCache)


@pytest.mark.parametrize("strategy", ["specee", "tree"])
def test_serving_paged_matches_dense(setup, strategy):
    """Continuous-batching parity: slot admission + retirement through the
    paged manager reproduce dense serving token-for-token."""
    run, m, params, sw = setup
    prompts = _prompts(run, seed=1)
    outs = {}
    for cache in ("dense", "paged"):
        _, outs[cache] = _serve(m, params, sw, prompts, strategy=strategy,
                                cache=cache)
    assert outs["dense"] == outs["paged"]


def test_serving_paged_matches_dense_kv_quant(setup):
    """The int8 KV path reads/writes through the page table bit-identically
    (dequant∘gather == gather∘dequant)."""
    run, m, params, sw = setup
    mq = build_model(run, ModelFlags(kv_quant=True))
    prompts = _prompts(run, seed=2)
    outs = {}
    for cache in ("dense", "paged"):
        _, outs[cache] = _serve(mq, params, sw, prompts, strategy="specee",
                                cache=cache)
    assert outs["dense"] == outs["paged"]
    assert len(outs["paged"][0]) == 5


def test_tree_rejects_kv_quant(setup):
    """Tree × kv_quant is unsupported (scratch writes are full-precision);
    the strategy rejects it with a clear error instead of a tree_map crash
    inside the first step. The MESSAGE is pinned: it names the cause and
    both escape hatches, and DESIGN.md §4's support matrix cites it — a
    reworded error must update the matrix in the same change."""
    run, m, params, sw = setup
    mq = build_model(run, ModelFlags(kv_quant=True))
    with pytest.raises(ValueError) as ei:
        Engine.create(mq, params, sw, strategy="tree")
    assert str(ei.value) == (
        "tree strategy does not support kv_quant: tree scratch writes are "
        "full-precision (the node K/V is re-read within the same step, "
        "where int8 round-tripping would corrupt verification); decode "
        "with the AR engine instead (DESIGN.md §4)")


def test_paged_hybrid_arch(setup):
    """Mixed stacks: attention entries paged, recurrent entries dense —
    the manager pages only what has a sequence axis."""
    run = get_config("recurrentgemma-9b").smoke()
    m = build_model(run)
    params = m.init(jax.random.PRNGKey(0))
    sw = eng.init_specee(m, jax.random.PRNGKey(1))
    prompts = jax.random.randint(jax.random.PRNGKey(5), (2, 6), 0,
                                 run.model.vocab_size)
    outs = {}
    for cache in ("dense", "paged"):
        session = Engine.create(m, params, sw, strategy="specee") \
            .new_session(cache=cache)
        outs[cache] = _drain(session,
                             session.prefill(prompts, max_new_tokens=4))
    assert outs["dense"] == outs["paged"]


# ---------------- compaction ----------------
def test_retirement_compacts_row_span(setup):
    """A finished (long-idle) slot's attention span collapses at retirement
    and its pages return to the free list; the slot readmits cleanly."""
    run, m, params, sw = setup
    se = ServingEngine(m, params, sw, strategy="specee", cache="paged")
    mgr = se.session.cache_mgr
    short, lng = _prompts(run, n=2, seed=3)
    r_short = se.submit(short, max_new_tokens=2)
    r_long = se.submit(lng, max_new_tokens=12)
    free0 = mgr.free_pages
    while not r_short.done:
        se.step()
    # the short row retired: span zero, pages back; the long row still pays
    spans = [se.session.row_span(r) for r in range(se.B)]
    assert 0 in spans and max(spans) > 0
    assert mgr.free_pages >= mgr.num_pages - mgr.pages_per_row, \
        "retired row's pages did not return to the free list"
    se.run_to_completion()
    assert r_long.done and len(r_long.output) == 12
    assert mgr.free_pages == mgr.num_pages          # full reclamation
    assert all(se.session.row_span(r) == 0 for r in range(se.B))
    # readmission into compacted slots
    r2 = se.submit(short, max_new_tokens=3)
    se.run_to_completion()
    assert r2.done and len(r2.output) == 3


def test_admission_control_oversubscribed_pool(setup):
    """A pool with room for one row defers the second request until the
    first retires (free-page admission gate) — nothing overcommits."""
    run, m, params, sw = setup
    spec = CacheSpec(kind="paged", page_size=16,
                     num_pages=-(-run.serve.max_seq_len // 16))  # one row
    se = ServingEngine(m, params, sw, strategy="specee", cache=spec)
    a, b = _prompts(run, n=2, seed=4)
    ra, rb = se.submit(a, max_new_tokens=3), se.submit(b, max_new_tokens=3)
    se.step()
    assert len(se.pending) == 1         # b deferred: no free row reservation
    done = se.run_to_completion()
    assert len(done) == 2 and ra.done and rb.done
    assert len(ra.output) == 3 and len(rb.output) == 3


# ---------------- chunked prefill ----------------
def test_chunked_matches_blocking_admission(setup):
    """Chunked admission (chunk=4) and blocking admission emit the same
    tokens — the chunk boundary is invisible downstream."""
    run, m, params, sw = setup
    prompts = _prompts(run, seed=5, lo=6, hi=12)
    outs = {}
    for chunk in (4, 0):
        _, outs[chunk] = _serve(m, params, sw, prompts, strategy="specee",
                                cache="paged", prefill_chunk=chunk)
    assert outs[4] == outs[0]


def test_chunked_prefill_interleaves_with_decode(setup):
    """The Sarathi invariant: while decode rows are live, a tick runs at
    most one chunk budget of prefill — a long admission spans many ticks and
    the live row keeps emitting throughout."""
    run, m, params, sw = setup
    chunk = 4
    se = ServingEngine(m, params, sw, strategy="specee", cache="paged",
                       prefill_chunk=chunk)
    short = _prompts(run, n=1, seed=6)[0]
    long_prompt = np.asarray(_prompts(run, n=1, seed=7, lo=20, hi=21)[0])
    r_short = se.submit(short, max_new_tokens=16)
    se.step()                                   # admit + first decode tick
    r_long = se.submit(long_prompt, max_new_tokens=2)
    progress = []
    ticks_during_admission = 0
    while not r_long.done:
        emitted_before = len(r_short.output)
        se.step()
        if se.scheduler.last_tick_tokens:
            ticks_during_admission += 1
            assert se.scheduler.last_tick_tokens <= chunk
            # the live row kept decoding during the admission tick
            if not r_short.done:
                progress.append(len(r_short.output) - emitted_before)
    assert ticks_during_admission >= len(long_prompt) // chunk
    assert any(p > 0 for p in progress), \
        "live decode stalled during chunked admission"
    se.run_to_completion()
    assert len(r_short.output) == 16 and len(r_long.output) == 2


def test_chunked_matches_blocking_admission_kv_quant(setup):
    """kv_quant × chunked prefill: ``attend_extend`` claims kv_quant
    awareness, but only whole-batch admission exercised it — chunked
    admission must quantize each chunk's K/V identically to the blocking
    path (same tokens out, both cache layouts)."""
    run, m, params, sw = setup
    mq = build_model(run, ModelFlags(kv_quant=True))
    prompts = _prompts(run, seed=11, lo=6, hi=12)
    outs = {}
    for cache in ("dense", "paged"):
        for chunk in (4, 0):
            _, outs[(cache, chunk)] = _serve(
                mq, params, sw, prompts, strategy="specee", cache=cache,
                prefill_chunk=chunk)
    assert outs[("dense", 4)] == outs[("dense", 0)]
    assert outs[("paged", 4)] == outs[("paged", 0)]
    assert outs[("paged", 0)] == outs[("dense", 0)]


def test_chunked_prefill_dense_cache_too(setup):
    """Chunked admission is cache-layout-independent (works over the dense
    manager as well)."""
    run, m, params, sw = setup
    prompts = _prompts(run, seed=8, lo=6, hi=12)
    outs = {}
    for cache in ("dense", "paged"):
        _, outs[cache] = _serve(m, params, sw, prompts, strategy="specee",
                                cache=cache, prefill_chunk=4)
    assert outs["dense"] == outs["paged"]


def test_chunked_fallback_non_attention_arch():
    """Recurrent/SSD stacks admit with one whole-prompt chunk (DESIGN.md §4
    fallback) instead of failing."""
    run = get_config("mamba2-130m").smoke()
    m = build_model(run)
    assert not m.supports_chunked_prefill()
    params = m.init(jax.random.PRNGKey(0))
    sw = eng.init_specee(m, jax.random.PRNGKey(1))
    prompts = _prompts(run, n=2, seed=9)
    _, outs = _serve(m, params, sw, prompts, max_new=3, strategy="specee",
                     cache="paged", prefill_chunk=4)
    assert all(len(o) == 3 for o in outs)


# ---------------- config validation ----------------
def test_serve_config_page_size_validation():
    with pytest.raises(ValueError, match="page_size must be > 0"):
        ServeConfig(page_size=0)
    with pytest.raises(ValueError, match="must divide"):
        ServeConfig(max_seq_len=1000, page_size=128)
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServeConfig(prefill_chunk=-1)
    # the smoke combination (16 / 128) is the CI-exercised one
    smoke = get_config("llama2-7b").smoke().serve
    assert smoke.page_size == 16 and smoke.max_seq_len == 128
    assert smoke.max_seq_len % smoke.page_size == 0


def test_cache_spec_resolution(setup):
    run, m, params, sw = setup
    assert CacheSpec.resolve(None, run.serve).kind == "dense"
    spec = CacheSpec.resolve("paged", run.serve)
    assert spec.kind == "paged" and spec.page_size == run.serve.page_size
    assert CacheSpec.resolve(spec, run.serve) is spec
    with pytest.raises(ValueError, match="kind"):
        CacheSpec(kind="mmap")
    sess = Engine.create(m, params, sw).new_session(batch=2)
    assert isinstance(sess.cache_mgr, DenseKVCache)   # default unchanged


# ---------------- slot-math property test ----------------
def test_paged_indirection_roundtrip_property():
    """Property (hypothesis): for any page table that is a permutation
    assignment of distinct pages per row, scatter-through-table followed by
    gather-view reproduces the dense layout exactly, and per-position
    scatter/gather agree with direct indexing."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st
    from repro.core import paged as paged_lib

    @settings(max_examples=25, deadline=None)
    @given(st.data())
    def run(data):
        B = data.draw(st.integers(1, 3))
        P = data.draw(st.integers(1, 4))
        ps = data.draw(st.sampled_from([2, 4, 8]))
        extra = data.draw(st.integers(0, 3))
        NP = B * P + extra + 1
        seed = data.draw(st.integers(0, 2**31 - 1))
        rng = np.random.default_rng(seed)
        perm = rng.permutation(NP - 1)[:B * P].reshape(B, P)
        table = jnp.asarray(perm, jnp.int32)
        dense = rng.standard_normal((B, P * ps, 3)).astype(np.float32)
        pool = jnp.zeros((NP, ps, 3), jnp.float32)
        # slab-scatter the whole dense layout, then gather it back
        pos = jnp.broadcast_to(jnp.arange(P * ps)[None], (B, P * ps))
        pool = paged_lib.scatter_slab(pool, table, pos, jnp.asarray(dense))
        view = paged_lib.gather_view(pool, table)
        np.testing.assert_array_equal(np.asarray(view), dense)
        # token-scatter at arbitrary per-row positions == dense row write
        wpos = jnp.asarray(rng.integers(0, P * ps, B), jnp.int32)
        vals = rng.standard_normal((B, 3)).astype(np.float32)
        pool2 = paged_lib.scatter_token(pool, table, wpos, jnp.asarray(vals))
        dense2 = dense.copy()
        dense2[np.arange(B), np.asarray(wpos)] = vals
        np.testing.assert_array_equal(
            np.asarray(paged_lib.gather_view(pool2, table)), dense2)
        got = paged_lib.gather_positions(pool2, table, wpos)
        np.testing.assert_array_equal(np.asarray(got), vals)

    run()


# ---------------- paged decode kernel ----------------
# (KV heads, query heads, pool dtype, row lengths, dead table entries alias
# the trash page, int8 pools + scale pools, windows). Every case has B=3
# rows of P=4 pages of 16 positions (capacity 64) in a pool with one extra
# (trash) page.
PAGED_KERNEL_CASES = {
    "gqa2_shuffled": (2, 4, jnp.float32, (5, 33, 64), False, False,
                      (None, 20)),
    "mha_f32": (4, 4, jnp.float32, (0, 64, 37), False, False, (None,)),
    "gqa3_f32": (2, 6, jnp.float32, (0, 64, 37), False, False, (None,)),
    "mha_bf16": (4, 4, jnp.bfloat16, (0, 64, 37), False, False, (None,)),
    "gqa3_bf16": (2, 6, jnp.bfloat16, (0, 64, 37), False, False, (None,)),
    "trash_aliased": (2, 6, jnp.float32, (0, 17, 50), True, False,
                      (None, 20)),
    "int8_mha": (4, 4, jnp.float32, (0, 64, 37), True, True, (None,)),
    "int8_gqa3": (2, 6, jnp.float32, (9, 64, 37), True, True, (None, 20)),
}


@pytest.mark.parametrize("case", list(PAGED_KERNEL_CASES))
def test_paged_decode_kernel_matches_ref(case):
    """Page-table-aware split-KV kernel (interpret mode) vs the
    gather-then-dense-reference oracle: shuffled table, ragged lengths (an
    empty row, a row at capacity, a row ending mid-page), MHA and GQA, f32
    and bf16 pools, dead table entries aliasing the trash page, and int8
    pools + scale pools against the XLA ``kv_quant`` dequant of the same
    codes."""
    from repro.kernels.decode_attention import ops as da_ops
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    from repro.models.model import _kv_dequantize, _kv_quantize
    KVH, H, dtype, lens, trash, int8, windows = PAGED_KERNEL_CASES[case]
    B, S, hd, ps = 3, 64, 32, 16
    P = S // ps
    NP = B * P + 1
    kp = jax.random.normal(jax.random.PRNGKey(0), (NP, ps, KVH, hd))
    vp = jax.random.normal(jax.random.PRNGKey(1), (NP, ps, KVH, hd))
    q = jax.random.normal(jax.random.PRNGKey(2), (B, 1, H, hd)).astype(dtype)
    table = jax.random.permutation(
        jax.random.PRNGKey(3), NP - 1)[:B * P].reshape(B, P)
    clen = jnp.array(lens, jnp.int32)
    if trash:
        live = (clen[:, None] + ps - 1) // ps > jnp.arange(P)[None]
        table = jnp.where(live, table, NP - 1)
    scales = {}
    if int8:
        (kp, ks), (vp, vs) = _kv_quantize(kp), _kv_quantize(vp)
        scales = {"k_scale": ks, "v_scale": vs}
        kr, vr = _kv_dequantize(kp, ks, dtype), _kv_dequantize(vp, vs, dtype)
    else:
        kp, vp = kp.astype(dtype), vp.astype(dtype)
        kr, vr = kp, vp
    # bf16 cases: the reference attends the same bf16 values in f32, and
    # the kernel's output is rounded to bf16 (2^-8 relative at most)
    tol = 2e-6 if dtype == jnp.float32 else 2.0 ** -8
    empty = np.asarray(clen) == 0
    for window in windows:
        out = da_ops.paged_decode_attention(None, q, kp, vp, table, clen,
                                            window=window, **scales)
        ref = paged_decode_attention_ref(
            q.astype(jnp.float32), kr.astype(jnp.float32),
            vr.astype(jnp.float32), table, clen, window=window)
        assert out.dtype == dtype
        out = np.asarray(out, np.float32)
        # a row with no key attends nothing: the l == 0 guard gives zeros
        # (the reference's softmax over all-masked logits is uniform)
        np.testing.assert_array_equal(out[empty], 0.0)
        np.testing.assert_allclose(out[~empty], np.asarray(ref)[~empty],
                                   atol=tol, rtol=tol)


def test_paged_decode_kernel_head_groups(monkeypatch):
    """Where one page of every head would not fit a step's VMEM, the kernel
    splits the KV heads into groups, a grid axis; the result is the
    reference's. The VMEM size is shrunk so that 16 f32 heads on 16-token
    pages take two groups of 8."""
    from repro.kernels.decode_attention import decode_attention as da
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    B, P, KVH, H, hd, ps = 2, 3, 16, 32, 32, 16
    monkeypatch.setattr(da, "_VMEM_BYTES", 2**17)
    assert da._head_groups(ps, KVH, H // KVH, hd, jnp.float32, jnp.float32,
                           False) == 2
    NP = B * P + 1
    kp = jax.random.normal(jax.random.PRNGKey(0), (NP, ps, KVH, hd))
    vp = jax.random.normal(jax.random.PRNGKey(1), (NP, ps, KVH, hd))
    q = jax.random.normal(jax.random.PRNGKey(2), (B, 1, H, hd))
    table = jax.random.permutation(
        jax.random.PRNGKey(3), NP - 1)[:B * P].reshape(B, P)
    clen = jnp.array([40, 7], jnp.int32)
    out = da.paged_decode_attention_fwd(q, kp, vp, table, clen)
    ref = paged_decode_attention_ref(q, kp, vp, table, clen)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6,
                               rtol=2e-6)


def test_paged_decode_kernel_end_to_end(setup):
    """decode_kernel + paged cache serves through the page-table kernel
    (shape/flow check; numerics covered by the ref parity above)."""
    run, m, params, sw = setup
    mk = build_model(run, ModelFlags(decode_kernel=True))
    prompts = _prompts(run, n=2, seed=10)
    _, outs = _serve(mk, params, sw, prompts, max_new=3, strategy="specee",
                     cache="paged")
    assert all(len(o) == 3 for o in outs)


def test_paged_decode_kernel_kv_quant_matches_xla(setup):
    """kv_quant no longer forces the gathered-XLA fallback: the paged kernel
    consumes the int8 pools + scale pools directly (same page-table gather,
    dequant inside the tile) and reproduces the XLA kv_quant path
    token-for-token."""
    run, m, params, sw = setup
    prompts = _prompts(run, n=2, seed=12)
    outs = {}
    for decode_kernel in (False, True):
        mq = build_model(run, ModelFlags(kv_quant=True,
                                         decode_kernel=decode_kernel))
        _, outs[decode_kernel] = _serve(mq, params, sw, prompts, max_new=4,
                                        strategy="specee", cache="paged")
    assert outs[True] == outs[False]

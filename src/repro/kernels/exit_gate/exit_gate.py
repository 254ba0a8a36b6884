"""Pallas TPU kernels for the fused exit gate (see package docstring).

Every kernel blocks ALL B rows of the hidden together. Mosaic requires each
block's last two dims to be multiples of (8, 128) or the array's own extent,
so per-row ``(1, Dt)`` hidden blocks, ``(Dt, 1)`` column blocks and ``(1, 1)``
outputs cannot compile at real B. Here a ``(B, Dt)`` hidden tile times a
``(Dt, Vt)`` LM-head tile is one MXU matmul, and per-row results live in
whole ``(B, ·)`` output blocks that stay resident across the grid.

``exit_gate_fused`` — XLA gathers the k speculative LM-head columns of every
row into a (k, B, D) strip (``spec_head.column_strip``); grid (nd,) reduces
it against the hidden over D tiles into the (B, k) logits, and the LAST tile
finishes the whole gate on-chip: softmax over the k logits, Δ-features
against ``prev_probs``, the 2-layer predictor GEMM→ReLU→GEMV→sigmoid —
features and intermediates never touch HBM.

``argmax_verify_fused`` — grid (nv, nd): vocab tiles with a D-reduction
inner loop. A (B, block_v) VMEM scratch accumulates the tile's logits; when
a tile's reduction completes, each row's (max, argmax) folds into the (B, 1)
outputs. Ties resolve to the lowest index (strict-greater update across
tiles, lowest column within a tile), matching ``jnp.argmax``. HBM traffic =
one pass over the LM head; the (B, V) logits are never materialized.

``topk_verify_fused`` — the top-k sibling (draft proposal path): same grid
and tile accumulation, but each completed tile folds into a running (B, k)
top-k list via k static extract-max passes over [running, tile]. The
running list is kept in descending (value, then ascending id) order and
tiles arrive in vocab order, so an equal value resolves to the running entry
and, within a tile, to the lowest vocab index — matching ``jax.lax.top_k``
on the materialized logits exactly.

Both verify kernels also take an int8 / packed-int4 ``repro.quant.QTensor``
head: integer weight tiles plus a (1, block_v) per-column scale strip, with
the dequant folded into the accumulation. Because the scale is constant down
the contracted D axis, dot(h, q*s) == dot(h, q) * s, so each tile issues one
integer-fed fp32 matmul and a vector multiply — the fp weight never exists.
int4 uses the plane packing from repro.quant: the packed (D/2, V) byte
matrix holds row i in the low nibble and row i + D/2 in the high nibble, and
the kernel receives the SAME hidden operand twice under two index maps
(blocks d and d + nd), so the two planes contract against their own halves
of h without any in-kernel interleave.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spec_head.spec_head import (accumulate_strip, column_strip,
                                               fit_block, strip_specs)

NEG_INF = -1e30
_I32_MAX = 2**31 - 1


# ---------------------------------------------------------------------------
# gate: spec-head strip GEMM + softmax + Δ-features + predictor MLP
# ---------------------------------------------------------------------------
def _gate_kernel(h_ref, c_ref, pp_ref, w1l_ref, w1p_ref, w1d_ref, b1_ref,
                 w2_ref, b2_ref, p_ref, probs_ref, logits_ref, *, nd: int):
    d = pl.program_id(0)

    @pl.when(d == 0)
    def _init():
        logits_ref[...] = jnp.zeros_like(logits_ref)

    accumulate_strip(h_ref, c_ref, logits_ref)

    @pl.when(d == nd - 1)
    def _finish():
        logits = logits_ref[...]                               # (B, k)
        m = jnp.max(logits, axis=1, keepdims=True)
        e = jnp.exp(logits - m)
        probs = e / jnp.sum(e, axis=1, keepdims=True)
        delta = probs - pp_ref[...].astype(jnp.float32)
        # features [logits | probs | delta] @ w1, as three row blocks of w1
        # (no lane concatenation of k-wide pieces)
        pre = (jnp.dot(logits, w1l_ref[...].astype(jnp.float32),
                       preferred_element_type=jnp.float32)
               + jnp.dot(probs, w1p_ref[...].astype(jnp.float32),
                         preferred_element_type=jnp.float32)
               + jnp.dot(delta, w1d_ref[...].astype(jnp.float32),
                         preferred_element_type=jnp.float32))
        hid = jnp.maximum(pre + b1_ref[...].astype(jnp.float32), 0.0)
        out = (jnp.dot(hid, w2_ref[...].astype(jnp.float32),
                       preferred_element_type=jnp.float32)
               + b2_ref[...].astype(jnp.float32))              # (B, 1)
        p_ref[...] = jax.nn.sigmoid(out)
        probs_ref[...] = probs


def exit_gate_fused(hn: jnp.ndarray, lm_head: jnp.ndarray,
                    spec_ids: jnp.ndarray, prev_probs: jnp.ndarray,
                    w1: jnp.ndarray, b1: jnp.ndarray, w2: jnp.ndarray,
                    b2: jnp.ndarray, block_d: int = 512, shard=None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """hn: (B, D); lm_head: (D, V); spec_ids: (B, k) int32; prev_probs:
    (B, k); predictor weights w1 (3k, H), b1 (H,), w2 (H, 1), b2 (1,).
    ``shard``: optional ShardCtx — the column gather runs under GSPMD on the
    (vocab-split) head, the kernel on every device (``replicated``).

    Returns (p_exit (B,), probs (B, k), logits (B, k)), all fp32.
    """
    assert w1.shape[0] == 3 * spec_ids.shape[1], (w1.shape, spec_ids.shape)
    strip = column_strip(lm_head, spec_ids)
    call = functools.partial(_gate_call, block_d=block_d)
    if shard is not None:
        call = shard.replicated(call)
    p_exit, probs, logits = call(hn, strip, prev_probs.astype(jnp.float32),
                                 w1, b1, w2, b2)
    return p_exit[:, 0], probs, logits


def _gate_call(hn, strip, prev_probs, w1, b1, w2, b2, *, block_d: int):
    B, D = hn.shape
    k = strip.shape[0]
    H = w1.shape[1]
    nd, in_specs = strip_specs(B, k, D, block_d)

    def whole(shape):
        return pl.BlockSpec(shape, lambda d: (0,) * len(shape))

    from repro.kernels import interpret_default
    fn = pl.pallas_call(
        functools.partial(_gate_kernel, nd=nd),
        grid=(nd,),
        in_specs=in_specs + [whole((B, k)), whole((k, H)), whole((k, H)),
                             whole((k, H)), whole((1, H)), whole((H, 1)),
                             whole((1, 1))],
        out_specs=[whole((B, 1)), whole((B, k)), whole((B, k))],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, k), jnp.float32),
            jax.ShapeDtypeStruct((B, k), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret_default(),
        name="specee_exit_gate",
    )
    return fn(hn, strip, prev_probs, w1[:k], w1[k:2 * k], w1[2 * k:],
              b1.reshape(1, H), w2, b2.reshape(1, 1))


# ---------------------------------------------------------------------------
# verify: streaming LM-head argmax / top-k (never materializes (B, V) logits)
# ---------------------------------------------------------------------------
def _pick_vocab_block(V: int, block_v: int):
    """Vocab strip width and pad. Prefer a block that divides V — padding
    the LM head would copy the whole (D, V) matrix through HBM, which is
    exactly the traffic these kernels exist to avoid. Only vocabs where
    fitting degrades below a lane width (e.g. minicpm's odd 122753) take the
    pad path; padded columns are masked to NEG_INF inside the kernel."""
    fitted = fit_block(V, min(block_v, V))
    if fitted >= min(128, V):
        return fitted, 0
    block_v = min(block_v, V)
    return block_v, (-V) % block_v


def _unpack_nibbles(p):
    """int8 packed tile -> (lo, hi) int32 sign-extended nibble planes."""
    p = p.astype(jnp.int32)
    return (p << 28) >> 28, p >> 4


def _dot_f32(h_ref, w):
    return jnp.dot(h_ref[...].astype(jnp.float32), w.astype(jnp.float32),
                   preferred_element_type=jnp.float32)


def _tile_fp(h_ref, w_ref):
    return _dot_f32(h_ref, w_ref[...])


def _tile_q8(h_ref, w_ref, s_ref):
    return _dot_f32(h_ref, w_ref[...]) * s_ref[...]


def _tile_q4(hlo_ref, hhi_ref, w_ref, s_ref):
    lo, hi = _unpack_nibbles(w_ref[...])
    return (_dot_f32(hlo_ref, lo) + _dot_f32(hhi_ref, hi)) * s_ref[...]


def _tile_cols(v, tile, V: int, block_v: int):
    """Global column ids of a (B, Vt) tile and the tile with padding masked."""
    col = v * block_v + jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return col, jnp.where(col < V, tile, NEG_INF)


def _first_max(vals, ids, fill):
    """Per-row (max (B, 1), lowest id attaining it (B, 1))."""
    m = jnp.max(vals, axis=1, keepdims=True)
    return m, jnp.min(jnp.where(vals == m, ids, fill), axis=1, keepdims=True)


def _init_argmax(tok_ref, max_ref):
    tok_ref[...] = jnp.zeros_like(tok_ref)
    max_ref[...] = jnp.full_like(max_ref, NEG_INF)


def _fold_argmax(v, tile, tok_ref, max_ref, *, V, block_v):
    """Fold a finished (B, Vt) logits tile into the running (B, 1) argmax."""
    col, vals = _tile_cols(v, tile, V, block_v)
    tmax, targ = _first_max(vals, col, _I32_MAX)
    better = tmax > max_ref[...]
    tok_ref[...] = jnp.where(better, targ, tok_ref[...])
    max_ref[...] = jnp.where(better, tmax, max_ref[...])


def _init_topk(ids_ref, vals_ref):
    ids_ref[...] = jnp.zeros_like(ids_ref)
    vals_ref[...] = jnp.full_like(vals_ref, NEG_INF)


def _fold_topk(v, tile, ids_ref, vals_ref, *, V, block_v):
    """Fold a finished (B, Vt) logits tile into the running (B, k) top-k."""
    col, tile = _tile_cols(v, tile, V, block_v)
    run_v, run_i = vals_ref[...], ids_ref[...]
    k = run_v.shape[1]
    slot = jax.lax.broadcasted_iota(jnp.int32, run_v.shape, 1)
    new_v = jnp.full(run_v.shape, NEG_INF, jnp.float32)
    new_i = jnp.zeros(run_i.shape, jnp.int32)
    for j in range(k):                             # static unroll, k is tiny
        rmax, rslot = _first_max(run_v, slot, k)
        tmax, tcol = _first_max(tile, col, _I32_MAX)
        # the running list holds earlier (lower) ids: it wins equal values
        from_run = rmax >= tmax
        rid = jnp.sum(jnp.where(slot == rslot, run_i, 0), axis=1,
                      keepdims=True)
        new_v = jnp.where(slot == j, jnp.where(from_run, rmax, tmax), new_v)
        new_i = jnp.where(slot == j, jnp.where(from_run, rid, tcol), new_i)
        run_v = jnp.where(from_run & (slot == rslot), NEG_INF, run_v)
        tile = jnp.where(~from_run & (col == tcol), NEG_INF, tile)
    vals_ref[...] = new_v
    ids_ref[...] = new_i


def _stream_kernel(*refs, n_in, tile_fn, init_fn, fold_fn, nd):
    """Shared body: accumulate the (B, Vt) logits of vocab tile v over the
    D tiles, then fold the finished tile into the resident outputs."""
    ins, outs, acc_ref = refs[:n_in], refs[n_in:n_in + 2], refs[-1]
    v = pl.program_id(0)
    d = pl.program_id(1)

    @pl.when(d == 0)
    def _init_tile():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((v == 0) & (d == 0))
    def _init_outs():
        init_fn(*outs)

    acc_ref[...] += tile_fn(*ins)

    @pl.when(d == nd - 1)
    def _fold_tile():
        fold_fn(v, acc_ref[...], *outs)


def _stream_call(hn, w, block_v: int, block_d: int, width: int, init_fn,
                 fold_fn, name: str):
    """Launch a streaming verify over an fp (D, V) head or a QTensor head.
    Returns the two (B, width) outputs (int32 ids, fp32 values)."""
    B, D = hn.shape
    bits = getattr(w, "bits", None)
    q = w if bits is None else w.q
    V = q.shape[1]
    rows = q.shape[0]                          # D, or D/2 for packed int4
    assert rows * (2 if bits == 4 else 1) == D, (q.shape, D)
    block_d = fit_block(rows, block_d)
    nd = rows // block_d
    block_v, pad_v = _pick_vocab_block(V, block_v)
    assert width <= block_v, (width, block_v)
    if pad_v:
        q = jnp.pad(q, ((0, 0), (0, pad_v)))
    nv = (V + pad_v) // block_v

    h_spec = pl.BlockSpec((B, block_d), lambda v, d: (0, d))
    w_spec = pl.BlockSpec((block_d, block_v), lambda v, d: (d, v))
    if bits is None:
        tile_fn, in_specs, operands = _tile_fp, [h_spec, w_spec], (hn, q)
    else:
        # zero-scale pad columns are masked to NEG_INF by the fold
        scale = jnp.pad(w.scale.reshape(1, V), ((0, 0), (0, pad_v)))
        s_spec = pl.BlockSpec((1, block_v), lambda v, d: (0, v))
        if bits == 4:
            hi_spec = pl.BlockSpec((B, block_d),
                                   lambda v, d, nd=nd: (0, d + nd))
            tile_fn = _tile_q4
            in_specs = [h_spec, hi_spec, w_spec, s_spec]
            operands = (hn, hn, q, scale)
        else:
            tile_fn = _tile_q8
            in_specs = [h_spec, w_spec, s_spec]
            operands = (hn, q, scale)
        name = f"{name}_q{bits}"

    out_spec = pl.BlockSpec((B, width), lambda v, d: (0, 0))
    from repro.kernels import interpret_default
    fn = pl.pallas_call(
        functools.partial(_stream_kernel, n_in=len(operands),
                          tile_fn=tile_fn, init_fn=init_fn,
                          fold_fn=functools.partial(fold_fn, V=V,
                                                    block_v=block_v),
                          nd=nd),
        grid=(nv, nd),
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, width), jnp.int32),
            jax.ShapeDtypeStruct((B, width), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((B, block_v), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret_default(),
        name=name,
    )
    return fn(*operands)


def argmax_verify_fused(hn: jnp.ndarray, lm_head, block_v: int = 512,
                        block_d: int = 512
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """hn: (B, D); lm_head: (D, V) array or ``repro.quant.QTensor`` of that
    logical shape (numerics then identical to this kernel on
    ``qt.dequantize()``: fp32 accumulation, scale folded after the tile dot —
    exact because scales are per-column).

    Returns (argmax token (B,) int32, max logit (B,) fp32) with fp32
    accumulation, reading the LM head exactly once.
    """
    tok, mx = _stream_call(hn, lm_head, block_v, block_d, 1, _init_argmax,
                           _fold_argmax, "specee_argmax_verify")
    return tok[:, 0], mx[:, 0]


def topk_verify_fused(hn: jnp.ndarray, lm_head, k: int, block_v: int = 512,
                      block_d: int = 512
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """hn: (B, D); lm_head: (D, V) array or QTensor; k: static top-k width.

    Returns (ids (B, k) int32, vals (B, k) fp32) sorted by descending
    logit (ties: ascending vocab id), with fp32 accumulation, reading the
    LM head exactly once and never materializing the (B, V) logits.
    """
    V = lm_head.shape[-1]
    assert k <= V, (k, V)
    return _stream_call(hn, lm_head, block_v, block_d, k, _init_topk,
                        _fold_topk, "specee_topk_verify")

"""Plain float32 reference of the architectures under bench/configs/.

Written from the published descriptions, not from the program: pre-norm
decoder layers (RMSNorm for Llama-style DeepSeek-LLM, LayerNorm with bias
for StarCoder2), rotary embeddings on the two halves of each head, grouped
keys and values, a SwiGLU MLP (DeepSeek-LLM) or a GELU MLP with biases
(StarCoder2), a final norm and an untied LM head. Every matmul runs at
"highest" precision, so on a TPU it is float32 and not a bf16 pass.

The reference is run once over each compared request: its prompt followed
by the tokens that were served. A served token's position may carry a depth
below the model's: SpecEE emitted it after that many layers, and the layers
it skipped saw its hidden state frozen at the exit (their keys and values
are projections of that state). The reference applies the same rule, then
returns the logits that predicted each served token.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import Arch, draw_layer, draw_outer, root_key



def _norm(arch: Arch, x, scale, bias):
    if arch.layernorm:
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + arch.eps) * scale + bias
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                        + arch.eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2
                          / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _act(arch: Arch, x):
    if arch.act == "silu":
        return x * jax.nn.sigmoid(x)
    if arch.act == "gelu_pytorch_tanh":
        return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                          * (x + 0.044715 * x ** 3)))
    raise ValueError(arch.act)


def _rows_int8(x):
    """Symmetric int8 rounding with one scale per row (per token)."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-30
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def layer(arch: Arch, w, h, depth, index, int8: bool = False):
    """One decoder layer over a whole sequence. h: (T, D) float32; depth:
    (T,) layers each position runs; index: this layer's number. Positions
    whose depth is at most ``index`` keep their hidden state. ``int8``
    rounds every matmul input to int8: each projection's input per token,
    queries, keys and values per head vector, attention weights per row
    (the control, whose weights ``lowered`` rounds per column)."""
    T = h.shape[0]
    q8 = _rows_int8 if int8 else (lambda x: x)
    pos = jnp.arange(T)
    zero = jnp.zeros((), jnp.float32)
    b = (lambda n: w[n]) if arch.bias else (lambda n: zero)
    x = _norm(arch, h, w["ln1_scale"],
              w["ln1_bias"] if arch.layernorm else zero)
    x = q8(x)
    q = (x @ w["wq"] + b("bq")).reshape(T, arch.heads, arch.hd)
    k = (x @ w["wk"] + b("bk")).reshape(T, arch.kv_heads, arch.hd)
    v = (x @ w["wv"] + b("bv")).reshape(T, arch.kv_heads, arch.hd)
    q, k = _rope(q, pos, arch.theta), _rope(k, pos, arch.theta)
    q, k, v = q8(q), q8(k), q8(v)
    rep = arch.heads // arch.kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(arch.hd)
    mask = pos[None, :] <= pos[:, None]
    if arch.window:
        mask &= pos[None, :] > pos[:, None] - arch.window
    p = q8(jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1))
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(T, -1)
    h2 = h + q8(o) @ w["wo"] + b("bo")
    x = _norm(arch, h2, w["ln2_scale"],
              w["ln2_bias"] if arch.layernorm else zero)
    x = q8(x)
    up = x @ w["w_up"] + b("b_up")
    up = _act(arch, x @ w["w_gate"]) * up if arch.gated else _act(arch, up)
    h2 = h2 + q8(up) @ w["w_down"] + b("b_down")
    return jnp.where((depth > index)[:, None], h2, h)


def _cols_int8(w):
    """Symmetric int8 rounding with one scale per output column."""
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0 + 1e-30
    return jnp.clip(jnp.round(w / s), -127, 127) * s


MATRICES = ("wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down", "lm_head")


def lowered(w: dict) -> dict:
    """The control's weights: every projection and the LM head in int8."""
    return {n: _cols_int8(x) if n in MATRICES else x for n, x in w.items()}


def gaps(arch: Arch, outer, h, nxt, pick=None):
    """Per position: how far the logit of the chosen token lies below the
    position's best logit (0 where it is the argmax). The chosen token is
    the next one of the sequence, or the argmax of ``pick`` (the logits of
    the control) where given."""
    z = logits(arch, outer, h)
    tok = nxt if pick is None else jnp.argmax(pick, -1)
    return jnp.max(z, -1) - jnp.take_along_axis(z, tok[:, None], -1)[:, 0]


def logits(arch: Arch, outer, h, int8: bool = False):
    x = _norm(arch, h, outer["final_scale"],
              outer["final_bias"] if arch.layernorm else 0.0)
    return (_rows_int8(x) if int8 else x) @ outer["lm_head"]


def served_gaps(arch: Arch, seed: int, seqs: Sequence[np.ndarray],
                depths: Sequence[np.ndarray], starts: Sequence[int],
                control: bool = False):
    """For each sequence (its prompt then its served tokens) the gap of
    every served token: positions ``start`` .. len-2 predicted them.
    ``depths`` gives each position's layer count; the weights are drawn
    again from ``seed``, a layer at a time. Every sequence is padded to the
    served context (``max_seq_len``), so each program compiles once.

    With ``control`` also the control's gaps: the same positions computed
    in int8 (every matmul's operands, see ``layer``), and the token it puts
    first read against the float32 logits. Returns
    (gaps, control gaps or None)."""
    key = root_key(seed)
    to32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: x.astype(jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        get_layer = jax.jit(lambda l: to32(draw_layer(arch, key, l)))
        run_layer = jax.jit(lambda w, h, d, i: layer(arch, w, h, d, i))
        run_int8 = jax.jit(lambda w, h, d, i: layer(arch, lowered(w), h, d,
                                                    i, int8=True))
        outer = jax.jit(lambda: to32(draw_outer(arch, key)))()
        hs, ds, nx = [], [], []
        for toks, dep in zip(seqs, depths):
            T = arch.cfg["serving"]["max_seq_len"]
            t = np.zeros(T, np.int32)
            t[:len(toks)] = toks
            d = np.zeros(T, np.int32)
            d[:len(dep)] = dep
            hs.append(outer["embed"][jnp.asarray(t)])
            ds.append(jnp.asarray(d))
            nx.append(jnp.asarray(np.concatenate([t[1:], [0]])))
        hq = list(hs) if control else None
        for i in range(arch.layers):
            w = get_layer(i)
            hs = [run_layer(w, h, d, jnp.int32(i)) for h, d in zip(hs, ds)]
            if control:
                hq = [run_int8(w, h, d, jnp.int32(i))
                      for h, d in zip(hq, ds)]
            del w
        gap = jax.jit(lambda o, h, n: gaps(arch, o, h, n))
        out = [np.asarray(gap(outer, h, n))[s:len(toks) - 1]
               for h, n, toks, s in zip(hs, nx, seqs, starts)]
        if not control:
            return out, None
        cgap = jax.jit(lambda o, h, q, n: gaps(
            arch, o, h, n, pick=logits(arch, lowered(o), q, int8=True)))
        ctl = [np.asarray(cgap(outer, h, q, n))[s:len(toks) - 1]
               for h, q, n, toks, s in zip(hs, hq, nx, seqs, starts)]
        return out, ctl

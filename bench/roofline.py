"""Operations and bytes that the algorithm needs, from shapes: the
yardstick of every roofline share and of the model step's share of peak.
Bytes are bf16 weights, activations and keys/values (2 bytes each).
"""
from __future__ import annotations

from typing import Tuple

from bench.weights import Arch

BF16 = 2


def paged_attention(arch: Arch, context: float, rows: int
                    ) -> Tuple[float, float]:
    """One decode-attention call of one layer over ``rows`` query rows
    whose contexts hold ``context`` positions in all: every key and value
    read once, the queries read and the outputs written."""
    q = arch.heads * arch.hd
    flops = 4.0 * context * q
    kv = 2.0 * context * arch.kv_heads * arch.hd * BF16
    return flops, kv + 2.0 * rows * q * BF16


def lm_head_pass(arch: Arch, rows: int) -> Tuple[float, float]:
    """One pass of ``rows`` hidden states over the whole LM head (the
    argmax and top-k verify kernels): the head read once."""
    flops = 2.0 * rows * arch.d * arch.vocab
    return flops, (arch.d * arch.vocab + rows * arch.d) * BF16


def layer_matmul_params(arch: Arch) -> int:
    q, kv = arch.heads * arch.hd, arch.kv_heads * arch.hd
    mlp = (3 if arch.gated else 2) * arch.d * arch.f
    return 2 * arch.d * q + 2 * arch.d * kv + mlp


def decode_token_flops(arch: Arch, context: float) -> float:
    """One decoded token at full configured depth and one LM-head pass,
    attending over ``context`` positions."""
    per_layer = 2.0 * layer_matmul_params(arch) + \
        4.0 * context * arch.heads * arch.hd
    return arch.layers * per_layer + 2.0 * arch.d * arch.vocab


def share(flops: float, nbytes: float, seconds: float, peaks: dict
          ) -> float:
    """Least time the chip could take, over the time taken, in %."""
    least = max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds

"""Pallas TPU kernels for SpecEE's compute hot spots.

Each kernel package ships three files:
  <name>.py — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (auto-selects interpret mode off-TPU)
  ref.py    — pure-jnp oracle used by the allclose tests

Kernels:
  spec_head        — the paper's custom operator (§6.2), TPU-adapted: fused
                     gather of LM-head columns for the speculative ids +
                     per-row (1×D)·(D×k) MXU matmul. Replaces the CUDA
                     cutlass/MegaBlocks group-GEMM with one dense row-batched
                     kernel (tree nodes = rows).
  predictor_mlp    — fused 2-layer MLP predictor (T1), one HBM round-trip.
  exit_gate        — the fused exit-gate pipeline: spec-head features +
                     predictor MLP in one kernel, plus the streaming LM-head
                     argmax-verify kernel (never materializes (B, V) logits).
  flash_attention  — blocked causal/windowed flash attention (prefill path).
  decode_attention — split-KV (flash-decoding) attention for 32k/500k decode.
"""


def on_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def interpret_default() -> bool:
    """Pallas interpret mode: True off-TPU (CPU CI), False on real hardware."""
    return not on_tpu()


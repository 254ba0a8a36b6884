"""Canonical result type of the unified decode API.

Every decode strategy — dense full-depth, AR SpecEE, tree speculative —
emits the SAME shape of result per step. This is the API-level expression of
the paper's merged-mapping insight ("different decoding methods share the
same essential characteristics"): a 1-token AR emit is just a tree emit with
``counts == 1``, so the serving engine, the launchers, and every example can
drive all three modes through one loop.
"""
from __future__ import annotations

from typing import Any, NamedTuple


class StepResult(NamedTuple):
    """One decode tick for every row of the session batch.

    The token buffer is FIXED-WIDTH (``W = strategy.emit_width``, e.g. 1 for
    dense/AR, tree depth + 1 for tree mode) with a per-row valid count —
    static shapes under jit, ragged semantics on top.

    A MEGATICK result (``DecodeSession.step(num_ticks=K)`` with K > 1, or any
    ``step_async``) widens the same contract to K device ticks: ``tokens`` is
    (B, K·W) with ``counts`` the per-row total across the megatick, and the
    per-tick stat fields become (B, K) planes with ``tick_live`` marking
    which ticks each row was live for (``ticks`` is how many device ticks
    actually ran — the loop early-exits once every row is done). Single-tick
    results keep the historical (B,) stat shapes with the trailing fields at
    their defaults, so existing consumers are untouched; tick-aware consumers
    use ``row_exit_points``/``row_accept_lens``, which handle both shapes.
    """
    tokens: Any        # (B, W) int32 — left-aligned emitted tokens
    #                     (megatick: (B, K*W), still left-aligned per row)
    counts: Any        # (B,)   int32 — valid tokens this tick (0 for a done
    #                     row once the session truncates it)
    done: Any          # (B,)   bool  — row finished (eos / budget); always
    #                     False from a raw strategy step, filled in by the
    #                     session's bookkeeping (host-side for single steps,
    #                     the device-resident carry for megaticks)
    exit_layer: Any    # (B,)   int32 — exit point taken (E if full depth)
    #                     (megatick: (B, K) per-tick plane)
    accept_len: Any    # (B,)   int32 — accepted draft tokens (tree mode;
    #                     0 for dense/AR) (megatick: (B, K))
    exited: Any        # (B,)   bool  — predictor-driven early exit happened
    #                     (megatick: (B, K))
    units_run: Any     # ()     int32 — units the layer loop executed
    #                     (megatick: summed over the ticks that ran)
    head_passes: Any   # ()     int32 — full LM-head passes: the draft's
    #                     top-k, each verify, the final emit (megatick: summed)
    ticks: Any = 1     # ()     int   — device ticks folded into this result
    tick_counts: Any = None   # (B, K) int32 — kept tokens per tick
    #                     (megatick only; None for single-tick results)
    tick_live: Any = None     # (B, K) bool — row live entering each tick
    #                     (megatick only; None for single-tick results)

    @property
    def batch(self) -> int:
        return self.tokens.shape[0]

    @property
    def width(self) -> int:
        return self.tokens.shape[1]

    @property
    def is_megatick(self) -> bool:
        """Whether the per-tick stat fields are (B, K) planes."""
        return self.tick_live is not None

    def row_tokens(self, row: int):
        """Host-side convenience: the valid tokens of one row as a list."""
        n = int(self.counts[row])
        return [int(t) for t in self.tokens[row, :n]]

    def row_exit_points(self, row: int):
        """Exit layer per live tick of one row (a 1-element list for a
        single-tick result — the historical per-step consumer contract)."""
        if not self.is_megatick:
            return [int(self.exit_layer[row])]
        return [int(self.exit_layer[row, t]) for t in range(int(self.ticks))
                if bool(self.tick_live[row, t])]

    def row_accept_lens(self, row: int):
        """Accepted draft length per live tick of one row (see
        ``row_exit_points``)."""
        if not self.is_megatick:
            return [int(self.accept_len[row])]
        return [int(self.accept_len[row, t]) for t in range(int(self.ticks))
                if bool(self.tick_live[row, t])]

"""Paper §7.4.4 + Fig. 8: predictor runtime overhead and design-space
exploration (layers × hidden), plus Fig. 18 (training-data fraction), plus
the fused-vs-unfused exit-gate A/B (PR: fused exit-gate pipeline), which
records ``BENCH_exit_gate.json`` at the repo root so the perf trajectory of
the decode hot loop is tracked across PRs.

``BENCH_exit_gate.json`` schema — an object of named row-groups:
  * ``gate_ab``       — fused-vs-unfused gate timing + analytic ``hbm_bytes``
                        per (B, D, V, k) shape (this module);
  * ``quant_verify``  — fp vs int8/int4 streaming-verify timing; the
                        quantized rows carry ``wbits`` and their
                        ``hbm_bytes`` shrink with the weight width (this
                        module);
  * ``quant_pareto``  — quant level × exit threshold speed/quality sweep
                        (``bench_ablation.quant_pareto``).
A legacy top-level list is read back as the ``gate_ab`` group.

    python -m benchmarks.bench_predictor              # everything
    python -m benchmarks.bench_predictor --gate-only  # just the gate A/B
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Timer, get_bundle, token_batches
from repro.config import SpecEEConfig
from repro.core import features as feat_lib
from repro.core import predictor as pred_lib
from repro.core import predictor_training as pt
from repro.kernels.exit_gate import ops as gate_ops


def _time(fn, *args, iters: int = 50) -> float:
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run(timer: Timer) -> None:
    b = get_bundle()
    m, params, sw = b.model, b.params, b.sw
    spec = b.run.specee
    B = 8
    feats = jax.random.normal(jax.random.PRNGKey(0),
                              (B, spec.feature_dim()))

    # predictor runtime vs one decoder unit runtime (paper: 5.6% of token)
    pp = pred_lib.predictor_at(sw.predictors, jnp.int32(0))
    t_pred = _time(jax.jit(lambda f: pred_lib.apply_predictor(pp, f)), feats)
    cache = m.empty_cache(B, 32)
    h = jax.random.normal(jax.random.PRNGKey(1), (B, b.run.model.d_model))
    t_unit = _time(jax.jit(
        lambda hh: m.run_unit(params, 0, jnp.int32(0), hh,
                              cache["segments"][0], cache["len"])[0]), h)
    timer.add("predictor/runtime", t_pred * 1e6,
              f"unit={t_unit*1e6:.0f}us ratio={t_pred/t_unit:.3f}")

    # Fig. 8 DSE: layers × hidden
    batches = token_batches(b.run, 2)
    data = pt.collect_dataset(m, params, sw.draft, batches)
    for layers in (1, 2, 3):
        for hidden in (128, 512, 1024):
            s = SpecEEConfig(predictor_layers=layers, predictor_hidden=hidden)
            p, met = pt.train_predictors(s, data, jax.random.PRNGKey(3),
                                         steps=150)
            one = pred_lib.predictor_at(p, jnp.int32(0))
            t = _time(jax.jit(
                lambda f: pred_lib.apply_predictor(one, f)), feats, iters=20)
            timer.add(f"predictor/dse_L{layers}_H{hidden}", t * 1e6,
                      f"acc={met['accuracy']:.3f}")

    # Fig. 18: training-data fraction vs accuracy
    E, T, F = data.features.shape
    for frac in (0.02, 0.1, 0.5, 1.0):
        n = max(8, int(T * frac))
        sub = pt.FeatureDataset(features=data.features[:, :n],
                                labels=data.labels[:, :n])
        _, met = pt.train_predictors(b.run.specee, sub,
                                     jax.random.PRNGKey(4), steps=150)
        timer.add(f"predictor/data_frac_{frac}", 0.0,
                  f"acc={met['accuracy']:.3f} n={n}")


# ---------------------------------------------------------------------------
# fused-vs-unfused exit-gate A/B
# ---------------------------------------------------------------------------
# (B, D, V, k): engine smoke scale, a 7B-ish decode shape, a 70B-ish one
GATE_SHAPES = [(8, 128, 512, 4), (4, 1024, 16000, 4), (8, 2048, 32000, 4)]

_GATE_JSON = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_exit_gate.json")


def _ab_time(fn_a, fn_b, args, iters: int = 5, rounds: int = 24):
    """Interleaved A/B timing, min over many short rounds — shared-machine
    noise bursts hit both paths symmetrically and the minimum converges to
    the quiet-machine cost instead of biasing whichever ran second."""
    fn_a(*args)
    fn_b(*args)  # compile both first
    best_a = best_b = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn_a(*args)
        jax.block_until_ready(out)
        best_a = min(best_a, (time.perf_counter() - t0) / iters)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn_b(*args)
        jax.block_until_ready(out)
        best_b = min(best_b, (time.perf_counter() - t0) / iters)
    return best_a, best_b


def _gate_bytes(B, D, V, k, wbytes=4):
    """Analytic per-exit-point HBM traffic (see kernels/exit_gate docstring).
    ``wbytes``: bytes per LM-head weight (4 fp32, 1 int8, 0.5 packed int4);
    quantized heads also stream their fp32 per-column scale row."""
    scales = V * 4 if wbytes < 4 else 0
    gather = k * D * wbytes
    head = D * V * wbytes + scales
    logits_round_trips = 3 * B * V * 4      # write + read + argmax read
    return {"unfused": gather + head + logits_round_trips,
            "fused": gather + head}


def bench_exit_gate(timer: Timer) -> list:
    """Per-exit-point wall time: the engine's historical four separately
    dispatched XLA ops vs. ONE call through the fused ``exit_gate`` +
    ``verify_argmax`` entry points (auto impl: Pallas on TPU, fused-XLA on
    CPU). The Pallas chain itself is additionally timed in interpret mode at
    the smoke shape as a correctness-path datapoint, not a perf claim."""
    rows = []
    for B, D, V, k in GATE_SHAPES:
        spec = SpecEEConfig(num_speculative=k)
        bank = pred_lib.init_predictors(spec, 12, jax.random.PRNGKey(0))
        hn = jax.random.normal(jax.random.PRNGKey(1), (B, D))
        lm_w = jax.random.normal(jax.random.PRNGKey(2), (D, V)) * 0.05
        ids = jax.random.randint(jax.random.PRNGKey(3), (B, k), 0, V)
        prev = jnp.full((B, k), 1.0 / k)
        ep = jnp.int32(3)

        # unfused: the pre-PR decode-loop sequence, one dispatch per stage
        f_feat = jax.jit(lambda hn, w, i, p: feat_lib.extract_features(
            hn, w, i, p))
        f_pred = jax.jit(lambda bk, e, ft: pred_lib.apply_predictor(
            pred_lib.predictor_at(bk, e), ft))
        f_logits = jax.jit(lambda hn, w: (hn @ w.astype(hn.dtype))
                           .astype(jnp.float32))
        f_verify = jax.jit(lambda gl, i: (
            jnp.argmax(gl, -1).astype(jnp.int32),
            jnp.any(jnp.argmax(gl, -1)[:, None] == i, 1)))

        def unfused(hn, lm_w, ids, prev, bank, ep):
            feats, probs = f_feat(hn, lm_w, ids, prev)
            p_exit = f_pred(bank, ep, feats)
            glogits = f_logits(hn, lm_w)
            tok, hit = f_verify(glogits, ids)
            return p_exit, probs, tok, hit

        @jax.jit
        def fused(hn, lm_w, ids, prev, bank, ep):
            p_exit, probs, _ = gate_ops.exit_gate(hn, lm_w, ids, prev,
                                                  bank, ep)
            tok, _ = gate_ops.verify_argmax(hn, lm_w)
            return p_exit, probs, tok, jnp.any(tok[:, None] == ids, 1)

        t_unfused, t_fused = _ab_time(unfused, fused,
                                      (hn, lm_w, ids, prev, bank, ep))
        row = {"B": B, "D": D, "V": V, "k": k,
               "unfused_us": t_unfused * 1e6, "fused_us": t_fused * 1e6,
               "speedup": t_unfused / t_fused,
               "hbm_bytes": _gate_bytes(B, D, V, k),
               "backend": jax.default_backend()}
        # the Pallas chain itself, for EVERY sweep row (off-TPU it runs in
        # interpret mode — a correctness-path datapoint, so the big shapes
        # use few iterations; on TPU this is the headline column)
        @jax.jit
        def fused_kernel(hn, lm_w, ids, prev, bank, ep):
            p_exit, probs, _ = gate_ops.exit_gate(
                hn, lm_w, ids, prev, bank, ep, impl="kernel")
            tok, _ = gate_ops.verify_argmax(hn, lm_w, impl="kernel")
            return p_exit, probs, tok
        kiters = 10 if (B, D, V, k) == GATE_SHAPES[0] or \
            jax.default_backend() == "tpu" else 1
        row["fused_kernel_us"] = _time(
            fused_kernel, hn, lm_w, ids, prev, bank, ep, iters=kiters) * 1e6
        rows.append(row)
        timer.add(f"exit_gate/B{B}_D{D}_V{V}", row["fused_us"],
                  f"unfused={row['unfused_us']:.1f}us "
                  f"speedup={row['speedup']:.2f}x")
    from benchmarks.common import merge_bench_json
    merge_bench_json(_GATE_JSON, "gate_ab", rows)
    return rows


def bench_quant_verify(timer: Timer) -> list:
    """fp vs int8/int4 streaming verify at each gate shape.

    Times the streaming impl the platform actually uses (Pallas kernel on
    TPU, XLA scan off-TPU) with the fp LM head against the quantized one;
    the quantized rows' analytic ``hbm_bytes`` shrink with the weight width
    (int8 ≈ 4×, packed int4 ≈ 8× less head traffic plus the fp32 scale
    row) — the memory-bound decode win the fused dequant buys. Written to
    the ``quant_verify`` row-group of ``BENCH_exit_gate.json``."""
    from benchmarks.common import merge_bench_json
    from repro.kernels import on_tpu
    from repro.quant import quantize_tensor

    impl = "kernel" if on_tpu() else "xla"
    rows = []
    for B, D, V, k in GATE_SHAPES:
        hn = jax.random.normal(jax.random.PRNGKey(1), (B, D))
        lm_w = jax.random.normal(jax.random.PRNGKey(2), (D, V)) * 0.05
        f_fp = jax.jit(lambda h, w: gate_ops.verify_argmax(h, w, impl=impl))
        for bits, wbytes in ((8, 1), (4, 0.5)):
            qt = quantize_tensor(lm_w, bits)
            f_q = jax.jit(lambda h, q: gate_ops.verify_argmax(h, q,
                                                              impl=impl))
            t_fp, t_q = _ab_time(lambda h: f_fp(h, lm_w),
                                 lambda h: f_q(h, qt), (hn,),
                                 iters=5, rounds=8)
            bytes_fp = _gate_bytes(B, D, V, k)
            bytes_q = _gate_bytes(B, D, V, k, wbytes=wbytes)
            rows.append({"B": B, "D": D, "V": V, "k": k, "wbits": bits,
                         "impl": impl,
                         "verify_fp_us": t_fp * 1e6,
                         "verify_q_us": t_q * 1e6,
                         "hbm_bytes_fp": bytes_fp,
                         "hbm_bytes": bytes_q,
                         "hbm_reduction":
                             bytes_fp["fused"] / bytes_q["fused"],
                         "backend": jax.default_backend()})
            timer.add(f"quant_verify/D{D}_V{V}_q{bits}", t_q * 1e6,
                      f"fp={t_fp*1e6:.1f}us "
                      f"hbm={bytes_fp['fused']/bytes_q['fused']:.2f}x less")
    merge_bench_json(_GATE_JSON, "quant_verify", rows)
    return rows


# ---------------------------------------------------------------------------
# CI perf gate over the committed BENCH_exit_gate.json (ROADMAP item 5)
# ---------------------------------------------------------------------------
def _load_groups() -> dict:
    if not os.path.exists(_GATE_JSON):
        return {}
    with open(_GATE_JSON) as f:
        data = json.load(f)
    if isinstance(data, list):          # legacy layout: bare gate_ab rows
        data = {"gate_ab": data}
    return data


def _fused_gate_time(B, D, V, k, iters=5, rounds=6) -> float:
    """Re-measure ONLY the fused path of ``bench_exit_gate`` (the gate's hot
    column) at a committed shape — min over short rounds, same estimator as
    ``_ab_time`` so fresh and committed numbers are comparable."""
    spec = SpecEEConfig(num_speculative=k)
    bank = pred_lib.init_predictors(spec, 12, jax.random.PRNGKey(0))
    hn = jax.random.normal(jax.random.PRNGKey(1), (B, D))
    lm_w = jax.random.normal(jax.random.PRNGKey(2), (D, V)) * 0.05
    ids = jax.random.randint(jax.random.PRNGKey(3), (B, k), 0, V)
    prev = jnp.full((B, k), 1.0 / k)
    ep = jnp.int32(3)

    @jax.jit
    def fused(hn, lm_w, ids, prev, bank, ep):
        p_exit, probs, _ = gate_ops.exit_gate(hn, lm_w, ids, prev, bank, ep)
        tok, _ = gate_ops.verify_argmax(hn, lm_w)
        return p_exit, probs, tok, jnp.any(tok[:, None] == ids, 1)

    args = (hn, lm_w, ids, prev, bank, ep)
    fused(*args)                        # compile
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fused(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def _quant_verify_time(B, D, V, bits, iters=3, rounds=6):
    """Re-measure the quantized streaming verify (``verify_q_us``) at a
    committed shape. Returns (impl, seconds)."""
    from repro.kernels import on_tpu
    from repro.quant import quantize_tensor

    impl = "kernel" if on_tpu() else "xla"
    hn = jax.random.normal(jax.random.PRNGKey(1), (B, D))
    lm_w = jax.random.normal(jax.random.PRNGKey(2), (D, V)) * 0.05
    qt = quantize_tensor(lm_w, bits)
    f_q = jax.jit(lambda h, q: gate_ops.verify_argmax(h, q, impl=impl))
    f_q(hn, qt)                         # compile
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = f_q(hn, qt)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return impl, best


_GATE_ABS_SLACK_US = 250.0      # absolute noise floor added to every ceiling


def gate(threshold: float = 0.5) -> int:
    """CI perf gate over the committed ``BENCH_exit_gate.json`` row groups
    (ROADMAP item 5):

      * ``gate_ab``       — re-measure the fused gate per committed shape and
                            fail when fresh > (1 + threshold) × committed
                            ``fused_us``; re-derive the analytic
                            ``hbm_bytes``, which must match EXACTLY (formula
                            drift silently rewrites the memory story).
                            ``fused_kernel_us`` is never re-measured here:
                            off-TPU it runs the Pallas chain in interpret
                            mode (minutes per call at the big shapes) and is
                            a correctness datapoint, not a perf claim.
      * ``quant_verify``  — re-measure the quantized streaming verify
                            (``verify_q_us``) under the same criterion;
                            rows recorded with another impl are skipped.
      * ``quant_pareto``  — produced by the heavyweight bench_ablation
                            sweep, so the gate checks the committed quality
                            column instead: ``match_vs_dense_fp32`` must be
                            1.0 (quantized SpecEE serving is lossless vs
                            dense fp32 by construction).

    Microsecond timings on a shared CPU are far noisier than serving
    throughput, hence the wide default threshold PLUS an absolute slack
    (``_GATE_ABS_SLACK_US``) on every ceiling: the smallest committed rows
    are tens of microseconds of pure dispatch overhead, where scheduler
    jitter alone exceeds any relative bound — the slack drowns that noise
    while leaving the millisecond-scale rows (the real memory-bound signal)
    gated at ~threshold. Rows recorded on another backend are skipped.
    Returns a process exit code."""
    groups = _load_groups()
    if not groups:
        print("[bench_predictor] --gate: no committed BENCH_exit_gate.json; "
              "skipping")
        return 0
    backend = jax.default_backend()
    failures, checked = [], 0
    for row in groups.get("gate_ab", []):
        if row.get("backend") != backend or not row.get("fused_us"):
            continue
        B, D, V, k = row["B"], row["D"], row["V"], row["k"]
        checked += 1
        bytes_now = _gate_bytes(B, D, V, k)
        if bytes_now != row.get("hbm_bytes"):
            print(f"[gate] gate_ab B{B}_D{D}_V{V}: hbm_bytes drift "
                  f"{bytes_now} != {row.get('hbm_bytes')} FAIL")
            failures.append(f"gate_ab/B{B}_D{D}_V{V}/hbm_bytes")
        fresh = _fused_gate_time(B, D, V, k) * 1e6
        ceil = (1.0 + threshold) * row["fused_us"] + _GATE_ABS_SLACK_US
        verdict = "OK" if fresh <= ceil else "FAIL"
        print(f"[gate] gate_ab    B{B}_D{D}_V{V:<6} fused={fresh:10.1f}us "
              f"vs committed {row['fused_us']:10.1f} (ceil {ceil:10.1f}) "
              f"{verdict}")
        if verdict == "FAIL":
            failures.append(f"gate_ab/B{B}_D{D}_V{V}")
    for row in groups.get("quant_verify", []):
        if row.get("backend") != backend or not row.get("verify_q_us"):
            continue
        B, D, V, bits = row["B"], row["D"], row["V"], row["wbits"]
        impl, fresh_s = _quant_verify_time(B, D, V, bits)
        if row.get("impl") != impl:
            continue                    # recorded with another verify impl
        checked += 1
        fresh = fresh_s * 1e6
        ceil = (1.0 + threshold) * row["verify_q_us"] + _GATE_ABS_SLACK_US
        verdict = "OK" if fresh <= ceil else "FAIL"
        print(f"[gate] quant_q{bits}  B{B}_D{D}_V{V:<6} "
              f"verify={fresh:10.1f}us vs committed "
              f"{row['verify_q_us']:10.1f} (ceil {ceil:10.1f}) {verdict}")
        if verdict == "FAIL":
            failures.append(f"quant_verify/B{B}_D{D}_V{V}_q{bits}")
    for row in groups.get("quant_pareto", []):
        if row.get("backend") != backend:
            continue
        checked += 1
        match = row.get("match_vs_dense_fp32")
        verdict = "OK" if match == 1.0 else "FAIL"
        print(f"[gate] pareto     {row.get('quant', '?'):5s} "
              f"thr={row.get('threshold')}: match_vs_dense_fp32={match} "
              f"{verdict}")
        if verdict == "FAIL":
            failures.append(
                f"quant_pareto/{row.get('quant')}@{row.get('threshold')}")
    if failures:
        print(f"[gate] FAIL: exit-gate regression (> {threshold:.0%} or "
              f"drift) in {failures}")
        return 1
    print(f"[gate] OK: {checked} rows within {threshold:.0%} of committed")
    return 0


if __name__ == "__main__":
    if "--gate" in sys.argv:
        thr = 0.5
        if "--gate-threshold" in sys.argv:
            thr = float(sys.argv[sys.argv.index("--gate-threshold") + 1])
        sys.exit(gate(threshold=thr))
    t = Timer()
    if "--gate-only" in sys.argv:
        bench_exit_gate(t)
        bench_quant_verify(t)
    else:
        run(t)
        bench_exit_gate(t)
        bench_quant_verify(t)
    t.emit()

#!/usr/bin/env python3
"""Smoke run of the SpecEE serving path on a TPU — smoke, not a benchmark.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # tensor parallel and replicas

One process drives every chip it uses. The run serves Llama2-7B at its
published widths (d_model 4096, 32 heads of 128, d_ff 11008, vocab 32000)
with seeded random bf16 weights through ``repro.serving.ServingEngine``:
paged KV cache, chunked admission, megatick 4 with async dispatch.

One chip, depth cut to 16 layers (32 do not fit 16 GB at B=8, S=2048):
  kernels  every main-path Pallas kernel, compiled, against its ref.py
  (a)      dense decode
  (b)      SpecEE with the exit threshold above 1, so no exit can fire.
           Every token (a) and (b) serve is held to the sequence path,
           teacher-forced on the served prefix: it is its top logit up to a
           bf16 rounding flip (see MARGIN_TOL)
  (c)      SpecEE at the default threshold, and tree decoding: every request
           completes its budget, with no NaN and no page leak
Four chips (--chips 4), and nothing else:
  (i)      16 layers at TP=4 and at TP=1 on device 0, every token held to
           the TP=1 sequence path as in (b); the TP=4 and TP=1 sequence
           paths' logits agree at every generated position
  (iii)    a ReplicaPool of four one-chip replicas, each pinned to its own
           device, held to the TP=1 sequence path
  (ii)     all 32 layers at TP=4 serving the same requests, every token held
           to their TP=4 sequence path

It exits non-zero, before any result line, when JAX finds no TPU, and
whenever a check fails. The last line of standard output is the verdict:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

CHECKOUT = os.path.dirname(os.path.abspath(__file__))

MODEL = "llama2-7b"             # the paper's model; bring-up only
ONE_CHIP_LAYERS = 16
SERVE = {"max_batch": 8, "max_seq_len": 2048, "page_size": 128,
         "prefill_chunk": 512}
NUM_REQUESTS = 6
PROMPT_LENS = (128, 1024)       # shortest and longest prompt
NEW_TOKENS = 32
MEGATICK = 4
SEED = 0
NO_EXIT = 1.5                   # exit threshold > 1: the predictor never fires
# A bf16 residual stream rounds every add to 8 significant bits (unit
# roundoff 2^-8). Two paths that order the same arithmetic differently (the
# SpecEE layer while_loop against the dense layer scan, TP partial sums
# against one chip) drift apart by a few such roundings per layer, which
# moves the logits by about a percent of their scale. A token flip where the
# reference's winning logit beats the other path's choice by at most 2^-5
# (3%) of the row's largest |logit| is that rounding; a wrong layer, cache
# row or reduction moves logits by their whole scale.
MARGIN_TOL = 2.0 ** -5
# kernel checks: the reference runs in fp32 at "highest" matmul precision,
# the kernels accumulate in fp32. The bound is set from the readings: the
# largest error of the 26 checks on a v5e was 0.00179, and 5e-3 leaves under
# three times that (no lower-precision variant was read against it)
KERNEL_TOL = 5e-3
# argmax / top-k kernels: products of bf16 values are exact in fp32, so the
# kernel and the reference differ only in summation order (~1e-6 relative);
# a differing id must be a tie under the reference to that precision
TIE_TOL = 1e-4


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    say(f"FAIL: {msg}")
    sys.exit(1)


def check(ok, msg: str) -> None:
    if not ok:
        fail(msg)


class CompileClock:
    """Seconds spent in XLA compiles (or persistent-cache loads), and the
    number of programs the persistent cache supplied."""

    def __init__(self, jax):
        self.secs = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def peak_bytes(devices) -> int:
    return max(d.memory_stats()["peak_bytes_in_use"] for d in devices)


def llama_run(layers: int):
    from repro.configs import get_config
    run = get_config(MODEL)
    return dataclasses.replace(
        run, model=dataclasses.replace(run.model, num_layers=layers),
        serve=dataclasses.replace(run.serve, **SERVE))


def published_layers() -> int:
    from repro.configs import get_config
    return get_config(MODEL).model.num_layers


def make_prompts(vocab: int):
    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, NUM_REQUESTS)
    lens[0], lens[-1] = PROMPT_LENS
    return [rng.integers(0, vocab, int(n)).astype(np.int32) for n in lens]


def build(run, mesh=None):
    """Model, bf16 parameters (drawn on the device, in the mesh's serving
    layout when given) and SpecEE weights, all from the seed."""
    import jax
    from repro.core import engine as eng
    from repro.models.model import build_model
    from repro.sharding.serving import init_params
    model = build_model(run)
    key = jax.random.PRNGKey(SEED)
    params = (model.init(key) if mesh is None
              else init_params(model, key, mesh))
    sw = eng.init_specee(model, jax.random.PRNGKey(SEED + 1))
    jax.block_until_ready((params, sw))
    return model, params, sw


class Phases:
    """Runs serving phases and reports each one's numbers."""

    def __init__(self, clock, devices):
        self.clock, self.devices = clock, devices

    def report(self, name, tokens, wall, compile_s):
        say(f"{name}: tokens={tokens} wall_s={wall:.3f} "
            f"compile_s={compile_s:.3f} "
            f"peak_bytes_in_use={peak_bytes(self.devices)} "
            f"(smoke, not a benchmark)")

    def served(self, name, se, reqs, model) -> dict:
        """Check one finished engine: budgets, vocab range, page leak,
        fault log, finite state. Returns tokens and exit stats."""
        se.drain()          # a pool stops on completion, not on idle
        check(len(se.completed) == len(reqs),
              f"{name}: {len(se.completed)}/{len(reqs)} requests completed")
        V = model.cfg.vocab_size
        for r in reqs:
            check(r.done and len(r.output) == NEW_TOKENS,
                  f"{name}: request {r.uid} emitted {len(r.output)} of "
                  f"{NEW_TOKENS} tokens")
            check(all(0 <= t < V for t in r.output),
                  f"{name}: request {r.uid} emitted an out-of-vocab token")
        mgr = se.session.cache_mgr
        check(mgr.free_pages == mgr.num_pages,
              f"{name}: page leak ({mgr.free_pages}/{mgr.num_pages} free)")
        events = [(e.site, e.action) for e in se.fault_log]
        check(not events, f"{name}: recovery ran: {events}")
        state, _ = se.session.snapshot()
        check(bool(np.isfinite(np.asarray(state.h_last, np.float32)).all()),
              f"{name}: non-finite hidden state")
        E = model.num_exit_points
        return {"outputs": [list(r.output) for r in reqs],
                "exits": sum(sum(1 for e in r.exit_points if e < E)
                             for r in reqs),
                "accept": sum(sum(r.accept_lens) for r in reqs)}

    def serve(self, name, model, params, sw, strategy, prompts,
              mesh=None) -> dict:
        from repro.serving import ServingEngine
        c0, t0 = self.clock.secs, time.perf_counter()
        se = ServingEngine(model, params, sw, strategy=strategy,
                           megatick=MEGATICK, cache="paged", mesh=mesh)
        reqs = [se.submit(p, max_new_tokens=NEW_TOKENS) for p in prompts]
        se.run_to_completion()
        wall = time.perf_counter() - t0
        out = self.served(name, se, reqs, se.model)
        se.close()
        self.report(name, NEW_TOKENS * len(prompts), wall,
                    self.clock.secs - c0)
        say(f"{name}: exits={out['exits']} accepted={out['accept']} "
            f"over {NEW_TOKENS * len(prompts)} tokens")
        del se
        gc.collect()
        return out


class Reference:
    """The model's own sequence path, teacher-forced: one causal forward
    over a request's prompt followed by the tokens a served run emitted (no
    KV cache, no paged kernel, no megatick, no Pallas kernel), giving the
    logits that predict every generated position from the served prefix.
    Under a mesh the parameters' layout partitions it by GSPMD."""

    def __init__(self, name, model, params, prompts):
        import jax
        import jax.numpy as jnp
        self.name, self.params, self.prompts = name, params, prompts
        self.pad = -(-(PROMPT_LENS[1] + NEW_TOKENS) // 128) * 128
        self.memo = {}

        @jax.jit
        def at(params, tokens, start):
            h = model.embed(params, tokens)
            positions = jnp.arange(tokens.shape[1])[None, :]
            h, _, _ = model.forward_hidden(params, h, positions)
            hs = jax.lax.dynamic_slice_in_dim(h[0], start, NEW_TOKENS, 0)
            return model.logits(params, hs)
        self.at = at

    def logits(self, i, output) -> np.ndarray:
        """(NEW_TOKENS, V): row j predicts generated token j of request i
        from its prompt and ``output[:j]``."""
        key = (i, tuple(output))
        if key not in self.memo:
            p = self.prompts[i]
            row = np.zeros((1, self.pad), np.int32)
            row[0, :len(p)] = p
            row[0, len(p):len(p) + len(output)] = output
            self.memo[key] = np.asarray(
                self.at(self.params, row, len(p) - 1), np.float64)
        return self.memo[key]

    def check(self, name, outputs) -> None:
        """Every served token must be the reference's top logit on the
        served prefix, up to a bf16 rounding flip: at most MARGIN_TOL of the
        row's largest |logit| below it."""
        off, worst = 0, 0.0
        for i, out in enumerate(outputs):
            L = self.logits(i, out)
            for j, t in enumerate(out):
                top2 = np.sort(L[j])[-2:]
                gap = float(top2[1] - L[j, t])
                tol = MARGIN_TOL * float(np.abs(L[j]).max())
                worst = max(worst, gap / tol)
                if gap > 0.0 or t != int(np.argmax(L[j])):
                    off += 1
                    say(f"{name}: request {i} token {j}: served {t}, "
                        f"{self.name} top {int(np.argmax(L[j]))}, top-2 "
                        f"margin {top2[1] - top2[0]:.6f}, gap {gap:.6f}, "
                        f"tolerance {tol:.6f}")
                check(gap <= tol, f"{name}: request {i} token {j} trails "
                                  f"{self.name}'s top logit beyond bf16 "
                                  "rounding")
        n = sum(len(o) for o in outputs)
        say(f"{name}: {n - off}/{n} served tokens are {self.name}'s "
            f"teacher-forced argmax, the rest within bf16 rounding of it; "
            f"largest gap/tolerance {worst:.3f}")


def same_requests(name, ref, got) -> None:
    same = sum(a == b for a, b in zip(ref, got))
    say(f"{name}: {same}/{len(ref)} requests token-identical")


def compare_logits(name, ref: Reference, other: Reference, outputs) -> None:
    """The two sequence paths' logits on the same token streams, at every
    generated position, agree to MARGIN_TOL of each row's largest
    |logit|."""
    worst = 0.0
    for i, out in enumerate(outputs):
        a, b = ref.logits(i, out), other.logits(i, out)
        err = np.abs(a - b).max(axis=1)
        tol = MARGIN_TOL * np.abs(a).max(axis=1)
        worst = max(worst, float((err / tol).max()))
        check(np.all(err <= tol),
              f"{name}: request {i} logits differ beyond bf16 rounding "
              f"(largest {float(err.max()):.6f})")
    say(f"{name}: logits at all {sum(len(o) for o in outputs)} generated "
        f"positions agree; largest difference/tolerance {worst:.3f}")


def check_kernels(jax, model, params, sw, clock) -> None:
    """Each main-path Pallas kernel once, compiled, at serving widths,
    against its ref.py (fp32 at "highest" matmul precision)."""
    import jax.numpy as jnp
    from repro import quant
    from repro.core.predictor import predictor_at
    from repro.kernels.decode_attention.ops import paged_decode_attention_raw
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    from repro.kernels.exit_gate import ops as gate_ops
    from repro.kernels.exit_gate.ref import (exit_gate_ref, mlp_ref,
                                             verify_argmax_ref,
                                             verify_topk_ref)
    from repro.kernels.predictor_mlp.ops import predictor_mlp
    from repro.kernels.spec_head.ops import spec_head
    from repro.kernels.spec_head.ref import spec_head_ref

    c0, t0 = clock.secs, time.perf_counter()
    cfg, spec = model.cfg, model.run.specee
    B, D, V, k = SERVE["max_batch"], cfg.d_model, cfg.vocab_size, \
        spec.num_speculative
    keys = jax.random.split(jax.random.PRNGKey(SEED + 7), 8)
    hn = jax.random.normal(keys[0], (B, D)).astype(jnp.bfloat16)
    ids = jax.random.randint(keys[1], (B, k), 0, V, jnp.int32)
    prev = jax.nn.softmax(jax.random.normal(keys[2], (B, k)), axis=-1)
    ep = jnp.int32(3)
    bank = sw.predictors
    lm = params["lm_head"]["w"]
    highest = jax.default_matmul_precision("highest")

    def close(name, got, want, tol=KERNEL_TOL):
        got = np.asarray(got, np.float64)
        want = np.asarray(want, np.float64)
        err = float(np.max(np.abs(got - want) / (np.abs(want) + 1.0)))
        check(got.shape == want.shape and err <= tol,
              f"kernel {name}: error {err:.3g} > {tol} (shape {got.shape} "
              f"vs {want.shape})")
        return err, name

    def ids_agree(name, got_ids, head):
        # every id the kernel picked must carry the reference logit of the
        # reference's pick at that rank, to fp32 summation-order precision
        with highest:
            ref_logits = np.asarray(
                hn.astype(jnp.float32) @ quant_lm(head).astype(jnp.float32),
                np.float64)
        got_ids = np.asarray(got_ids).reshape(B, -1)
        want = -np.sort(-ref_logits, axis=1)[:, :got_ids.shape[1]]
        picked = np.take_along_axis(ref_logits, got_ids, axis=1)
        scale = np.abs(ref_logits).max(axis=1, keepdims=True)
        check(np.all(np.abs(picked - want) <= TIE_TOL * scale),
              f"kernel {name}: ids differ from the reference beyond ties")

    def quant_lm(head):
        return head.dequantize() if hasattr(head, "bits") else head

    results = []
    heads = {"fp": lm}
    for bits in (8, 4):
        heads[f"q{bits}"] = quant.quantize_tensor(lm, bits)
    for tag, head in heads.items():
        tok, mx = gate_ops.verify_argmax(hn, head, impl="kernel")
        ids_agree(f"argmax_verify[{tag}]", tok, head)
        with highest:
            _, mx_r = verify_argmax_ref(hn, head)
        results.append(close(f"argmax_verify[{tag}]", mx, mx_r))
        tids, tvals = gate_ops.verify_topk(hn, head, k, impl="kernel")
        ids_agree(f"topk_verify[{tag}]", tids, head)
        with highest:
            _, tvals_r = verify_topk_ref(hn, head, k)
        results.append(close(f"topk_verify[{tag}]", tvals, tvals_r))
        logits, probs = spec_head(hn, head, ids)
        with highest:
            logits_r, probs_r = spec_head_ref(hn, head, ids)
        results.append(close(f"spec_head[{tag}]", logits, logits_r))
        results.append(close(f"spec_head[{tag}] probs", probs, probs_r))
        gbank = bank if tag == "fp" else {"layers": [
            {"w": quant.quantize_tensor(l["w"], head.bits), "b": l["b"]}
            for l in bank["layers"]]}
        p, gprobs, glogits = gate_ops.exit_gate(hn, head, ids, prev, gbank,
                                                ep, impl="kernel")
        pp = predictor_at(gbank, ep)
        with highest:
            p_r, gprobs_r, glogits_r = exit_gate_ref(hn, head, ids, prev, pp)
        results.append(close(f"exit_gate[{tag}] p_exit", p, p_r))
        results.append(close(f"exit_gate[{tag}] probs", gprobs, gprobs_r))
        results.append(close(f"exit_gate[{tag}] logits", glogits, glogits_r))
        feats = jnp.concatenate([logits_r, probs_r, probs_r - prev], axis=-1)
        got = predictor_mlp(feats, pp)
        with highest:
            want = mlp_ref(feats, pp)
        results.append(close(f"predictor_mlp[{tag}]", got, want))

    # paged decode attention over one full row of pages per slot, bf16 and
    # int8 pools, random page table and lengths
    H, hd = cfg.num_heads, cfg.resolved_head_dim()
    KVH, ps = cfg.num_kv_heads, SERVE["page_size"]
    P = SERVE["max_seq_len"] // ps
    n_pages = B * P
    q = jax.random.normal(keys[3], (B, 1, H, hd)).astype(jnp.bfloat16)
    table = jax.random.permutation(keys[4], n_pages).reshape(B, P)
    table = table.astype(jnp.int32)
    lens = jax.random.randint(keys[5], (B,), 1, SERVE["max_seq_len"] + 1,
                              jnp.int32)
    kq = jax.random.randint(keys[6], (n_pages, ps, KVH, hd), -127, 128,
                            jnp.int32).astype(jnp.int8)
    vq = jax.random.randint(keys[7], (n_pages, ps, KVH, hd), -127, 128,
                            jnp.int32).astype(jnp.int8)
    ks = jax.random.uniform(keys[6], (n_pages, ps, KVH), jnp.float32,
                            0.002, 0.02)
    vs = jax.random.uniform(keys[7], (n_pages, ps, KVH), jnp.float32,
                            0.002, 0.02)
    kf = (kq.astype(jnp.float32) * ks[..., None]).astype(jnp.bfloat16)
    vf = (vq.astype(jnp.float32) * vs[..., None]).astype(jnp.bfloat16)
    got = paged_decode_attention_raw(q, kf, vf, table, lens)
    with highest:
        want = paged_decode_attention_ref(q.astype(jnp.float32),
                                          kf.astype(jnp.float32),
                                          vf.astype(jnp.float32), table, lens)
    results.append(close("paged_decode_attention[bf16]", got, want))
    got = paged_decode_attention_raw(q, kq, vq, table, lens, k_scale=ks,
                                     v_scale=vs)
    with highest:
        want = paged_decode_attention_ref(
            q.astype(jnp.float32), kq.astype(jnp.float32) * ks[..., None],
            vq.astype(jnp.float32) * vs[..., None], table, lens)
    results.append(close("paged_decode_attention[int8]", got, want))
    worst, worst_name = max(results)
    say(f"kernels: {len(results)} checks against ref.py passed, largest "
        f"relative error {worst:.3g} ({worst_name}); compile_s="
        f"{clock.secs - c0:.3f} wall_s={time.perf_counter() - t0:.3f} "
        "(smoke, not a benchmark)")


def one_chip(jax, clock) -> None:
    from repro.api import SpecEEStrategy, TreeStrategy
    devices = jax.devices()[:1]
    run = llama_run(ONE_CHIP_LAYERS)
    say(f"model {MODEL}: published widths (d_model {run.model.d_model}, "
        f"{run.model.num_heads} heads of {run.model.resolved_head_dim()}, "
        f"d_ff {run.model.d_ff}, vocab {run.model.vocab_size}); depth cut "
        f"{published_layers()} -> {ONE_CHIP_LAYERS} layers to fit one 16 GB "
        f"chip at batch {SERVE['max_batch']}, {SERVE['max_seq_len']} "
        "tokens; random bf16 "
        f"weights from seed {SEED}")
    t0, c0 = time.perf_counter(), clock.secs
    model, params, sw = build(run)
    say(f"weights: wall_s={time.perf_counter() - t0:.3f} "
        f"compile_s={clock.secs - c0:.3f} "
        f"peak_bytes_in_use={peak_bytes(devices)} (smoke, not a benchmark)")
    check_kernels(jax, model, params, sw, clock)
    prompts = make_prompts(run.model.vocab_size)
    say(f"requests: {len(prompts)} prompts of "
        f"{sorted(len(p) for p in prompts)} tokens, {NEW_TOKENS} new each")
    ph = Phases(clock, devices)
    dense = ph.serve("(a) dense", model, params, sw, "dense", prompts)
    no_exit = ph.serve("(b) specee threshold>1", model, params, sw,
                       SpecEEStrategy(threshold=NO_EXIT), prompts)
    check(no_exit["exits"] == 0, "(b): an exit fired above threshold 1")
    same_requests("(b) vs (a)", dense["outputs"], no_exit["outputs"])
    ref = Reference("the sequence path", model, params, prompts)
    ref.check("(a)", dense["outputs"])
    ref.check("(b)", no_exit["outputs"])
    del ref
    ph.serve("(c) specee", model, params, sw, "specee", prompts)
    ph.serve("(c) tree", model, params, sw, TreeStrategy(), prompts)


def tp_and_replicas(jax, ph, clock, devices, tp4, prompts) -> None:
    """(i) and (iii) on the 16-layer model. Everything they build lives in
    this frame and is freed when it returns, before (ii) needs the room."""
    from repro.api import Engine, SpecEEStrategy
    from repro.launch.mesh import make_replica_meshes
    from repro.serving import ReplicaPool, ServingEngine
    replica_meshes = make_replica_meshes(4, 1)
    strategy = SpecEEStrategy(threshold=NO_EXIT)
    # drawn in replica 0's layout, so TP=1 on device 0 and replica 0 serve
    # these very buffers
    model, params, sw = build(llama_run(ONE_CHIP_LAYERS),
                              mesh=replica_meshes[0])
    depth = f"{ONE_CHIP_LAYERS} layers"
    tp1 = ph.serve(f"(i) {depth} tp=1 device 0", model, params, sw,
                   strategy, prompts, mesh=replica_meshes[0])
    tp4_out = ph.serve(f"(i) {depth} tp=4", model, params, sw, strategy,
                       prompts, mesh=tp4)
    same_requests("(i) tp=4 vs tp=1", tp1["outputs"], tp4_out["outputs"])
    ref = Reference("the tp=1 sequence path", model, params, prompts)
    ref.check("(i) tp=1", tp1["outputs"])
    ref.check("(i) tp=4", tp4_out["outputs"])
    # the parameters in the layout a TP=4 engine serves them in
    params4 = Engine.create(model, params, strategy="dense", mesh=tp4).params
    ref4 = Reference("the tp=4 sequence path", model, params4, prompts)
    compare_logits("(i) tp=4 vs tp=1 sequence paths", ref, ref4,
                   tp1["outputs"])
    del ref4, params4

    c0, t0 = clock.secs, time.perf_counter()
    engines = [ServingEngine(model, params, sw, strategy=strategy,
                             megatick=MEGATICK, cache="paged", mesh=m)
               for m in replica_meshes]
    pool = ReplicaPool(engines, evict_stragglers=False)
    prs = [pool.submit(p, max_new_tokens=NEW_TOKENS) for p in prompts]
    pool.run_to_completion()
    wall = time.perf_counter() - t0
    check(not list(pool.fault_log),
          f"(iii): pool recovery ran: {list(pool.fault_log)}")
    for i, se in enumerate(engines):
        mine = [pr for pr in prs if pr.replica == i]
        check(mine, f"(iii): replica {i} served no request")
        out = ph.served(f"(iii) replica {i}", se, [pr.handle for pr in mine],
                        se.model)
        state, _ = se.session.snapshot()
        placed = {d for leaf in jax.tree_util.tree_leaves(
            (se.engine.params, se.engine.sw, state)) for d in leaf.devices()}
        check(placed == {devices[i]},
              f"(iii): replica {i} weights/cache on {placed}, want "
              f"{devices[i]}")
        say(f"(iii) replica {i}: {len(mine)} requests on {devices[i]}, "
            f"weights and cache on that device only; exits={out['exits']}")
        se.close()
    ph.report("(iii) 4 replicas", NEW_TOKENS * len(prompts), wall,
              clock.secs - c0)
    replicas = [list(pr.output) for pr in prs]
    same_requests("(iii) replicas vs (i) tp=1", tp1["outputs"], replicas)
    ref.check("(iii) replicas", replicas)


def four_chips(jax, clock) -> None:
    from jax.sharding import AxisType
    from repro.api import SpecEEStrategy
    devices = jax.devices()[:4]
    tp4 = jax.make_mesh((1, 4), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2, devices=devices)
    prompts = make_prompts(llama_run(ONE_CHIP_LAYERS).model.vocab_size)
    ph = Phases(clock, devices)
    tp_and_replicas(jax, ph, clock, devices, tp4, prompts)
    gc.collect()

    run32 = llama_run(published_layers())
    say(f"(ii) model {MODEL} at full depth ({run32.model.num_layers} "
        "layers), parameters drawn directly in the TP=4 layout")
    model32, params32, sw32 = build(run32, mesh=tp4)
    out = ph.serve(f"(ii) {run32.model.num_layers} layers tp=4", model32,
                   params32, sw32, SpecEEStrategy(threshold=NO_EXIT),
                   prompts, mesh=tp4)
    check(out["exits"] == 0, "(ii): an exit fired above threshold 1")
    # all 32 layers do not fit one chip beside their shards, so the
    # reference is the same weights' sequence path partitioned by GSPMD,
    # which (i) held to the unsharded one
    Reference("the tp=4 sequence path", model32, params32, prompts).check(
        f"(ii) {run32.model.num_layers} layers tp=4", out["outputs"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tensor-parallel and replica "
                         "phases, on four chips")
    args = ap.parse_args()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[chip_smoke] needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"[chip_smoke] --chips {args.chips} needs {args.chips} "
              f"devices; JAX found {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(CHECKOUT, "src"))
    from repro.runtime.compile_cache import enable_compile_cache
    say(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock(jax)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(jax, clock)
    else:
        one_chip(jax, clock)
    say(f"total: wall_s={time.perf_counter() - t0:.3f} "
        f"compile_s={clock.secs:.3f} cache_hits={clock.cache_hits} "
        f"peak_bytes_in_use={peak_bytes(devices[:args.chips])} "
        "(smoke, not a benchmark)")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

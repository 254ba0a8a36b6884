"""Production serving launcher: continuous batching over the unified
decode API (``repro.api``) with SpecEE as the default fast path.

Smoke usage (CPU):
    PYTHONPATH=src python -m repro.launch.serve --arch llama2-7b --smoke \
        --requests 8
    PYTHONPATH=src python -m repro.launch.serve --smoke --mode tree \
        --requests 4

Without --smoke (or --ci, which implies it) the launcher serves the
registry config at its published widths and ``ServeConfig``.

Fault tolerance (DESIGN.md §7):
    --checkpoint-dir D   arm SIGTERM preemption: the engine drains, saves a
                         step-atomic snapshot into D, and exits with code 17
    --restore            resume the latest snapshot in D token-identically
    --inject SITE        deterministic fault injection at one named site
                         (dispatch / finish_timeout / nan_logits /
                         pool_exhausted / sigterm / device_lost) — the run
                         must still complete every request, and --ci
                         verifies the outputs against an in-process
                         fault-free reference. ``device_lost`` needs a
                         tensor-parallel mesh (--mesh 1,2): the engine
                         remeshes to a lower TP degree (DESIGN.md §10)
    --fault-log PATH     dump the engine's FaultEvent ring to PATH as JSONL
                         after the run (the machine-readable post-mortem)
    --num-pages N        oversubscribe the paged pool (fewer pages than
                         max_batch rows need) to drive victim eviction

The serving engine defaults the fused exit-gate pipeline ON
(serve-path adoption; pass --no-fused-gate to pin the reference path).
The full-scale path is the same strategy step jit'd against the production
mesh (see launch/dryrun.py, which lowers exactly this serve step for every
assigned architecture × decode shape).
"""
from __future__ import annotations

import argparse
import sys
import tempfile
import time

import jax
import numpy as np

PREEMPTED_EXIT_CODE = 17


def main() -> None:
    from repro.runtime import faultinject

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-7b")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced same-family preset "
                         "(RunConfig.smoke) instead of the published config")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--mode", default="specee",
                    choices=["specee", "dense", "tree"],
                    help="decode strategy behind the serving engine")
    ap.add_argument("--no-specee", action="store_true",
                    help="alias for --mode dense (back-compat)")
    ap.add_argument("--no-fused-gate", action="store_true",
                    help="pin the reference (unfused) exit-gate path")
    ap.add_argument("--cache", default="paged", choices=["paged", "dense"],
                    help="KV cache layout (paged pools vs the dense "
                         "slot-masked reference)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="paged-KV page size (default: ServeConfig.page_size)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="paged-KV pool size in pages (default: capacity "
                         "parity with dense; smaller oversubscribes the "
                         "pool and exercises victim eviction)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="Sarathi-style chunked-prefill budget per tick "
                         "(0 = blocking admission; default: "
                         "ServeConfig.prefill_chunk)")
    ap.add_argument("--megatick", type=int, default=1,
                    help="decode ticks fused into one device-resident "
                         "lax.while_loop dispatch (1 = historical per-tick "
                         "host sync); > 1 also pipelines serving ticks "
                         "(async dispatch-ahead)")
    ap.add_argument("--sync-ticks", action="store_true",
                    help="disable the async serving pipeline even with "
                         "--megatick > 1")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="arm SIGTERM preemption: drain + snapshot here, "
                         f"exit {PREEMPTED_EXIT_CODE}; restart with "
                         "--restore to resume")
    ap.add_argument("--restore", action="store_true",
                    help="resume the latest checkpoint in --checkpoint-dir "
                         "(no-op on an empty directory)")
    ap.add_argument("--inject", default=None,
                    choices=list(faultinject.SITES),
                    help="deterministically inject one fault at the named "
                         "site; the run must still complete (recovery path)")
    ap.add_argument("--fault-log", default=None, metavar="PATH",
                    help="write the FaultEvent recovery trail to PATH as "
                         "JSONL after the run (engine + pool + per-replica "
                         "sources in one file)")
    ap.add_argument("--ci", action="store_true",
                    help="CI smoke: few short requests + completion asserts")
    ap.add_argument("--ticks-per-check", type=int, default=1,
                    help="(reserved) serving ticks between health checks")
    ap.add_argument("--quant", default=None, choices=["int8", "int4"],
                    help="weight-only compression (repro.quant): quantize "
                         "the LM head, predictor bank, and attention/MLP "
                         "projections; dequant is fused into the decode "
                         "kernels (the fp params stay untouched)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature for --mode dense "
                         "(0 = greedy)")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed for the session (--temperature > 0)")
    ap.add_argument("--trained", action="store_true",
                    help="train draft+predictors first (slower start)")
    ap.add_argument("--mesh", default="1,1", metavar="DATA,MODEL",
                    help="decode mesh shape; MODEL > 1 turns on tensor-"
                         "parallel decode (DESIGN.md §9). Without real "
                         "accelerators the launcher forces host devices "
                         "(XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N) so CPU smoke runs exercise the same "
                         "sharded program")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel ServingEngine replicas behind one "
                         "shared queue (ReplicaPool); each replica gets its "
                         "own disjoint MODEL-wide device slice")
    args = ap.parse_args()
    try:
        data_par, model_par = (int(x) for x in args.mesh.split(","))
    except ValueError:
        ap.error(f"--mesh must be DATA,MODEL ints, got {args.mesh!r}")
    if data_par != 1:
        ap.error("--mesh DATA must be 1: data parallelism is --replicas "
                 "(independent engines), not an in-engine mesh axis")
    if model_par < 1 or args.replicas < 1:
        ap.error("--mesh MODEL and --replicas must be >= 1")
    need_devices = args.replicas * model_par
    if need_devices > 1:
        # host-mesh fallback: must land in XLA_FLAGS before the first jax
        # backend touch (the heavy imports below). A real TPU/GPU fleet is
        # unaffected — the flag only multiplies the CPU platform.
        import os
        import re
        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
        if m is None or int(m.group(1)) < need_devices:
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", "", flags)
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{need_devices}").strip()
    mode = "dense" if args.no_specee else args.mode
    if args.ci:
        args.smoke = True
        args.requests = min(args.requests, 4)
        args.max_new = min(args.max_new, 6)
    if args.restore and not args.checkpoint_dir:
        ap.error("--restore requires --checkpoint-dir")
    if args.inject == "sigterm" and not args.checkpoint_dir:
        # the injected preemption is recovered in-process, which needs
        # somewhere to put the checkpoint
        args.checkpoint_dir = tempfile.mkdtemp(prefix="serve-ckpt-")
    if args.inject == "device_lost" and model_par <= 1:
        ap.error("--inject device_lost needs a tensor-parallel mesh to "
                 "degrade (e.g. --mesh 1,2): an unsharded engine has no "
                 "surviving devices to remesh onto and the fault is "
                 "terminal")
    if args.replicas > 1 and (args.checkpoint_dir or args.restore
                              or args.inject is not None):
        ap.error("--replicas composes with in-pool failover (a dead "
                 "replica's requests migrate to survivors), not with the "
                 "single-engine --checkpoint-dir/--restore/--inject paths")

    # arm SIGTERM before the heavy startup (jax import + model build +
    # tracing can run for minutes): a preemption landing mid-build must
    # defer to the first serve tick — which drains, saves, and exits
    # cleanly — not kill the process with the default handler
    guard = None
    if args.checkpoint_dir:
        from repro.runtime.fault import PreemptionGuard
        guard = PreemptionGuard()
        guard.install()

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.api import CacheSpec
    from repro.configs import get_config
    from repro.core import engine as eng
    from repro.models.model import build_model
    from repro.runtime import telemetry
    from repro.runtime.faultinject import FaultSchedule
    from repro.serving import Preempted, ServingEngine

    if args.trained:
        from benchmarks.common import get_bundle
        b = get_bundle(args.arch)
        model, params, sw = b.model, b.params, b.sw
        run = b.run
    else:
        run = get_config(args.arch)
        if args.smoke:
            run = run.smoke()
        model = build_model(run)
        params = model.init(jax.random.PRNGKey(0))
        sw = eng.init_specee(model, jax.random.PRNGKey(1))

    strategy = mode
    if args.temperature > 0.0:
        if mode != "dense":
            ap.error("--temperature requires --mode dense (SpecEE "
                     "verification is argmax-defined; see ROADMAP)")
        from repro.api import DenseStrategy
        strategy = DenseStrategy(temperature=args.temperature)
    cache = args.cache
    if args.num_pages is not None:
        if args.cache != "paged":
            ap.error("--num-pages requires --cache paged")
        cache = CacheSpec(kind="paged",
                          page_size=(args.page_size if args.page_size
                                     else run.serve.page_size),
                          num_pages=args.num_pages)
    # prompts are a pure function of the CLI, so a restarted --restore run
    # (and the in-process parity reference) regenerates the same workload
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, run.model.vocab_size,
                            int(rng.integers(4, 16)))
               for _ in range(args.requests)]

    def make_engine(megatick: int, checkpoint_dir=None, mesh=None):
        return ServingEngine(model, params, sw, strategy=strategy,
                             prng_seed=args.seed,
                             fused_gate=not args.no_fused_gate,
                             cache=cache, page_size=args.page_size,
                             prefill_chunk=args.prefill_chunk,
                             megatick=megatick,
                             async_ticks=False if args.sync_ticks else None,
                             checkpoint_dir=checkpoint_dir,
                             guard=guard if checkpoint_dir else None,
                             quant=args.quant, mesh=mesh)

    def run_engine(megatick: int, checkpoint_dir=None, restore=False,
                   mesh=None):
        engine = make_engine(megatick, checkpoint_dir=checkpoint_dir,
                             mesh=mesh)
        restored = restore and engine.restore_checkpoint()
        if restored:
            print(f"[serve] restored tick {engine._tick} from "
                  f"{checkpoint_dir} ({len(engine.completed)} requests "
                  "already complete)")
        else:
            for p in prompts:
                engine.submit(p, max_new_tokens=args.max_new)
        t0 = time.perf_counter()
        try:
            engine.run_to_completion()
        except Preempted as p:
            if args.inject == "sigterm":
                # injected preemption: recover in-process — exactly what a
                # restarted --restore process would do
                print(f"[serve] {p}; recovering in-process")
                engine.close()
                return run_engine(megatick, checkpoint_dir=checkpoint_dir,
                                  restore=True)
            print(f"[serve] {p}")
            engine.close()
            sys.exit(PREEMPTED_EXIT_CODE)
        engine.close()
        return engine, time.perf_counter() - t0

    schedule = None
    if args.inject == "pool_exhausted":
        schedule = FaultSchedule.at(pool_exhausted=range(8))
    elif args.inject == "sigterm":
        schedule = FaultSchedule.once("sigterm", visit=2)
    elif args.inject is not None:
        schedule = FaultSchedule.once(args.inject, visit=1)
    inj = faultinject.install(schedule) if schedule else None

    # ----- data-parallel replica pool (--replicas R) -----
    if args.replicas > 1:
        from repro.launch.mesh import make_replica_meshes
        from repro.serving import ReplicaPool
        meshes = make_replica_meshes(args.replicas, model_par)
        pool = ReplicaPool([make_engine(args.megatick, mesh=ms)
                            for ms in meshes])
        prs = [pool.submit(p, max_new_tokens=args.max_new) for p in prompts]
        t0 = time.perf_counter()
        pool.run_to_completion()
        dt = time.perf_counter() - t0
        toks = sum(len(r.output) for r in pool.completed)
        print(f"[serve] {len(pool.completed)} requests, {toks} tokens in "
              f"{dt:.2f}s ({toks/dt:.1f} tok/s, replicas={args.replicas}, "
              f"mesh=(1,{model_par}), mode={mode}, "
              f"megatick={args.megatick})")
        if args.ci:
            assert len(pool.completed) == args.requests, \
                f"CI smoke: {len(pool.completed)}/{args.requests} completed"
            assert all(r.done and len(r.output) == args.max_new
                       for r in prs), \
                "CI smoke: a pooled request missed its token budget"
            ref_engine, _ = run_engine(1)
            ref = [r.output for r in sorted(ref_engine.completed,
                                            key=lambda r: r.uid)]
            got = [list(pr.output) for pr in prs]
            assert got == ref, \
                "CI smoke: pool tokens diverge from the single-engine " \
                "reference"
            print("[serve] CI smoke OK (replica-pool token parity with the "
                  "single-engine reference)")
        if args.fault_log:
            n = pool.fault_log.dump_jsonl(args.fault_log, source="pool")
            for i, rep in enumerate(pool.replicas):
                n += rep.fault_log.dump_jsonl(args.fault_log,
                                              source=f"replica{i}",
                                              append=True)
            print(f"[serve] fault log: {n} events -> {args.fault_log}")
        pool.close()
        return

    mesh = None
    if model_par > 1:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(1, model_par)
    engine, dt = run_engine(args.megatick,
                            checkpoint_dir=args.checkpoint_dir,
                            restore=args.restore, mesh=mesh)
    faultinject.uninstall()
    done = engine.completed
    toks = sum(len(r.output) for r in done)
    mgr = engine.session.cache_mgr
    print(f"[serve] {len(done)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s, mode={mode}, cache={mgr.kind}, "
          f"chunk={engine.scheduler.chunk_tokens}, "
          f"megatick={args.megatick}, async={engine.async_ticks}, "
          f"fused_gate={not args.no_fused_gate}, "
          f"quant={args.quant or 'fp'})")
    print(f"[serve] counters: {engine.counters}")
    print("[serve] compiles by span:\n" + telemetry.compile_table())
    if inj is not None:
        assert args.inject in inj.fired_sites(), \
            f"--inject {args.inject} never fired (schedule {schedule.plan})"
        recovery = [(e.site, e.action) for e in engine.fault_log]
        print(f"[serve] injected {args.inject} at visits "
              f"{sorted(inj.schedule.plan[args.inject])}; recovery log: "
              f"{recovery}")
        if args.inject == "device_lost":
            # the loss must degrade IN PLACE, not crash: a remesh event in
            # the log and a TP degree strictly below the built mesh
            assert any(e.action == "remesh" for e in engine.fault_log), \
                "--inject device_lost: no remesh in the fault log"
            assert engine.tp_degree < model_par, \
                f"--inject device_lost: tp still {engine.tp_degree}"
            print(f"[serve] remeshed tp {model_par}->{engine.tp_degree} "
                  "(degraded mode, verified replay)")
    if args.fault_log:
        n = engine.fault_log.dump_jsonl(args.fault_log, source="engine")
        print(f"[serve] fault log: {n} events -> {args.fault_log}")
    if args.ci:
        assert len(done) == args.requests, \
            f"CI smoke: {len(done)}/{args.requests} requests completed"
        assert all(r.done and len(r.output) == args.max_new for r in done), \
            "CI smoke: a request missed its token budget"
        if mgr.kind == "paged":
            assert mgr.free_pages == mgr.num_pages, \
                f"CI smoke: page leak ({mgr.free_pages}/{mgr.num_pages} free)"
        # token parity: restored, fault-injected, eviction-pressured, and
        # fused/pipelined runs must all emit exactly what the plain
        # per-tick fault-free loop emits
        need_ref = (args.megatick > 1 or args.restore
                    or args.inject is not None or args.num_pages is not None
                    or model_par > 1)
        if need_ref:
            ref_engine, _ = run_engine(1)
            got = {r.uid: r.output for r in done}
            ref = {r.uid: r.output for r in ref_engine.completed}
            assert got == ref, \
                "CI smoke: tokens diverge from the fault-free megatick=1 " \
                "reference"
            ref_mgr = ref_engine.session.cache_mgr
            if ref_mgr.kind == "paged":
                assert ref_mgr.free_pages == ref_mgr.num_pages, \
                    "CI smoke: page leak in the megatick=1 reference"
            print("[serve] CI smoke OK (token-parity with the fault-free "
                  "megatick=1 reference)")
        else:
            print("[serve] CI smoke OK (paged-cache scheduler path "
                  "exercised)" if mgr.kind == "paged"
                  else "[serve] CI smoke OK")
    for r in done:
        line = (f"  req {r.uid}: {len(r.output)} tokens "
                f"exits={sum(1 for e in r.exit_points if e < model.num_exit_points)}")
        if r.evictions:
            line += f" evictions={r.evictions}"
        if mode == "tree":
            line += f" accepted={sum(r.accept_lens)}"
        print(line)


if __name__ == "__main__":
    main()

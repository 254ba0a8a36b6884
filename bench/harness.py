"""Runs one cell of BENCHMARK.json: set-up, the measured window, the
metrics, and the comparison with the plain reference that decides
``correct``.

Clock and spans. Every time is the host's ``perf_counter``. The loop wraps
each call into the program in a profiler annotation (``bench.submit``,
``bench.step``, ``bench.observe``, ``bench.wait``; the window itself is
``bench.window``), so a traced run can say what the host was doing in each
idle gap of the device.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from bench import traffic as traffic_lib
from bench.weights import Arch, load_config

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
SAMPLE_CAP = 64         # served tokens compared of each sampled request
                        # but the longest, which is compared in full
SAMPLE_TOKENS = 600     # served tokens the comparison covers at the least
DRAIN_S = 150.0         # how long requests due in the window may take after
WARM_NEW = 9            # tokens each warm-up request decodes (> 2 megaticks)


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Record:
    """What the host saw of one request."""
    req: traffic_lib.Request
    handle: object = None
    sent: Optional[float] = None        # when it was submitted
    admit: Optional[float] = None       # the step it left the queue
    first: Optional[float] = None       # the step its first token showed
    done: Optional[float] = None
    seen: int = 0
    row: Optional[int] = None           # the batch row it was first seen in
    events: List[tuple] = field(default_factory=list)  # (time, new tokens)

    @property
    def due(self) -> Optional[float]:
        return self.req.due


@dataclass
class Run:
    """Everything a metric reader may read of one run."""
    arch: Arch
    mix: dict
    records: List[Record]
    t0: float                  # the first request was sent (ramp start)
    window: tuple              # (open, close), host clock
    exit_points: int           # the model's exit points (units)
    peaks: dict
    trace: object = None       # bench.trace.Summary of the traced run
    row_tokens: List[tuple] = field(default_factory=list)  # Loop.ctx

    def in_window(self) -> List[Record]:
        return [r for r in self.records if r.req.phase == "window"]


def benchmark() -> dict:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_of(bm: dict, workload: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def metrics_for(bm: dict, workload: str, kind: str) -> List[dict]:
    return [m for m in bm[kind]
            if "workloads" not in m or workload in m["workloads"]]


def limits_of(workload: str) -> dict:
    with open(os.path.join(HERE, "limits", f"{workload}.json")) as f:
        return json.load(f)


def load_peaks(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def load_reader(name: str):
    """The reader of metric ``name``: ``bench/metrics/<name>.py``, or for a
    metric split by cell kind (``<base>.<kind>``) whose kinds read alike,
    ``bench/metrics/<base>.py``."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    if not os.path.exists(path):
        path = os.path.join(HERE, "metrics", f"{name.rsplit('.', 1)[0]}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``<checkout>/.jax_cache`` (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), for every program however small,
    so that only a checkout's first run compiles."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(CHECKOUT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCount:
    """Backend compiles (persistent-cache misses) while ``armed``."""

    def __init__(self):
        import jax
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def check_devices(chips: int) -> None:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found "
                     f"{len(devices)}")


# ----- the loop ----------------------------------------------------------
class Loop:
    def __init__(self, se, records: List[Record]):
        self.se, self.records, self.clock = se, records, time.perf_counter
        self.live: Dict[int, Record] = {}
        self.ctx: List[tuple] = []      # (time, context held, rows held)

    def submit(self, rec: Record) -> None:
        rec.handle = self.se.submit(rec.req.prompt,
                                    max_new_tokens=rec.req.max_new_tokens)
        rec.sent = self.clock()
        self.live[rec.handle.uid] = rec

    def step(self) -> None:
        with span("bench.step"):
            self.se.step()
        with span("bench.observe"):
            self.observe(self.clock())

    def observe(self, now: float) -> None:
        queued = set(self.se.scheduler.queued)
        for row, r in enumerate(self.se.slots):
            rec = self.live.get(r.uid) if r is not None else None
            if rec is not None and rec.row is None:
                rec.row = row
        for uid in list(self.live):
            rec = self.live[uid]
            h = rec.handle
            if rec.admit is None and uid not in queued:
                rec.admit = now
            n = len(h.output)
            if n > rec.seen:
                if rec.first is None:
                    rec.first = now
                rec.events.append((now, n - rec.seen))
                rec.seen = n
            if h.done:
                rec.done = now
                del self.live[uid]
        held = [r for r in self.se.slots if r is not None]
        self.ctx.append((now, sum(len(r.prompt) + len(r.output)
                                  for r in held), len(held)))

    def open_loop(self, t0: float, ramp_s: float, seconds: float,
                  on_open=None, on_close=None) -> tuple:
        """Send on the schedule, step while there is work, and follow every
        request to completion. Returns (window open, window close)."""
        ws, we = t0 + ramp_s, t0 + ramp_s + seconds
        opened = closed = False
        todo = list(self.records)
        i, deadline = 0, we + DRAIN_S
        while True:
            now = self.clock()
            if not opened and now >= ws:
                opened = True
                if on_open:
                    on_open()
            if opened and not closed and now >= we:
                closed = True
                if on_close:
                    on_close()
            if now > deadline:
                break
            with span("bench.submit"):
                while i < len(todo) and t0 + todo[i].due <= now:
                    self.submit(todo[i])
                    i += 1
            if self.se.busy:
                self.step()
            elif i < len(todo):
                wake = t0 + todo[i].due
                wake = min(wake, ws) if not opened else wake
                wake = min(wake, we) if not closed else wake
                with span("bench.wait"):
                    time.sleep(max(0.0, wake - now))
            elif closed:
                break
        return ws, we

    def closed_loop(self, batch: int, seconds: float,
                    on_open=None) -> tuple:
        """B clients, each with its own sequence of requests: a client sends
        its next request when its last one completes. The window opens once
        all B rows are decoding and closes at the first step that ends
        ``seconds`` later."""
        queue: Dict[int, List[Record]] = {}
        for rec in self.records:
            queue.setdefault(rec.req.client, []).append(rec)
        active = {c: q.pop(0) for c, q in queue.items()}
        for rec in active.values():
            self.submit(rec)
        ws = None
        while True:
            self.step()
            now = self.clock()
            for c, rec in active.items():
                if rec.done is not None and queue[c]:
                    active[c] = queue[c].pop(0)
                    with span("bench.submit"):
                        self.submit(active[c])
            if ws is None and sum(s is not None
                                  for s in self.se.slots) == batch:
                ws = now
                if on_open:
                    on_open()
            if ws is not None and now >= ws + seconds:
                return ws, now
            if not self.se.busy and not any(queue.values()):
                raise RuntimeError("the closed loop ran out of requests")


# ----- metrics -----------------------------------------------------------
def pct(values, q) -> Optional[float]:
    return float(np.percentile(values, q)) if len(values) else None


def end_to_end(run: Run, setup_s: float) -> Dict[str, float]:
    out = {"setup_s": setup_s}
    if run.mix["loop"] == "open":
        win = [r for r in run.in_window() if r.first is not None]
        ttft = [1e3 * (r.first - (run.t0 + r.due)) for r in win]
        tpot = [1e3 * (r.events[-1][0] - r.first) / (r.seen - 1)
                for r in win if r.done is not None and r.seen > 1]
        out["ttft_p50_ms"] = pct(ttft, 50)
        out["ttft_p90_ms"] = pct(ttft, 90)
        out["tpot_p50_ms"] = pct(tpot, 50)
    else:
        ws, we = run.window
        toks = sum(n for r in run.records for t, n in r.events
                   if ws < t <= we)
        out["tok_s"] = toks / (we - ws)
    return out


def load_details(run: Run) -> dict:
    """What a knee sweep reads: whether the window's requests all finished,
    and whether their queue wait grew from the first quarter of the window
    to the last (a growing backlog)."""
    win = sorted(run.in_window(), key=lambda r: r.due)
    q = max(1, len(win) // 4)
    wait = [(r.admit - (run.t0 + r.due)) * 1e3 if r.admit else None
            for r in win]
    head = [w for w in wait[:q] if w is not None]
    tail = [w for w in wait[-q:] if w is not None]
    return {"requests": len(win),
            "finished": sum(r.done is not None for r in win),
            "queue_ms_first_quarter": float(np.mean(head)) if head else None,
            "queue_ms_last_quarter": float(np.mean(tail)) if tail else None}


# ----- correctness -------------------------------------------------------
def sample(run: Run, seed: int) -> List[tuple]:
    """The compared requests, as (record, tokens compared or None for all):
    the one with the most served tokens, in full, and one drawn from the
    seed among those that held each other batch row, its first SAMPLE_CAP
    tokens, so every row the window filled is compared in every run; then
    more drawn from the seed, as many tokens each, until SAMPLE_TOKENS are
    covered."""
    if run.mix["loop"] == "open":
        pool = [r for r in run.in_window() if r.done is not None]
    else:
        pool = [r for r in run.records if r.seen > 0]
    if not pool:
        return []
    pool = sorted(pool, key=lambda r: (-r.seen, r.req.index))
    longest = pool[0]
    by_row: Dict[int, List[Record]] = {}
    for r in pool[1:]:
        by_row.setdefault(-1 if r.row is None else r.row, []).append(r)
    rng = np.random.default_rng([seed, 2])
    picked = [(longest, None)]
    for row in sorted(by_row):
        if row != longest.row:
            cands = by_row[row]
            picked.append((cands[int(rng.integers(len(cands)))], SAMPLE_CAP))
    rest = [r for r in pool[1:] if all(r is not p for p, _ in picked)]
    covered = sum(min(r.seen, cap or r.seen) for r, cap in picked)
    for i in rng.permutation(len(rest)):
        if covered >= SAMPLE_TOKENS:
            break
        picked.append((rest[i], SAMPLE_CAP))
        covered += min(rest[i].seen, SAMPLE_CAP)
    return picked


def compare(arch: Arch, seed: int, picked: List[tuple],
            exit_points: int, control: bool = False) -> dict:
    """Widest gap, over the compared served tokens of the sampled requests,
    by which each one's logit lies below the reference's best at the depth
    it was served from."""
    from bench.reference import served_gaps
    L = arch.layers
    seqs, depths, starts = [], [], []
    for rec, cap in picked:
        out = [int(t) for t in rec.handle.output]
        exits = [int(e) for e in rec.handle.exit_points]
        if len(exits) != len(out) - 1:
            raise RuntimeError(f"request {rec.req.index}: {len(out)} tokens "
                               f"but {len(exits)} exit points")
        out = out[:cap]
        exits = exits[:len(out) - 1]
        T = len(rec.req.prompt)
        seqs.append(np.concatenate([rec.req.prompt, out]).astype(np.int32))
        dep = np.full(T + len(out), L, np.int32)
        dep[T:T + len(exits)] = [e + 1 if e < exit_points else L
                                 for e in exits]
        depths.append(dep)
        starts.append(T - 1)
    gaps, ctl = served_gaps(arch, seed, seqs, depths, starts, control)
    allg = np.concatenate(gaps) if gaps else np.zeros(0)
    early = sum(int(np.sum(d[s + 1:] < L)) for d, s in zip(depths, starts))
    out = {"widest_gap": float(allg.max()) if allg.size else None,
           "tokens": int(allg.size), "requests": len(picked),
           "flipped": int(np.sum(allg > 0)), "early_exits": early}
    if ctl is not None:
        c = np.concatenate(ctl)
        out["control"] = {"widest_gap": float(c.max()),
                          "flipped": int(np.sum(c > 0))}
    return out


def verdict(widest_gap: Optional[float], limit: float, failed: int) -> bool:
    """``correct``: a gap was read, it is within the limit, and every
    request due in the window finished."""
    return widest_gap is not None and widest_gap <= limit and failed == 0


# ----- one run -----------------------------------------------------------
def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True,
             control: bool = False,
             config=None, mix=None, cell=None,
             limit: Optional[float] = None, details: bool = False) -> dict:
    """One run of one cell; returns the result object. ``config``, ``mix``,
    ``cell`` and ``limit`` override what BENCHMARK.json and bench/limits/
    name (tests drive tiny sizes on the CPU with them)."""
    bm = benchmark()
    cell = cell or cell_of(bm, workload)
    if require_chip:
        check_devices(cell["chips"])
    import jax
    devices = jax.devices()[:cell["chips"]]
    cache_dir = enable_compile_cache() if require_chip else None
    compiles = CompileCount()
    arch = Arch(config or load_config(cell["config"]))
    mix = mix or traffic_lib.load(cell["traffic"])
    serving = arch.cfg["serving"]
    B, K = serving["max_batch"], serving["megatick"]
    reqs = traffic_lib.generate(mix, seed, arch.vocab, seconds, B)
    records = [Record(r) for r in reqs]

    from bench import sut
    exits = mix.get("exits") if mix["strategy"] == "specee" else None
    model, params, sw, gate_c = sut.build(arch, seed, exits)
    se = sut.engine(model, params, sw, mix["strategy"], K)
    del params, sw
    say(f"{workload}: seed {seed}, {len(reqs)} requests, compile cache "
        f"{cache_dir}" + (f", exit gate c={gate_c:.6f}" if exits else ""))

    # warm-up: every distinct prompt length once, each decoding past two
    # megaticks, so nothing the window runs compiles inside it
    rng = np.random.default_rng([seed, 1])
    for n in traffic_lib.warm_lengths(reqs):
        se.submit(rng.integers(0, arch.vocab, n).astype(np.int32),
                  max_new_tokens=WARM_NEW)
    se.run_to_completion(max_ticks=100_000)
    se.completed.clear()
    mgr = se.session.cache_mgr
    if mgr.free_pages != mgr.num_pages:
        raise RuntimeError("warm-up left pages allocated")

    loop = Loop(se, records)
    tracer = Tracer() if trace else None
    window_span = []

    def open_window():
        # an annotation made before the profiler starts is never recorded
        if tracer:
            tracer.start()
        window_span.append(span("bench.window"))
        window_span[0].__enter__()

    def close_window():
        window_span[0].__exit__(None, None, None)

    compiles.armed = True
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    if mix["loop"] == "open":
        ws, we = loop.open_loop(t0, mix["ramp_s"], seconds,
                                on_open=open_window, on_close=close_window)
    else:
        ws, we = loop.closed_loop(B, seconds, on_open=open_window)
        close_window()
        se.drain()
    if tracer:
        tracer.stop()
    compiles.armed = False
    in_window_compiles = compiles.count

    peak = 0
    stats = [d.memory_stats() for d in devices]
    if all(stats):
        peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    run = Run(arch=arch, mix=mix, records=records, t0=t0, window=(ws, we),
              exit_points=model.num_exit_points,
              peaks=load_peaks(kind) if require_chip else {},
              row_tokens=loop.ctx)
    e2e = end_to_end(run, setup_s)
    window = run.in_window() if mix["loop"] == "open" else [
        r for r in records if r.sent is not None]
    failed = sum(1 for r in window if r.done is None) \
        if mix["loop"] == "open" else 0

    picked = sample(run, seed)
    del se, loop
    gc.collect()
    t_ref = time.perf_counter()
    cmp_ = compare(arch, seed, picked, model.num_exit_points, control)
    ref_s = time.perf_counter() - t_ref
    lim = {"max_gap": limit} if limit is not None else limits_of(workload)
    checks = {"widest_gap": {"value": cmp_["widest_gap"],
                             "limit": lim["max_gap"]}}
    correct = verdict(cmp_["widest_gap"], lim["max_gap"], failed)

    result = {"correct": bool(correct), "attempted": len(window),
              "failed": failed}
    if trace:
        if tracer:
            run.trace = tracer.summary()
            device["busy_s"] = run.trace.busy_s
            device["window_s"] = run.trace.window_s
        metrics = {}
        for m in metrics_for(bm, workload, "per_layer"):
            v = load_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        if run.trace is not None:
            result["breakdown"] = run.trace.breakdown()
    else:
        result["metrics"] = {
            m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
            for m in metrics_for(bm, workload, "end_to_end")
            if e2e.get(m["name"]) is not None}
        result["device"] = device
    if details:
        result["details"] = dict(e2e, **load_details(run))
    result["compared"] = {k: cmp_[k] for k in ("requests", "tokens",
                                                "flipped", "early_exits")}
    if "control" in cmp_:
        # the control in the program's place, judged by the same verdict
        c = result["control"] = dict(
            cmp_["control"],
            correct=verdict(cmp_["control"]["widest_gap"], lim["max_gap"],
                            failed))
        checks["control_widest_gap"] = {"value": c["widest_gap"],
                                        "limit": lim["max_gap"]}
        say(f"control int8: widest gap {c['widest_gap']} ({c['flipped']} "
            f"of {cmp_['tokens']} not the reference's argmax), correct "
            f"{c['correct']}")
    result["checks"] = checks
    say(f"window {we - ws:.3f} s, {len(window)} requests, {failed} failed, "
        f"compiles in window {in_window_compiles}, reference {ref_s:.1f} s, "
        f"compared {cmp_['requests']} requests / {cmp_['tokens']} tokens "
        f"({cmp_['flipped']} not the reference's argmax, "
        f"{cmp_['early_exits']} early exits)")
    say(f"setup_s {setup_s:.3f}; end to end "
        + ", ".join(f"{k} {v:.4f}" for k, v in e2e.items() if v is not None))
    for name, c in checks.items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    return result


class Tracer:
    """The JAX profiler across the measured window of a traced run."""

    def __init__(self):
        self.dir = os.path.join(CHECKOUT, ".bench_trace")
        self.on = False

    def start(self) -> None:
        import jax
        import shutil
        from bench.trace import options
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir, profiler_options=options())
        self.on = True

    def stop(self) -> None:
        import jax
        if self.on:
            jax.profiler.stop_trace()
            self.on = False

    def summary(self):
        from bench.trace import reduce_dir
        return reduce_dir(self.dir)

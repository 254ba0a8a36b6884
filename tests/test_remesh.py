"""Elastic remesh on device loss (DESIGN.md §10).

The tentpole invariant: a ``device_lost`` fault inside a sharded engine is
NOT a kill — the engine drains its in-flight megatick, consults
``plan_replica_remesh`` for the largest TP degree over the survivors,
rebuilds its Engine/session/scheduler in place, and re-admits every
unfinished request with verified replay. The degraded run must be
TOKEN-IDENTICAL (outputs AND per-request stats) to a fault-free single-
engine reference, leak zero pages, and leave a ``FaultEvent(action=
"remesh")`` in the log. Only when no factorization remains (unsharded
engine, no devices left) does the fault surface as
``ServingFault(site="device_lost")`` — standalone that's terminal; under a
``ReplicaPool`` it falls back to PR 9 kill-and-requeue, and the death of
the last replica still raises ``ServingFault(site="replica_pool")``.

Two layers of coverage:
  * in-process: ``ServingEngine.remesh(None)`` exercises the exact
    drain → rebuild → replay machinery (an unsharded engine remeshing to
    itself) across dense/specee/tree × dense/paged × kill tick {1,2,3}
    without needing a multi-device runtime;
  * subprocess (``--xla_force_host_platform_device_count``): real TP=2
    meshes losing a device mid-flight, standalone and under a 2-replica
    pool, remeshing to TP=1 with full parity.

Satellites ride along: deadline shedding + load-shed rejection
(degraded-mode serving), the ``FaultLog`` bounded ring + JSONL export,
and engine-level ``cancel``.
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.api import DenseStrategy
from repro.configs import get_config
from repro.core import engine as eng
from repro.models.model import build_model
from repro.runtime import faultinject
from repro.runtime.faultinject import FaultSchedule
from repro.serving import (FaultEvent, FaultLog, LoadShedPolicy, ReplicaPool,
                           ServingEngine, ServingFault)


@pytest.fixture(scope="module")
def setup():
    run = get_config("llama2-7b").smoke()
    run = dataclasses.replace(
        run, serve=dataclasses.replace(run.serve, max_batch=3))
    m = build_model(run)
    params = m.init(jax.random.PRNGKey(0))
    sw = eng.init_specee(m, jax.random.PRNGKey(1))
    return run, m, params, sw


def _prompts(run, n=4, seed=11):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, run.model.vocab_size, int(rng.integers(4, 12)))
            for _ in range(n)]


def _outputs(se):
    return {r.uid: list(r.output) for r in se.completed}


def _stats(se):
    return {r.uid: (list(r.exit_points), list(r.accept_lens))
            for r in se.completed}


def _assert_no_leak(se):
    mgr = se.session.cache_mgr
    if mgr.kind == "paged":
        assert mgr.free_pages == mgr.num_pages, \
            f"page leak: {mgr.free_pages}/{mgr.num_pages} free"


# ---------------- in-process: the rebuild+replay machinery ----------------
@pytest.mark.parametrize("strategy", ["dense", "specee", "tree"])
@pytest.mark.parametrize("cache", ["dense", "paged"])
def test_remesh_rebuild_replay_parity(setup, strategy, cache):
    """``remesh(None)`` mid-flight (the TP=1 -> TP=1 degenerate rebuild) is
    token- and stats-identical to a fault-free run for every kill tick in
    {1, 2, 3} — the drain/readmit/verified-replay core the device-loss path
    runs, minus the mesh swap (covered by the subprocess tests)."""
    run, m, params, sw = setup
    prompts = _prompts(run)

    def serve(remesh_at=None):
        se = ServingEngine(m, params, sw, strategy=strategy, megatick=2,
                           cache=cache)
        for p in prompts:
            se.submit(p, max_new_tokens=8)
        if remesh_at is not None:
            for _ in range(remesh_at):
                se.step()
            se.remesh(None, site="test", detail=f"tick{remesh_at}")
        se.run_to_completion()
        se.close()
        return se

    ref = serve()
    assert not ref.fault_log
    for kill_tick in (1, 2, 3):
        se = serve(remesh_at=kill_tick)
        assert _outputs(se) == _outputs(ref), (strategy, cache, kill_tick)
        assert _stats(se) == _stats(ref), (strategy, cache, kill_tick)
        events = [e for e in se.fault_log if e.action == "remesh"]
        assert len(events) == 1 and events[0].site == "test"
        assert "readmitted=" in events[0].detail
        _assert_no_leak(se)
        # replay actually VERIFIED the recorded prefix (not just re-emitted)
        replayed = [r for r in se.completed if r.replay_total]
        assert all(r.replayed == r.replay_total for r in replayed)


def test_remesh_sampled_run_parity(setup):
    """Sampled decode remeshes reproducibly: the rebuilt session re-seeds
    from the engine's original ``prng_seed`` and sample keys are position-
    keyed, so replay verification holds at temperature > 0 too."""
    run, m, params, sw = setup
    prompts = _prompts(run, n=3, seed=23)
    strat = DenseStrategy(temperature=1.0)

    def serve(remesh_at=None):
        se = ServingEngine(m, params, sw, strategy=strat, megatick=2,
                           prng_seed=7)
        for p in prompts:
            se.submit(p, max_new_tokens=8)
        if remesh_at is not None:
            for _ in range(remesh_at):
                se.step()
            se.remesh(None, site="test")
        se.run_to_completion()
        se.close()
        return se

    ref = serve()
    se = serve(remesh_at=2)
    assert _outputs(se) == _outputs(ref)
    _assert_no_leak(se)


# ---------------- device_lost: no-survivor fallback ladder ----------------
def test_device_lost_unsharded_engine_raises(setup):
    """An unsharded engine has no surviving devices to remesh onto: the
    injected loss drains what it can and surfaces site="device_lost" with a
    give_up (NOT remesh) fault event."""
    run, m, params, sw = setup
    se = ServingEngine(m, params, sw, strategy="specee", megatick=2)
    for p in _prompts(run, n=2):
        se.submit(p, max_new_tokens=6)
    with faultinject.injected(FaultSchedule.once("device_lost", visit=1)):
        with pytest.raises(ServingFault) as ei:
            se.run_to_completion()
    assert ei.value.site == "device_lost"
    assert any(e.action == "give_up" for e in se.fault_log)
    assert not any(e.action == "remesh" for e in se.fault_log)
    se.close()


def test_device_lost_pool_fallback_kill_and_requeue(setup):
    """Under a pool, an engine that CANNOT remesh falls back to PR 9
    kill-and-requeue: the survivor replays the dead replica's tokens and
    the final outputs still match a fault-free single-engine run."""
    run, m, params, sw = setup
    prompts = _prompts(run)

    ref = ServingEngine(m, params, sw, strategy="specee", megatick=2)
    for p in prompts:
        ref.submit(p, max_new_tokens=8)
    ref.run_to_completion()
    ref.close()
    ref_out = [list(r.output) for r in sorted(ref.completed,
                                              key=lambda r: r.uid)]

    pool = ReplicaPool([
        ServingEngine(m, params, sw, strategy="specee", megatick=2)
        for _ in range(2)])
    prs = [pool.submit(p, max_new_tokens=8) for p in prompts]
    with faultinject.injected(
            FaultSchedule.once("device_lost", visit=1)) as inj:
        pool.run_to_completion()
    assert inj.fired_sites() == frozenset({"device_lost"})
    assert sorted(pool.alive) == [False, True]
    kills = [e for e in pool.fault_log if e.action == "kill_replica"]
    assert kills and kills[0].site == "device_lost"
    assert not any(e.action == "remesh" for e in pool.fault_log)
    assert sum(pr.migrations for pr in prs) >= 1
    assert [list(pr.output) for pr in prs] == ref_out
    assert pool.degraded and pool.health.replicas_live == 1
    pool.close()


def test_device_lost_last_replica_raises_replica_pool(setup):
    """Exhausting the ladder entirely (single unsharded replica, device
    lost) still surfaces the PR 9 terminal fault: site="replica_pool"."""
    run, m, params, sw = setup
    pool = ReplicaPool([ServingEngine(m, params, sw, strategy="specee",
                                      megatick=2)])
    for p in _prompts(run, n=2):
        pool.submit(p, max_new_tokens=6)
    with faultinject.injected(FaultSchedule.once("device_lost", visit=1)):
        with pytest.raises(ServingFault) as ei:
            pool.run_to_completion()
    assert ei.value.site == "replica_pool"


# ---------------- degraded-mode serving: deadlines + load shedding -------
def test_deadline_shed(setup):
    """Requests past their deadline are SHED with a structured fault —
    queued or slotted — while undeadlined work completes normally and the
    cancelled rows leak no pages."""
    run, m, params, sw = setup
    prompts = _prompts(run)
    engine = ServingEngine(m, params, sw, strategy="specee", megatick=1,
                           prefill_chunk=0)
    pool = ReplicaPool([engine])
    shed = [pool.submit(prompts[i], max_new_tokens=48, deadline_ticks=3)
            for i in range(2)]
    kept = [pool.submit(prompts[i], max_new_tokens=6) for i in (2, 3)]
    pool.run_to_completion()
    for pr in shed:
        assert pr.failed and pr.done
        assert pr.fault is not None and pr.fault.site == "deadline"
        assert 0 < len(pr.output) < 48      # partial progress retained
    for pr in kept:
        assert pr.done and not pr.failed and len(pr.output) == 6
    assert pool.failed == shed
    assert [pr.uid for pr in pool.completed] == [pr.uid for pr in kept]
    sheds = [e for e in pool.fault_log if e.site == "deadline"]
    assert len(sheds) == 2 and all(e.action == "shed" for e in sheds)
    _assert_no_leak(engine)
    pool.close()


def test_deadline_generous_completes(setup):
    """A deadline the request beats is a no-op: no shed, no fault."""
    run, m, params, sw = setup
    pool = ReplicaPool([ServingEngine(m, params, sw, strategy="specee",
                                      megatick=2)])
    prs = [pool.submit(p, max_new_tokens=4, deadline_ticks=500)
           for p in _prompts(run, n=2)]
    pool.run_to_completion()
    assert all(pr.done and not pr.failed for pr in prs)
    assert not pool.failed and not pool.fault_log
    pool.close()


def test_load_shed_bounded_queue(setup):
    """``only_degraded=False`` bounds intake unconditionally: the queue
    admits up to max_queue, rejects beyond it with site="load_shed", and
    admits again once a pool tick drains the queue onto replicas."""
    run, m, params, sw = setup
    prompts = _prompts(run, n=3)
    pool = ReplicaPool(
        [ServingEngine(m, params, sw, strategy="specee", megatick=2)],
        shed=LoadShedPolicy(max_queue=1, only_degraded=False))
    pool.submit(prompts[0], max_new_tokens=4)
    with pytest.raises(ServingFault) as ei:
        pool.submit(prompts[1], max_new_tokens=4)
    assert ei.value.site == "load_shed"
    assert any(e.site == "load_shed" and e.action == "reject"
               for e in pool.fault_log)
    pool.step()                             # drains the queue onto slots
    pool.submit(prompts[2], max_new_tokens=4)
    done = pool.run_to_completion()
    assert len(done) == 2                   # the rejected one never entered
    pool.close()


def test_load_shed_only_when_degraded(setup):
    """The default policy sheds only while degraded: a healthy pool admits
    freely; after a replica death the same bound rejects."""
    run, m, params, sw = setup
    prompts = _prompts(run, n=3)
    pool = ReplicaPool(
        [ServingEngine(m, params, sw, strategy="specee", megatick=2)
         for _ in range(2)],
        shed=LoadShedPolicy(max_queue=0, only_degraded=True))
    assert not pool.degraded
    pool.submit(prompts[0], max_new_tokens=4)   # healthy: bound inactive
    pool.step()
    pool.kill_replica(1, reason="test")
    assert pool.degraded
    with pytest.raises(ServingFault) as ei:
        pool.submit(prompts[1], max_new_tokens=4)
    assert ei.value.site == "load_shed"
    pool.run_to_completion()
    pool.close()


def test_pool_health_snapshot(setup):
    run, m, params, sw = setup
    pool = ReplicaPool([ServingEngine(m, params, sw, strategy="specee")])
    h = pool.health
    assert (h.replicas_total, h.replicas_live) == (1, 1)
    assert h.tp_degrees == (1,) and h.built_tp_degrees == (1,)
    assert h.queued == 0 and h.degraded is False
    pool.close()


# ---------------- FaultLog ring + JSONL export ----------------
def test_fault_log_ring_bounds_and_counts():
    log = FaultLog(cap=4)
    assert not log and len(log) == 0 and log.dropped == 0
    for i in range(7):
        log.append(FaultEvent(site="health", tick=i, action="x"))
    assert len(log) == 4 and log.total == 7 and log.dropped == 3
    assert [e.tick for e in log] == [3, 4, 5, 6]
    assert log[0].tick == 3 and log[-1].tick == 6
    assert [e.tick for e in log[1:3]] == [4, 5]
    with pytest.raises(ValueError):
        FaultLog(cap=0)


def test_fault_log_dump_jsonl(tmp_path):
    log = FaultLog(cap=3)
    log.extend(FaultEvent(site="evict", tick=i, action="evict",
                          detail=f"row={i}") for i in range(5))
    path = str(tmp_path / "faults.jsonl")
    assert log.dump_jsonl(path, source="engine") == 3
    rows = [json.loads(l) for l in open(path)]
    # seq preserves the GLOBAL index: 2 dropped events leave a visible gap
    assert [r["seq"] for r in rows] == [2, 3, 4]
    assert rows[0] == {"seq": 2, "source": "engine", "site": "evict",
                       "tick": 2, "action": "evict", "detail": "row=2"}
    other = FaultLog()
    other.append(FaultEvent(site="deadline", tick=9, action="shed"))
    assert other.dump_jsonl(path, source="pool", append=True) == 1
    rows = [json.loads(l) for l in open(path)]
    assert len(rows) == 4 and rows[-1]["source"] == "pool"


# ---------------- engine-level cancel ----------------
def test_engine_cancel_queued_and_slotted(setup):
    run, m, params, sw = setup
    prompts = _prompts(run)
    se = ServingEngine(m, params, sw, strategy="specee", megatick=1,
                       prefill_chunk=0)
    reqs = [se.submit(p, max_new_tokens=6) for p in prompts]
    assert se.cancel(reqs[3].uid) is True       # still queued: withdrawn
    assert se.cancel(999) is False              # unknown uid
    se.step()                                   # admits 0..2 into slots
    assert se.cancel(reqs[0].uid) is True       # slotted: row retired
    se.run_to_completion()
    se.close()
    assert sorted(r.uid for r in se.completed) == [reqs[1].uid, reqs[2].uid]
    _assert_no_leak(se)


# ---------------- subprocess: real TP meshes losing a device -------------
def _run_subprocess(script: str) -> str:
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", script],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=900)
    return r.stdout + r.stderr


_TP2_REMESH = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import dataclasses
import jax
import numpy as np
from repro.configs import get_config
from repro.core import engine as eng
from repro.models.model import build_model
from repro.runtime import faultinject
from repro.runtime.faultinject import FaultSchedule
from repro.serving import ServingEngine
from jax.sharding import AxisType

run = get_config("llama2-7b").smoke()
run = dataclasses.replace(
    run, serve=dataclasses.replace(run.serve, max_batch=3))
m = build_model(run)
params = m.init(jax.random.PRNGKey(0))
sw = eng.init_specee(m, jax.random.PRNGKey(1))
rng = np.random.default_rng(5)
prompts = [rng.integers(0, run.model.vocab_size, int(rng.integers(4, 12)))
           for _ in range(4)]


def serve(cache, mesh=None):
    se = ServingEngine(m, params, sw, strategy="specee", megatick=2,
                       cache=cache, mesh=mesh)
    for p in prompts:
        se.submit(p, max_new_tokens=8)
    se.run_to_completion()
    se.close()
    return se


def outputs(se):
    return {r.uid: list(r.output) for r in se.completed}


def stats(se):
    return {r.uid: (list(r.exit_points), list(r.accept_lens))
            for r in se.completed}


for cache in ("dense", "paged"):
    ref = serve(cache)              # fault-free UNSHARDED reference
    for kill_tick in (1, 2, 3):
        mesh = jax.make_mesh((1, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2,
                             devices=jax.devices()[:2])
        with faultinject.injected(
                FaultSchedule.once("device_lost", visit=kill_tick)) as inj:
            se = serve(cache, mesh=mesh)
        assert inj.fired_sites() == frozenset({"device_lost"}), inj.fired
        assert se.tp_degree == 1, se.tp_degree
        ev = [e for e in se.fault_log if e.action == "remesh"]
        assert len(ev) == 1 and ev[0].site == "device_lost", list(se.fault_log)
        assert "tp 2->1" in ev[0].detail, ev[0].detail
        assert not any(e.action == "give_up" for e in se.fault_log)
        assert outputs(se) == outputs(ref), (cache, kill_tick)
        assert stats(se) == stats(ref), (cache, kill_tick)
        mgr = se.session.cache_mgr
        if mgr.kind == "paged":
            assert mgr.free_pages == mgr.num_pages, \\
                (mgr.free_pages, mgr.num_pages)
        print("ok", cache, kill_tick)
print("TP2-REMESH-OK")
"""


def test_device_lost_tp2_remeshes_to_tp1_subprocess():
    """The acceptance run: a TP=2 engine loses a device at tick {1,2,3}
    (dense AND paged cache) and remeshes to TP=1 — bit-identical tokens and
    stats vs the fault-free unsharded reference, zero page leak, exactly one
    FaultEvent(action="remesh"), no give_up/kill."""
    out = _run_subprocess(_TP2_REMESH)
    assert "TP2-REMESH-OK" in out, out


_POOL_REMESH = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import jax
import numpy as np
from repro.configs import get_config
from repro.core import engine as eng
from repro.models.model import build_model
from repro.runtime import faultinject
from repro.runtime.faultinject import FaultSchedule
from repro.launch.mesh import make_replica_meshes
from repro.serving import ReplicaPool, ServingEngine

run = get_config("llama2-7b").smoke()
run = dataclasses.replace(
    run, serve=dataclasses.replace(run.serve, max_batch=3))
m = build_model(run)
params = m.init(jax.random.PRNGKey(0))
sw = eng.init_specee(m, jax.random.PRNGKey(1))
rng = np.random.default_rng(7)
prompts = [rng.integers(0, run.model.vocab_size, int(rng.integers(4, 12)))
           for _ in range(4)]

ref = ServingEngine(m, params, sw, strategy="specee", megatick=2)
for p in prompts:
    ref.submit(p, max_new_tokens=8)
ref.run_to_completion()
ref.close()
ref_out = [list(r.output) for r in sorted(ref.completed,
                                          key=lambda r: r.uid)]

meshes = make_replica_meshes(2, 2)
pool = ReplicaPool([ServingEngine(m, params, sw, strategy="specee",
                                  megatick=2, mesh=ms) for ms in meshes])
assert pool.health.degraded is False
assert pool.health.tp_degrees == (2, 2), pool.health
prs = [pool.submit(p, max_new_tokens=8) for p in prompts]
with faultinject.injected(
        FaultSchedule.once("device_lost", visit=2)) as inj:
    pool.run_to_completion()
assert inj.fired_sites() == frozenset({"device_lost"})
# a remesh, NOT a kill: both replicas alive, one degraded to TP=1
assert pool.alive == [True, True], pool.alive
assert sorted(pool.health.tp_degrees) == [1, 2], pool.health
assert pool.health.degraded is True
assert all(pr.migrations == 0 for pr in prs)
assert any(e.action == "remesh" and e.site == "device_lost"
           for e in pool.fault_log)
assert any(e.action == "degraded" and e.site == "health"
           for e in pool.fault_log)
assert not any(e.action == "kill_replica" for e in pool.fault_log)
assert [list(pr.output) for pr in prs] == ref_out, "token divergence"
for rep in pool.replicas:
    mgr = rep.session.cache_mgr
    if mgr.kind == "paged":
        assert mgr.free_pages == mgr.num_pages
pool.close()
print("POOL-REMESH-OK")
"""


def test_device_lost_under_pool_remeshes_in_place_subprocess():
    """A 2x TP=2 pool absorbs a device loss as an IN-PLACE remesh of the
    affected replica (alive stays [True, True], zero migrations), the pool
    flips to degraded exactly once, and outputs match the fault-free
    unsharded single-engine reference."""
    out = _run_subprocess(_POOL_REMESH)
    assert "POOL-REMESH-OK" in out, out

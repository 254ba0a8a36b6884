"""The serving loop's telemetry: LM-head passes counted on the device per
tick, the engine's counters against the results they sum and across
megatick sizes and pipelining, and compiles put down to the innermost
open span."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import DenseStrategy, Engine, SpecEEStrategy, TreeStrategy
from repro.configs import get_config
from repro.core import engine as eng
from repro.core.tree import TreeSpec
from repro.models.model import build_model
from repro.runtime import telemetry
from repro.serving import ServingEngine


@pytest.fixture(scope="module")
def setup():
    run = get_config("llama2-7b").smoke()
    run = dataclasses.replace(
        run, specee=dataclasses.replace(run.specee, schedule_enabled=False))
    m = build_model(run)
    params = m.init(jax.random.PRNGKey(0))
    sw = eng.init_specee(m, jax.random.PRNGKey(1))
    return run, m, params, sw


def _results(session, K, max_new=7):
    prompts = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0, 512)
    session.prefill(prompts, max_new_tokens=max_new)
    out = []
    while not session.all_done():
        out.append(session.step(num_ticks=K))
    return out


# threshold 2: no predictor output exceeds it, so no verify runs;
# threshold -1: every output exceeds it, so (scheduling off) the verify
# runs at every unit the loop runs
@pytest.mark.parametrize("K", [1, 3])
@pytest.mark.parametrize("case", ["dense", "never_fires", "always_fires",
                                  "tree"])
def test_head_passes_per_tick(setup, case, K):
    run, m, params, sw = setup
    strategy = {"dense": DenseStrategy(),
                "never_fires": SpecEEStrategy(threshold=2.0),
                "always_fires": SpecEEStrategy(threshold=-1.0),
                "tree": TreeStrategy(tree=TreeSpec(depth=2, branch=2))}[case]
    e = Engine.create(m, params, sw, strategy=strategy)
    results = _results(e.new_session(), K)
    assert m.num_exit_points == sum(r for _, r in m.segments)  # 1 per unit
    for res in results:
        ticks, units = int(res.ticks), int(res.units_run)
        want = {"dense": ticks, "never_fires": 2 * ticks,
                "always_fires": 2 * ticks + units,
                "tree": 3 * ticks}[case]
        assert int(res.head_passes) == want, (case, ticks, units)


def _serve(m, params, sw, strategy, megatick, async_ticks, prompts):
    se = ServingEngine(m, params, sw, strategy=strategy, megatick=megatick,
                       async_ticks=async_ticks)
    seen = []
    collect = se._collect

    def recording(res, slots, finished):
        seen.append(res)
        return collect(res, slots, finished)
    se._collect = recording
    reqs = [se.submit(p, max_new_tokens=6) for p in prompts]
    se.run_to_completion()
    assert all(r.done and len(r.output) == 6 for r in reqs)
    return se, seen


@pytest.mark.parametrize("strategy", ["dense", "specee"])
def test_counters_same_across_megatick_and_pipelining(setup, strategy):
    """Two requests of one length admitted together: every tick runs the
    same batch whatever the megatick size or pipelining, so the counters
    agree, and they are the sums of the results the engine collected."""
    run, m, params, sw = setup
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, run.model.vocab_size, 9) for _ in range(2)]
    counts = {}
    for megatick, async_ticks in ((1, False), (4, True), (4, False)):
        se, seen = _serve(m, params, sw, strategy, megatick, async_ticks,
                          prompts)
        c = se.counters
        assert c.units_run == sum(int(r.units_run) for r in seen)
        assert c.head_passes == sum(int(r.head_passes) for r in seen)
        assert c.ticks == sum(int(r.ticks) for r in seen)
        assert c.decoded_tokens == 2 * 5     # the first token is prefill's
        assert c.prefill_tokens == 18 and c.prefill_chunks == 2
        counts[(megatick, async_ticks)] = c
    assert len({dataclasses.astuple(c) for c in counts.values()}) == 1
    assert counts[(1, False)].ticks == 5


def test_counters_snapshot_and_since():
    c = telemetry.Counters()
    c.add(ticks=4, units_run=40, head_passes=9)
    snap = c.snapshot()
    added = c.add(ticks=2, units_run=3)
    assert added == {"ticks": 2, "units_run": 3}
    assert c.since(snap) == telemetry.Counters(ticks=2, units_run=3)
    assert snap.ticks == 4


def test_compile_goes_to_innermost_span():
    before = dict(telemetry.compiles)
    x = jnp.arange(7.0)
    with telemetry.span("test.a"):
        with telemetry.span("test.b"):
            jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
        jax.jit(lambda v: v - 2.0)(x).block_until_ready()
    b, a = telemetry.compiles["test.b"], telemetry.compiles["test.a"]
    assert b[0] == 1 and b[1] > 0
    assert a[0] == 1 and "test.b" not in before
    assert "test.b: 1" in telemetry.compile_table()

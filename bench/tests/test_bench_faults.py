"""A run with the timed path broken underneath comes out not correct: the
chip check is skipped and the rest of a run is driven at tiny size, once
for each fault a serving cell can have (one chip has no exchange between
chips to leave out)."""
import jax.numpy as jnp
import pytest

from bench.tests import tiny  # noqa: I100 (puts the program on sys.path)


def _state_unchanged(monkeypatch):
    """Every decode tick returns the state it was given."""
    from repro.core import engine as eng
    real = eng.megatick_decode

    def broken(tick_fn, state, *a, **k):
        return real(lambda st: (tick_fn(st)[0], st), state, *a, **k)
    monkeypatch.setattr(eng, "megatick_decode", broken)


def _half_batch(monkeypatch):
    """The upper half of the batch is left out of every tick: those rows
    emit the token they were given."""
    from repro.core import engine as eng
    real = eng.megatick_decode

    def broken(tick_fn, state, *a, **k):
        def tick(st):
            em, new = tick_fn(st)
            B = st.last_token.shape[0]
            keep = jnp.arange(B) < B // 2
            toks = jnp.where(keep[:, None], em.tokens, st.last_token[:, None])
            return em._replace(tokens=toks), new
        return real(tick, state, *a, **k)
    monkeypatch.setattr(eng, "megatick_decode", broken)


def _token_altered(monkeypatch):
    """The third token of every request is altered where the engine
    records it."""
    from repro.serving import server
    real = server.ServingEngine._fold_tick

    def broken(self, req, toks, exit_point, accept_len):
        if len(req.output) == 2 and toks:
            toks = [(toks[0] + 1) % self.model.cfg.vocab_size] + toks[1:]
        return real(self, req, toks, exit_point, accept_len)
    monkeypatch.setattr(server.ServingEngine, "_fold_tick", broken)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "token_altered": _token_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", ["deepseek-7b-l14.chat",
                                      "starcoder2-15b-l10.code"])
def test_fault_is_not_correct(fault, workload, monkeypatch):
    FAULTS[fault](monkeypatch)
    r = tiny.run(workload, limit=0.05)
    assert not r["correct"], r
    assert r["checks"]["widest_gap"]["value"] > 0.05

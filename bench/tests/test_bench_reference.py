"""The plain references against the program at tiny sizes, for both
architectures, including served SpecEE requests with pinned exits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, sut, weights
from bench.tests import tiny

CONFIGS = ["deepseek-7b-l14", "starcoder2-15b-l10"]


@pytest.mark.parametrize("name", CONFIGS)
def test_one_layer_drawn_alone_is_the_stacks(name):
    arch = weights.Arch(tiny.config(name))
    key = weights.root_key(2 ** 40 + 17)
    stack = jax.jit(lambda k: weights.draw_stack(arch, k))(key)
    one = jax.jit(lambda k: weights.draw_layer(arch, k, 2))(key)
    for n, x in one.items():
        assert x.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(x), np.asarray(stack[n][2]))


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_logits_match_program_sequence_path(name):
    """In float32 the program's own sequence path (no cache, no kernel)
    and the reference compute the same logits at every position."""
    cfg = dict(tiny.config(name), torch_dtype="float32")
    arch = weights.Arch(cfg)
    model, params, _, _ = sut.build(arch, 11)
    toks = np.random.default_rng(0).integers(0, arch.vocab, 40)
    with jax.default_matmul_precision("highest"):
        _, _, extras = model.prefill(params, {"tokens": jnp.asarray(
            toks[None], jnp.int32)})
        got = np.asarray(model.logits(params, extras["h_final"])[0])
        key = weights.root_key(11)
        outer = weights.draw_outer(arch, key)
        h = outer["embed"][toks].astype(jnp.float32)
        depth = jnp.full((len(toks),), arch.layers)
        for i in range(arch.layers):
            w = weights.draw_layer(arch, key, i)
            h = reference.layer(arch, w, h, depth, i)
        want = np.asarray(reference.logits(arch, outer, h))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("workload", ["deepseek-7b-l14.chat",
                                      "starcoder2-15b-l10.batch-specee",
                                      "deepseek-7b-l14.batch-dense"])
def test_served_tokens_match_reference(workload):
    """A whole harness run at tiny size, the chip check skipped: every
    compared served token is within bf16 rounding of the reference's best
    at the depth it was served from; the SpecEE cells exit early."""
    r = tiny.run(workload, limit=0.05)
    assert r["correct"], r
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["compared"]["tokens"] >= 50
    specee = not workload.endswith("dense")
    assert (r["compared"]["early_exits"] > 0) == specee

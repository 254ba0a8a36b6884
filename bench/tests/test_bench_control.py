"""The control comes out not correct: the reference computed in int8 (the
precision below the configurations' bf16), teacher-forced on the served
tokens and judged by the run's own verdict, puts first tokens whose float32
gap the limit refuses, while the served tokens of the same run pass it. At tiny sizes the two readings
overlap on some seeds; these seeds and sizes separate them. Open-loop
mixes, whose compared requests do not depend on the host's speed, so the
readings repeat."""
import pytest

from bench.tests import tiny


# (workload, seed, width, vocabulary, limit): readings on the CPU were
# served 0.0 / control 0.116 and served 0.011 / control 0.122
@pytest.mark.parametrize("workload,seed,width,vocab,limit", [
    ("deepseek-7b-l14.chat", 4, 64, 512, 0.05),
    ("starcoder2-15b-l10.code", 5, 128, 4096, 0.04)])
def test_control_is_refused(workload, seed, width, vocab, limit):
    cfg = tiny.config(workload.split(".")[0], d=width, vocab=vocab)
    r = tiny.run(workload, seed=seed, limit=limit, control=True, config=cfg)
    assert r["correct"], r
    assert r["checks"]["widest_gap"]["value"] <= limit
    assert r["control"]["widest_gap"] > limit
    assert r["control"]["correct"] is False
    assert r["checks"]["control_widest_gap"]["value"] > limit
    assert r["control"]["flipped"] > r["compared"]["flipped"]

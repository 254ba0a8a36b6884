"""The traffic of every mix is the same multiset of work for every seed."""
import glob
import os

import numpy as np
import pytest

from bench import traffic

MIXES = sorted(os.path.basename(p)[:-5] for p in glob.glob(
    os.path.join(traffic.HERE, "traffic", "*.json")))
OPEN = [m for m in MIXES if traffic.load(m)["loop"] == "open"]


def _work(reqs):
    return (sorted(len(r.prompt) for r in reqs),
            sorted(r.max_new_tokens for r in reqs))


@pytest.mark.parametrize("name", MIXES)
def test_same_multiset_other_order(name):
    mix = traffic.load(name)
    a = traffic.generate(mix, 7, 49152, 51, 8)
    b = traffic.generate(mix, 2 ** 33 + 5, 49152, 51, 8)
    for phase in ("ramp", "window"):
        pa = [r for r in a if r.phase == phase]
        pb = [r for r in b if r.phase == phase]
        assert _work(pa) == _work(pb)
        if mix["loop"] == "open":
            ga = np.diff([r.due for r in pa])
            gb = np.diff([r.due for r in pb])
            # n-1 of the n stratified gaps: all but one agree as multisets
            assert len(set(np.round(ga, 9)) ^ set(np.round(gb, 9))) <= 2
        else:
            # each client sends the same sequence whatever the seed
            assert _clients(pa) == _clients(pb)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


def _clients(reqs):
    seqs = {}
    for r in reqs:
        seqs.setdefault(r.client, []).append((len(r.prompt),
                                              r.max_new_tokens))
    return seqs


@pytest.mark.parametrize("name", OPEN)
def test_window_arrivals_inside_window(name):
    mix = traffic.load(name)
    reqs = traffic.generate(mix, 1, 1000, 51, 8)
    win = [r.due for r in reqs if r.phase == "window"]
    ramp = [r.due for r in reqs if r.phase == "ramp"]
    assert len(win) == traffic.window_count(mix, 51)
    assert mix["ramp_s"] <= min(win) and max(win) < mix["ramp_s"] + 51
    assert 0.0 <= min(ramp) and max(ramp) < mix["ramp_s"]


def test_stratified_quantiles():
    spec = {"dist": "lognormal", "median": 128, "sigma": 0.8, "min": 32,
            "max": 512}
    x = traffic.lengths(spec, 101)
    assert x[50] == 128 and x.min() >= 32 and x.max() <= 512
    assert list(x) == sorted(x)
    u = traffic.lengths({"dist": "uniform", "min": 256, "max": 1024,
                         "grid": 64}, 4)
    # quantiles 352, 544, 736, 928 on a 64-token grid (ties to even)
    assert list(u) == [384, 512, 768, 896]
    g = traffic.gaps(2.0, 1000)
    assert abs(g.mean() - 0.5) < 0.01

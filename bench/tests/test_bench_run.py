"""The command as a check calls it, and the manifest against the
benchmark's files."""
import json
import os
import re
import subprocess
import sys

from bench import harness, weights

ROOT = harness.CHECKOUT


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "deepseek-7b-l14.chat", "--seed", str(2 ** 33 + 1), "--seconds",
         "51", "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_manifest_names_files_that_exist():
    bm = harness.benchmark()
    assert bm["command"][1] == "bench/run.py" and bm["paths"] == ["bench"]
    for c in bm["configs"]:
        cfg = weights.load_config(c["name"])
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"]
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
    e2e = {m["name"] for m in bm["end_to_end"]}
    for w in bm["workloads"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
        assert harness.limits_of(w["name"])["max_gap"] > 0
        mine = harness.metrics_for(bm, w["name"], "end_to_end")
        assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2
        layer = harness.metrics_for(bm, w["name"], "per_layer")
        assert layer and all(m["moves"] in {x["name"] for x in mine}
                             for m in layer)
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
        assert callable(harness.load_reader(m["name"]))
    json.dumps(bm)


def test_manifest_names_and_limits_of_form():
    bm = harness.benchmark()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bm[k]]
    assert len(names) == len(set(names)) and all(map(name.match, names))
    for w in bm["workloads"] + bm["configs"]:
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for w in bm["workloads"]:
        assert w["chips"] == 1 and name.match(w["traffic"])
    for m in bm["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert unit.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bm["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200
    assert 1 <= bm["run_seconds"] <= 51


def test_sample_compares_every_row():
    from bench import traffic

    def rec(i, row, seen):
        r = harness.Record(traffic.Request(i, None, seen, 0.0, "window"))
        r.row, r.seen, r.done = row, seen, 1.0
        return r
    records = [rec(i, i % 4, 10 + i) for i in range(12)]
    run = harness.Run(arch=None, mix={"loop": "open"}, records=records,
                      t0=0.0, window=(0.0, 1.0), exit_points=1, peaks={})
    for seed in (1, 2 ** 33 + 7):
        picked = harness.sample(run, seed)
        assert picked[0] == (records[11], None)      # the longest, in full
        assert sorted(r.row for r, _ in picked[:4]) == [0, 1, 2, 3]
        # then more, to SAMPLE_TOKENS or the whole pool
        assert len(picked) == len(records)
        assert all(cap == harness.SAMPLE_CAP for _, cap in picked[1:])

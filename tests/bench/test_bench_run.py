"""bench/run.py refuses to run without a TPU or without the program,
and BENCHMARK.json keeps to the benchmark's contract."""
import json
import os
import re
import shutil
import subprocess
import sys

import bench_small
from bench import harness

ROOT = bench_small.ROOT


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "oph-train",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_off_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout


def test_run_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    bench = harness.benchmark()
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns(".work", "__pycache__"))
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    assert "correct" not in proc.stdout


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keeps_the_contract():
    bench = harness.benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    metrics = list(e2e) + [m["name"] for m in bench["per_layer"]]
    for group in (names, cells, metrics):
        assert len(group) == len(set(group))
        assert all(NAME.match(n) for n in group)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        conf = harness.load_json(os.path.join(ROOT, c["file"]))
        assert conf["reduced"] == c["reduced"]
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
    pairs = set()
    four = 0
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        four += w["chips"] == 4
        traffic = harness.load_json(os.path.join(
            harness.BENCH_DIR, "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "runners", traffic["runner"] + ".py"))
        reported = harness.cell_metrics(w, bench, trace=False)
        assert "setup_s" in {m["name"] for m in reported}
        assert len(reported) >= 2
        assert harness.cell_metrics(w, bench, trace=True)
    assert four <= max(1, len(bench["workloads"]) // 2)
    for m in bench["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert os.path.exists(os.path.join(harness.BENCH_DIR, "metrics",
                                           m["name"] + ".py"))
        for cell in m.get("workloads", []):
            moved = e2e[m["moves"]]
            assert cell in moved.get("workloads", cells)
    assert len(json.dumps(bench)) < 64 * 1024

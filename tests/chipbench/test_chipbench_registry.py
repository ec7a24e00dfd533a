"""Configurations, traffic, loops and metric readers are found by name, a
new file is picked up without an edit, and ``BENCHMARK.json`` keeps to the
rules the check applies before any run."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return registry.benchmark()


def test_every_cell_finds_its_files(bench):
    for cell in bench["workloads"]:
        cfg = registry.config(cell["config"])
        traffic = registry.traffic(cell["traffic"])
        assert registry.loop(traffic["kind"]).serve
        assert cfg["shards"] > 0 and traffic["rate_qps"] > 0
        names = set(traffic["bootstrap"])
        for phase in traffic["phases"]:
            names |= {m["query"] for m in phase["mix"]} | set(phase["adapt"])
        assert names <= set(cfg.get("queries", cfg.get("templates")))
        readers = registry.readers(bench, cell["name"])
        assert set(readers) == {m["name"] for m in bench["per_layer"]}


def test_a_new_metric_file_is_picked_up(bench, tmp_path):
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(registry.HERE / sub, tmp_path / sub)
    (tmp_path / "metrics" / "demo.pass_count.py").write_text(
        "def read(ctx):\n    return len(ctx['run'].windows)\n")
    cell = bench["workloads"][0]["name"]
    other = dict(bench["workloads"][0], name="demo-other-cell")
    extended = dict(bench, workloads=bench["workloads"] + [other],
                    per_layer=bench["per_layer"] + [dict(
                        name="demo.pass_count", unit="count", better="lower",
                        source="host_clock", layer="service",
                        moves="query_p95_ms", workloads=[cell])])
    readers = registry.readers(extended, cell, tmp_path)
    assert "demo.pass_count" in readers

    class Run:
        windows = [1, 2, 3]
    assert readers["demo.pass_count"](dict(run=Run())) == 3
    assert "demo.pass_count" not in registry.readers(
        extended, other["name"], tmp_path)


def test_a_new_config_and_traffic_file_are_found(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps({"x": 1}))
    (tmp_path / "traffic" / "burst.json").write_text(json.dumps({"y": 2}))
    assert registry.config("tiny", tmp_path) == {"x": 1}
    assert registry.traffic("burst", tmp_path) == {"y": 2}
    with pytest.raises(KeyError):
        registry.reader("absent", tmp_path)


def test_benchmark_json_keeps_to_the_rules(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    cells = len(bench["workloads"])
    assert 1 <= cells <= 24
    # 24 cells at this window must fit the check's 43,200 seconds
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    root = registry.CHECKOUT
    for p in bench["paths"]:
        assert (root / p).is_dir()
    names = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200
        assert (root / c["file"]).is_file()
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
        names.add(c["name"])
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == cells
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    every = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(every) == len(set(every))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert (registry.HERE / "metrics" / f"{m['name']}.py").is_file()
    assert len(json.dumps(bench)) < 64 * 1024


def _run(argv, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable] + argv, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _last_json(stdout):
    for line in reversed(stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except ValueError:
            continue
    return None


def test_main_command_fails_without_a_tpu(bench):
    cell = bench["workloads"][0]["name"]
    p = _run(bench["command"][1:] + ["--workload", cell, "--seed", "7",
                                     "--seconds", "1", "--trace", "0"],
             registry.CHECKOUT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert _last_json(p.stdout) is None


def test_main_command_fails_with_only_the_benchmark_files(bench, tmp_path):
    shutil.copy(registry.CHECKOUT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(registry.CHECKOUT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cell = bench["workloads"][0]["name"]
    p = _run(bench["command"][1:] + ["--workload", cell, "--seed", "7",
                                     "--seconds", "1", "--trace", "0"],
             tmp_path)
    assert p.returncode != 0
    assert _last_json(p.stdout) is None

"""Cells are found by name from data files, and ``BENCHMARK.json``
keeps to the benchmark's contract."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import compare
import harness
import tinycell

SPEC_PATH = os.path.join(tinycell.ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    with open(SPEC_PATH) as f:
        return json.load(f)


def test_new_config_and_mix_become_a_cell(tmp_path):
    """A configuration and a traffic mix dropped in as files, with an
    entry in BENCHMARK.json, are a cell the harness finds by name."""
    root = tinycell.make_root(str(tmp_path))
    b = os.path.join(root, "bench")
    shutil.copy(os.path.join(b, "configs", "tiny.json"),
                os.path.join(b, "configs", "tiny2.json"))
    shutil.copy(os.path.join(b, "traffic", "train.json"),
                os.path.join(b, "traffic", "mix2.json"))
    shutil.copy(os.path.join(b, "checks", tinycell.CELL + ".json"),
                os.path.join(b, "checks", "tiny2.mix2.json"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny2", "source": "test",
                            "file": "bench/configs/tiny2.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny2.mix2", "config": "tiny2",
                              "traffic": "mix2", "chips": 1, "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    cell = harness.find_cell("tiny2.mix2", root)
    assert (cell.config_name, cell.traffic_name) == ("tiny2", "mix2")
    assert cell.config["hidden_size"] == 64
    assert [m["name"] for m in cell.end_to_end] == ["peer_tokens_per_s",
                                                    "setup_s"]
    # per-layer metrics list their cells: the new one is not among them
    assert cell.per_layer == []
    assert harness.entry(cell).run
    with pytest.raises(KeyError):
        harness.find_cell("tiny2.nope", root)


def test_every_cell_resolves_with_its_files(spec):
    for w in spec["workloads"]:
        cell = harness.find_cell(w["name"])
        assert cell.chips in (1, 4)
        assert harness.entry(cell).run
        assert harness.reference(cell).train_readings
        for m in cell.per_layer:
            assert harness.metric_reader(cell, m["name"]).read(None) is None
        # every compared number is one the comparison computes
        assert cell.limits and set(cell.limits) <= set(compare.NUMBERS)


def test_spec_keeps_to_the_contract(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["bench"]
    assert spec["command"] == ["python3", "bench/run.py"]
    assert 1 <= spec["run_seconds"] <= 51
    cells = len(spec["workloads"])
    # a full check of 24 cells fits its 43200 s
    assert 2 + 14 * 24 * (spec["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    names = set()
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        with open(os.path.join(tinycell.ROOT, c["file"])) as f:
            body = json.load(f)
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert any(w["config"] == c["name"] for w in spec["workloads"])
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        names.add(w["name"])
    assert len(names) == cells
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(1,
                                                                  cells // 2)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert set(m["workloads"]) <= names


def test_cpu_run_exits_nonzero_without_a_result():
    """No CPU fallback: without a TPU the run fails and prints no
    result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(tinycell.BENCH, "run.py"),
         "--workload", "templar-1b.peer-accum16", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tinycell.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""

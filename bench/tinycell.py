"""A test-sized cell for the benchmark's own tests: a checkout root in a
temporary directory holding a copy of ``bench/``, a tiny configuration
and training mix dropped in as files, and a ``BENCHMARK.json`` naming
the cell. The tests drive it on the CPU, skipping the look for a chip.
"""
from __future__ import annotations

import json
import os
import shutil
import time

import jax

import harness

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CELL = "tiny.train"


def make_root(tmp: str, *, layers: int = 2, dtype: str = "float32",
              limits_of: str = "templar-1b.peer-accum16") -> str:
    """A checkout root under ``tmp`` whose ``BENCHMARK.json`` holds the
    one cell ``tiny.train``: Qwen2's block (GQA, biases, tied head) at
    width 64, with the limits of the real cell ``limits_of``."""
    root = os.path.join(tmp, "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    b = os.path.join(root, "bench")
    with open(os.path.join(BENCH, "configs", "qwen2-1.5b.json")) as f:
        c = json.load(f)
    c.update(num_hidden_layers=layers, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, intermediate_size=128,
             vocab_size=250, logit_rows=256, compute_dtype=dtype)
    with open(os.path.join(BENCH, "traffic", "peer-accum16.json")) as f:
        t = json.load(f)
    t.update(batch=4, seq=32, microbatch=2, pool=4)
    shutil.copy(os.path.join(BENCH, "checks", limits_of + ".json"),
                os.path.join(b, "checks", CELL + ".json"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": CELL, "config": "tiny", "traffic": "train",
                          "chips": 1, "why": "test"}]
    for m in spec["per_layer"]:
        m["workloads"] = [CELL]
    for path, obj in ((os.path.join(b, "configs", "tiny.json"), c),
                      (os.path.join(b, "traffic", "train.json"), t),
                      (os.path.join(root, "BENCHMARK.json"), spec)):
        with open(path, "w") as f:
            json.dump(obj, f)
    return root


def run(root: str, *, seed: int = 3, step_fault=None) -> dict:
    """One run of the tiny cell on the CPU, as ``bench/run.py`` would
    make it but for the look for a chip; returns the result object."""
    cell = harness.find_cell(CELL, root)
    out = harness.entry(cell).run(
        cell, seed=seed, seconds=0.3, trace=False,
        t_start=time.perf_counter(), devices=jax.devices(),
        step_fault=step_fault)
    return harness.result(cell, out, traced=False)

"""Benchmark harness: resolves a cell of ``BENCHMARK.json`` by name to
the files that define it, checks the chips, and prints the result.

Everything about one configuration, traffic mix or per-layer metric
sits in a file of its own, found by the name ``BENCHMARK.json`` gives:

  bench/configs/<config>.json     sizes as run (the entry's ``file``)
  bench/reference/<ref>.py        the plain reference a config names
  bench/traffic/<traffic>.json    the entry a mix drives, and its sizes
  bench/entries/<entry>.py        one driver per entry point
  bench/checks/<cell>.json        the limits that decide ``correct``
  bench/metrics/<metric>.py       one reader per per-layer metric

So a later cell, mix or metric is a new file and a new entry in
``BENCHMARK.json``, and no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
from typing import Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = "bench"


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: str

    def path(self, *parts) -> str:
        return os.path.join(self.root, BENCH_DIR, *parts)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config = next(c for c in spec["configs"] if c["name"] == w["config"])
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(
        name=name, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]),
        config=_read_json(os.path.join(root, config["file"])),
        traffic=_read_json(os.path.join(root, BENCH_DIR, "traffic",
                                        w["traffic"] + ".json")),
        limits=_read_json(os.path.join(root, BENCH_DIR, "checks",
                                       name + ".json")),
        end_to_end=e2e, per_layer=per_layer, root=root)


def load_module(path: str, name: str):
    """Import the Python file ``path`` as module ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def entry(cell: Cell):
    e = cell.traffic["entry"]
    return load_module(cell.path("entries", e + ".py"), f"bench_entry_{e}")


def reference(cell: Cell):
    r = cell.config["reference"]
    return load_module(cell.path("reference", r + ".py"), f"bench_ref_{r}")


def metric_reader(cell: Cell, name: str):
    return load_module(cell.path("metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_"))


# ------------------------------------------------------------ device


def fail(msg: str, code: int = 2) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def require_chips(chips: int):
    """The devices to run on; exits non-zero, printing no result, where
    JAX finds no TPU or fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU found (JAX sees {devs[0].platform}); the benchmark "
             f"runs only on the chip")
    if len(devs) < chips:
        fail(f"cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def device_info(devs) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


class CompileWatch:
    """Counts JAX compile events while armed (``jax.monitoring``): the
    measured window must hold none."""

    def __init__(self):
        import jax
        self.armed = False
        self.events: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if self.armed and ("compile" in event or "trace" in event):
            self.events.append(event)

    def __enter__(self):
        self.events.clear()
        self.armed = True
        return self

    def __exit__(self, *exc):
        self.armed = False
        if exc[0] is None and self.events:
            raise RuntimeError(f"{len(self.events)} compile events inside "
                               f"the measured window: {self.events[:5]}")


# ------------------------------------------------------------ result


@dataclasses.dataclass
class Outcome:
    """What an entry hands back after its window and its check."""
    attempted: int
    failed: int
    metrics: Dict[str, float]          # end-to-end values by name
    checks: List[Tuple[str, float, float]]   # (name, value, limit)
    device: dict
    reader_ctx: Optional[dict] = None  # traced run: what readers read
    trace: Optional[dict] = None       # traced run: tracecut.reduce()


def passed(value: float, limit: float) -> bool:
    return math.isfinite(value) and value <= limit


def result(cell: Cell, out: Outcome, traced: bool) -> dict:
    """The result object, its keys in the order the contract sets."""
    correct = out.failed == 0 and all(passed(v, lim)
                                      for _, v, lim in out.checks)
    metrics = {}
    if traced:
        for m in cell.per_layer:
            v = metric_reader(cell, m["name"]).read(out.reader_ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": out.metrics[m["name"]],
                                  "unit": m["unit"]}
    device = dict(out.device)
    res = {"correct": correct, "attempted": out.attempted,
           "failed": out.failed, "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = out.trace["busy_s"]
        device["window_s"] = out.trace["window_s"]
        res["breakdown"] = {"device_ops": out.trace["device_ops"],
                            "idle_gaps": out.trace["idle_gaps"]}
    res["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim in out.checks}
    return res


def emit(res: dict) -> None:
    """Each compared number beside its limit as the last lines of
    standard error, then the result as the last line of standard out."""
    for name, c in res["checks"].items():
        ok = "ok" if passed(c["value"], c["limit"]) else "FAIL"
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)

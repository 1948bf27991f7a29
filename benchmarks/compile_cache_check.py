"""Cold-vs-warm persistent-compile-cache assertion (CI leg).

Runs ``gauntlet_bench --compile-cache`` TWICE in fresh subprocesses
sharing one cache directory (``JAX_COMPILATION_CACHE_DIR`` pointed at a
fixed path inside the checkout, emptied before the cold leg). The first
run compiles every round entry point cold and populates the cache; the
second run's round-0 "compile" is a cache deserialization. The gate
compares ``xla_compile_s`` — the cumulative XLA backend-compile seconds
the bench records via ``jax.monitoring`` (the event fires only on true
cache misses, i.e. exactly the work a persistent cache removes;
trace/lower time, which no cache can remove, is excluded) — and asserts
the warm run's total sits at least ``--min-ratio`` times below cold. The wall-clock compile
overhead (``compile_round_ms − steady_round_ms``) is printed alongside
as the user-visible effect.

Run:  PYTHONPATH=src python benchmarks/compile_cache_check.py
          [--peers 8] [--rounds 2] [--min-ratio 5.0]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "gauntlet_bench.py")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(BENCH)))
CACHE = os.path.join(ROOT, ".jax_cache", "compile_cache_check")
OUTS = os.path.join(ROOT, "experiments", "bench", "compile_cache_check")


def run_leg(label: str, cache_dir: str, out_path: str, peers, rounds,
            eval_chunk):
    cmd = [sys.executable, BENCH, "--rounds", str(rounds),
           "--peers", *[str(p) for p in peers],
           "--eval-chunk", str(eval_chunk),
           "--compile-cache", "--out", out_path]
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache_dir)
    env.setdefault("PYTHONPATH", os.path.join(ROOT, "src"))
    print(f"[{label}] {' '.join(cmd[1:])}", flush=True)
    subprocess.run(cmd, check=True, env=env, cwd=ROOT)
    with open(out_path) as f:
        return json.load(f)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--peers", type=int, nargs="*", default=[32])
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--eval-chunk", type=int, default=0,
                    help="0 (full vmap) keeps the measurement "
                         "compile-dominated: XLA compile scales with "
                         "the fused width while trace/lower — which no "
                         "cache can remove — stays flat")
    ap.add_argument("--min-ratio", type=float, default=5.0,
                    help="cold/warm compile-overhead ratio to require")
    args = ap.parse_args()
    shutil.rmtree(CACHE, ignore_errors=True)     # the cold leg starts empty
    os.makedirs(OUTS, exist_ok=True)
    cold = run_leg("cold", CACHE, os.path.join(OUTS, "cold.json"),
                   args.peers, args.rounds, args.eval_chunk)
    n_entries = sum(len(files) for _, _, files in os.walk(CACHE))
    assert n_entries > 0, (
        f"cold run left no entries in {CACHE} — persistent cache "
        f"not engaged (see repro.launch.compile_cache)")
    warm = run_leg("warm", CACHE, os.path.join(OUTS, "warm.json"),
                   args.peers, args.rounds, args.eval_chunk)
    cold_s = warm_s = 0.0
    for rc, rw in zip(cold["series"], warm["series"]):
        key = (rc["peers"], rc.get("mesh_devices", 0))
        assert key == (rw["peers"], rw.get("mesh_devices", 0))
        cold_ov = rc["compile_round_ms"] - rc["steady_round_ms"]
        warm_ov = rw["compile_round_ms"] - rw["steady_round_ms"]
        cold_s += rc["xla_compile_s"]
        warm_s += rw["xla_compile_s"]
        print(f"peers={key[0]} mesh={key[1]}: xla compile "
              f"{rc['xla_compile_s']:.1f} s → "
              f"{rw['xla_compile_s']:.1f} s; round-0 wall overhead "
              f"{cold_ov:.0f} ms → {warm_ov:.0f} ms")
    assert cold_s > 0, (
        f"cold run recorded no XLA compile time — is the "
        f"jax.monitoring backend_compile event gone?")
    ratio = cold_s / max(warm_s, 1e-3)
    assert ratio >= args.min_ratio, (
        f"warm XLA compile time only {ratio:.1f}x below cold "
        f"({cold_s:.1f} s → {warm_s:.1f} s, need "
        f"≥{args.min_ratio:.1f}x) — persistent cache miss?")
    print(f"compile cache check OK: XLA compile {cold_s:.1f} s cold "
          f"→ {warm_s:.1f} s warm ({ratio:.1f}x, "
          f"≥{args.min_ratio:.1f}x required), {n_entries} cache "
          f"entries")


if __name__ == "__main__":
    main()

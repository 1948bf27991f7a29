"""Readings that set a training cell's limits, in one process on the
chip: the program's gaps to the reference over many seeds, the
low-precision control's, and those of planted faults.

  python3 bench/calibrate.py --workload templar-1b.peer-accum16 \
      --seeds 11 12 13 --control-seeds 11 12 13 --fault-seeds 11 12 13 \
      --out chiprun_out/cal.jsonl

For each seed: the compiled step runs its first checked steps from the
seed (as a run's set-up does), then the float32 reference. For control
seeds the reference runs again with float8 matrix products in the
program's place. For fault seeds two faults are read: the program with
half of each batch left out (where a batch has two rows or more), and
the reference with its update applied the wrong way round in the
program's place (its copy of the parameters does not fit beside an
accumulating step). Each reading is one JSON line of ``--out`` and of
standard error: every number the comparison computes, with where it
was worst, and whether the cell's committed limits call it correct.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

import harness  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    harness.require_chips(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax

    import compare
    import faults
    ep = harness.entry(cell)
    prog = ep.Program(cell)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    def emit(kind, seed, got, ref):
        t0 = time.perf_counter()
        gaps = compare.gaps(got, ref)
        checks = compare.checks(gaps, cell.limits)
        row = {"cell": cell.name, "kind": kind, "seed": seed,
               "correct": all(harness.passed(v, lim) for _, v, lim in checks),
               "gaps": gaps,
               "compare_s": time.perf_counter() - t0,
               "got": compare.norms_only(got),
               "ref": compare.norms_only(ref)}
        line = json.dumps(row)
        print(line, file=sys.stderr, flush=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")

    planted = [faults.half_batch] if cell.traffic["batch"] >= 2 else []
    seeds = sorted(set(args.seeds) | set(args.control_seeds)
                   | set(args.fault_seeds))
    for seed in seeds:
        runs = {}
        with jax.set_mesh(prog.mesh):
            pool = prog.batches(seed)
            if seed in args.seeds:
                runs["program"] = prog.first_steps(seed, pool)[2]
            if seed in args.fault_seeds:
                for fault in planted:
                    runs[fault.__name__] = prog.first_steps(
                        seed, pool, fault(prog.step))[2]
            del pool
            gc.collect()
        ref = prog.reference(cell, seed, "float32")
        for kind in list(runs):
            emit(kind, seed, runs.pop(kind), ref)
        if seed in args.fault_seeds:
            emit("negated_update", seed,
                 prog.reference(cell, seed, "float32", flip_update=True), ref)
        if seed in args.control_seeds:
            emit("control_fp8", seed, prog.reference(cell, seed, "fp8"),
                 ref)
        del ref
        gc.collect()


if __name__ == "__main__":
    main()

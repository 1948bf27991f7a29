"""Run one benchmark cell on the chips of this machine.

  python3 bench/run.py --workload templar-1b.peer-accum16 --seed 7 \
      --seconds 30 --trace 0

Runs from the root of a checkout. Exits non-zero, printing no result,
where JAX finds no TPU or fewer chips than the cell asks for. The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared for
``correct`` beside its limit, which also end standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

import harness  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    devices = harness.require_chips(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"bench: compile cache {enable_compile_cache()}", file=sys.stderr)
    out = harness.entry(cell).run(cell, seed=args.seed, seconds=args.seconds,
                                  trace=bool(args.trace), t_start=T_START,
                                  devices=devices)
    harness.emit(harness.result(cell, out, bool(args.trace)))


if __name__ == "__main__":
    main()

"""Device time per step of each named scope of a cell's program, from a
profiler trace of its steady steps (``scopecut``).

  python3 bench/scope_split.py --workload templar-1b.peer-step2k \
      --seed 7 --seconds 10

Runs from the root of a checkout, on the chips the cell asks for. Builds
the cell's program as its entry does, warms it up, then traces the steps
of a window of ``--seconds`` (``bench.window``, one ``bench.step`` span
each). The last line of standard output is one JSON object:

- ``scope_ms``: device self time per step of each class of
  ``scopecut.CLASSES``; ``scoped_over_busy``: their sum over ``busy_s``;
- ``top_self_ms``: the three instructions with most self time per step
  in each class;
- ``idle_gaps``: the longest idle gaps, named by the innermost
  ``bench.*`` or ``gauntlet.*`` host span;
- ``parse_s``: the seconds taken to read the instruction map from the
  compiled program's text after the window.

With ``--out DIR`` it also writes ``DIR/<cell>.hlo.txt``, the compiled
program's text, and ``DIR/<cell>.self_ms.json``, every instruction's
self time per step, so that the split can be read again without a chip.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

import harness  # noqa: E402
import scopecut  # noqa: E402
import tracecut  # noqa: E402

WARMUP_STEPS = 2


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", help="directory for the HLO text and the "
                    "self time of every instruction")
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    harness.require_chips(cell.chips)
    import jax
    import numpy as np
    from jax.profiler import ProfileData
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    prog = harness.entry(cell).Program(cell)
    pool = prog.batches(args.seed)
    params, state = prog.weights(args.seed), prog._zeros()
    log_dir = tempfile.mkdtemp(prefix="bench_scopes_")
    with jax.set_mesh(prog.mesh):
        for i in range(WARMUP_STEPS):
            params, state, loss = prog.step(params, state, pool[i],
                                            np.int32(i))
        jax.block_until_ready((params, state, loss))
        jax.profiler.start_trace(log_dir)
        steps, i = 0, WARMUP_STEPS
        with jax.profiler.TraceAnnotation(tracecut.WINDOW_SPAN):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < args.seconds:
                with jax.profiler.TraceAnnotation("bench.step"):
                    params, state, loss = prog.step(
                        params, state, pool[i % len(pool)], np.int32(i))
                    jax.block_until_ready((params, state, loss))
                steps, i = steps + 1, i + 1
            window_s = time.perf_counter() - t0
        jax.profiler.stop_trace()
    t_parse = time.perf_counter()
    hlo_text = prog.step.as_text()
    names = scopecut.op_names(hlo_text)
    parse_s = time.perf_counter() - t_parse
    planes = list(ProfileData.from_file(tracecut.find_trace(log_dir)).planes)
    shutil.rmtree(log_dir, ignore_errors=True)
    reduced = tracecut.reduce(planes)
    selfs = scopecut.self_times(planes)
    classes = scopecut.by_class(selfs, names)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, cell.name + ".hlo.txt"), "w") as f:
            f.write(hlo_text)
        with open(os.path.join(args.out, cell.name + ".self_ms.json"),
                  "w") as f:
            json.dump({k: v * 1e3 / steps for k, v in selfs.items()}, f)
    top = {c: [] for c in scopecut.CLASSES}
    for instr, secs in sorted(selfs.items(), key=lambda kv: -kv[1]):
        row = top[scopecut.scope_class(names.get(instr))]
        if len(row) < 3:
            row.append([instr, secs * 1e3 / steps])
    print(json.dumps({
        "workload": cell.name, "seed": args.seed, "steps": steps,
        "window_s": reduced["window_s"], "busy_s": reduced["busy_s"],
        "host_window_s": window_s,
        "tokens_per_s": steps * cell.traffic["batch"] * cell.traffic["seq"]
        / window_s,
        "step_ms": window_s * 1e3 / steps,
        "scope_ms": {c: s * 1e3 / steps for c, s in classes.items()},
        "scoped_over_busy": sum(classes.values()) / reduced["busy_s"],
        "top_self_ms": top, "idle_gaps": scopecut.named_gaps(planes),
        "instructions": len(names), "parse_s": parse_s}), flush=True)


if __name__ == "__main__":
    main()

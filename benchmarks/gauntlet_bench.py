"""Gauntlet round-evaluation latency, retraces and memory vs. peer count
and validator mesh size.

Measures the validator's full round pipeline (fast-filter → uniqueness →
batched primary-eval → scoreboard → aggregate) across a peer-count
sweep, once per requested mesh size (``--mesh-devices 0 4`` runs a
no-mesh leg and a 4-device shard_map leg), and reports per (peers,
mesh_devices) row:

  * wall time per round (first round = compile, then steady-state
    median) and a per-stage wall-ms breakdown
    (``Validator.last_stage_ms``, medianed over the steady rounds)
  * compiled-call dispatches per round (``Validator.compiled_calls``)
  * compile counts per jitted entry point (``Validator.trace_counts_all``)
    — the rounds after warmup run with a *varying* |S_t| (the full set,
    half, three quarters), and the bench asserts the static-shape padded
    entry points add ZERO traces across that churn — on the mesh path
    too (shard_map'd entry points share the sticky pow2 buckets)
  * AOT memory analysis of the primary AND baseline entry points at the
    round's real operand shapes: full-vmap vs ``eval_chunk``-blocked
    temp bytes (the chunked numbers must stay materially below
    full-vmap at the largest peer count)
  * live ``device.memory_stats()`` after the last round (``null`` on
    CPU backends, real allocator telemetry on accelerators)

The result is written as a schema-stable ``BENCH_gauntlet.json``
(schema_version 3; committed at the repo root so later PRs have a perf
trajectory to regress against). ``--check PATH`` regresses the fresh
numbers against such a committed trajectory, matching series rows by
``(peers, mesh_devices)``, and FAILS on regression: trace counts and
compiled calls must match exactly, AOT memory within ``--mem-band``,
steady-round latency under ``--latency-band`` times committed.

``--expect-mesh-speedup X`` asserts the mesh leg's ms_per_peer at the
largest shared peer count is at least X times below the no-mesh leg's
(CI runs this on a forced multi-device host; a 1-core container shows
~parity and must not assert).

Peers are simulated by publishing format-valid random payloads through
ONE shared jitted fabricator (noise + compress fused: a single dispatch
per peer per round, which is what makes 1024-peer rounds practical to
generate). ``--scheme`` selects the gradient scheme. ``--compile-cache``
turns on the persistent XLA compilation cache so a second run compiles
warm (see repro.launch.compile_cache).

Run:  PYTHONPATH=src python benchmarks/gauntlet_bench.py [--rounds N]
          [--peers 8 16 32 64] [--mesh-devices 0 4] [--eval-chunk 8]
          [--scheme demo] [--compile-cache]
          [--out BENCH_gauntlet.json] [--check BENCH_gauntlet.json]
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import jax
import numpy as np

sys.path.insert(0, "benchmarks")
import common  # noqa: E402

from repro.comms.bucket import BucketStore          # noqa: E402
from repro.comms.chain import Chain                 # noqa: E402
from repro.configs.base import TrainConfig          # noqa: E402
from repro.configs.registry import tiny_config      # noqa: E402
from repro.core import scores as S                  # noqa: E402
from repro.core.gauntlet import Validator           # noqa: E402
from repro.data import pipeline                     # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_peer_mesh        # noqa: E402
from repro.models import model as M                 # noqa: E402
from repro.schemes import make_scheme               # noqa: E402
from repro.sharding import peer_mesh_size           # noqa: E402

BATCH, SEQ = 2, 32
# cumulative XLA backend-compile seconds (the part a persistent cache
# removes: the event only fires on true cache misses, so a warm run's
# total is ~0 — benchmarks/compile_cache_check.py gates on this)
_XLA_COMPILE_SECS = [0.0]


def _on_compile_event(name, secs, **_kw):
    if "backend_compile" in name:
        _XLA_COMPILE_SECS[0] += secs


jax.monitoring.register_event_duration_secs_listener(_on_compile_event)
# the five static-shape entry points whose traces must pin flat (the
# bench validator has no grad_fn, so replay/sketch never run here)
PINNED = ("sync_scores", "fingerprint", "baselines", "primary",
          "aggregate")
STAGES = ("fast_filter", "uniqueness", "primary_eval", "scoreboard",
          "aggregate")


def build(num_peers: int, eval_chunk: int, scheme_name: str,
          mesh_devices: int = 0, seed: int = 0, obs=None):
    cfg = tiny_config()
    hp = TrainConfig(learning_rate=3e-3, warmup_steps=2, total_steps=1000,
                     top_g=min(4, num_peers), eval_set_size=num_peers,
                     demo_chunk=16, demo_topk=8, eval_chunk=eval_chunk,
                     scheme=scheme_name)
    corpus = pipeline.MarkovCorpus(cfg.vocab_size, seed=seed)
    chain = Chain(blocks_per_round=10)
    store = BucketStore(chain)
    data_fns = {
        "assigned": lambda p, r: pipeline.select_data(
            corpus, seed, p, r, BATCH, SEQ),
        "unassigned": lambda p, r: pipeline.unassigned_data(
            corpus, seed, p, r, BATCH, SEQ),
    }
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    scheme = make_scheme(hp, params)
    eval_loss = jax.jit(lambda p, b: M.loss_fn(p, b, cfg)[0])
    mesh = make_peer_mesh(mesh_devices) if mesh_devices else None
    validator = Validator("validator-0", params, scheme, eval_loss, hp,
                          chain, store, data_fns,
                          rng=np.random.RandomState(seed), mesh=mesh,
                          obs=obs)
    uids = [f"peer-{i:04d}" for i in range(num_peers)]
    for uid in uids:
        chain.register_peer(uid, store.create_bucket(uid))

    # ONE jitted fabricator shared by every simulated peer: per-leaf
    # noise + scheme.compress fused into a single program keyed only by
    # the fold-in key, so publishing N peers is N dispatches, not N
    # traced tree-walks (the difference between 64- and 1024-peer
    # rounds being practical to generate)
    leaves, treedef = jax.tree.flatten(params)

    def _fabricate(key):
        noise = [0.01 * jax.random.normal(jax.random.fold_in(key, i),
                                          leaf.shape)
                 for i, leaf in enumerate(leaves)]
        return scheme.compress(jax.tree.unflatten(treedef, noise))

    return validator, chain, store, uids, jax.jit(_fabricate)


def publish_round(validator, chain, store, uids, fabricate, rnd: int):
    sync = S.sample_params_for_sync(validator.params,
                                    jax.random.PRNGKey(rnd))
    key = jax.random.PRNGKey(rnd * 7919 + 1)
    for i, uid in enumerate(uids):
        payload = fabricate(jax.random.fold_in(key, i))
        store.put_gradient(uid, rnd, payload,
                           validator.scheme.payload_bytes(payload))
        store.buckets[uid].put(f"sync/round-{rnd:08d}", sync,
                               chain.block, 8)


def eval_sizes(num_peers: int, rounds: int):
    """Round 0 runs the full set (pins the sticky buckets at their
    high-water mark); later rounds churn |S_t| and |F_t|."""
    cycle = [num_peers, max(num_peers // 2, 1),
             max(3 * num_peers // 4, 1)]
    return [num_peers] + [cycle[r % len(cycle)]
                          for r in range(rounds - 1)]


def live_memory_stats():
    """Allocator telemetry of device 0 (None on CPU backends)."""
    stats = jax.local_devices()[0].memory_stats()
    if not stats:
        return None
    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
            "largest_alloc_size")
    return {k: int(stats[k]) for k in keep if k in stats}


def bench(num_peers: int, rounds: int, eval_chunk: int,
          scheme: str = "demo", mesh_devices: int = 0, obs=None):
    validator, chain, store, uids, fabricate = build(
        num_peers, eval_chunk, scheme, mesh_devices, obs=obs)
    mesh_n = peer_mesh_size(validator.mesh) if mesh_devices else 0
    sizes = eval_sizes(num_peers, rounds)
    times, calls, stage_rows = [], [], []
    # the shared aggregate program's jit cache is process-wide, so count
    # this run's traces as deltas against the post-build snapshot
    base_traces = validator.trace_counts_all()
    warm_traces = None
    compile_s0 = _XLA_COMPILE_SECS[0]
    for rnd, n_active in enumerate(sizes):
        publish_round(validator, chain, store, uids, fabricate, rnd)
        chain.advance(chain.blocks_per_round)
        active = uids[:n_active]
        before = validator.compiled_calls
        t0 = time.perf_counter()
        rep = validator.run_round(rnd, active, fast_set_size=n_active)
        jax.block_until_ready(jax.tree.leaves(validator.params)[0])
        times.append((time.perf_counter() - t0) * 1e3)
        calls.append(validator.compiled_calls - before)
        stage_rows.append(dict(validator.last_stage_ms))
        assert len(rep.evaluated) == n_active
        if rnd == 0:
            warm_traces = validator.trace_counts_all()
    xla_compile_s = _XLA_COMPILE_SECS[0] - compile_s0
    final_traces = validator.trace_counts_all()
    churn_traces = {k: final_traces.get(k, 0) - warm_traces.get(k, 0)
                    for k in PINNED}
    # static-shape acceptance: churn must add ZERO compiles (with a
    # mesh this also pins the shard_map'd variants)
    assert all(v == 0 for v in churn_traces.values()), churn_traces
    mem_full = validator.primary_memory_analysis(eval_chunk=0)
    mem_chunked = validator.primary_memory_analysis(
        eval_chunk=eval_chunk or 0)
    bmem_full = validator.baseline_memory_analysis(eval_chunk=0)
    bmem_chunked = validator.baseline_memory_analysis(
        eval_chunk=eval_chunk or 0)
    steady = sorted(times[1:]) or times
    steady_stages = stage_rows[1:] or stage_rows
    stage_ms = {s: round(statistics.median(
        r.get(s, 0.0) for r in steady_stages), 3) for s in STAGES}
    return {"peers": num_peers, "mesh_devices": mesh_n,
            "rounds": rounds, "eval_set_sizes": sizes,
            "compile_round_ms": times[0],
            "xla_compile_s": round(xla_compile_s, 3),
            "steady_round_ms": steady[len(steady) // 2],
            "ms_per_peer": steady[len(steady) // 2] / num_peers,
            "stage_ms": stage_ms,
            "compiled_calls_per_round": calls[-1],
            "traces_per_entry": {k: final_traces.get(k, 0)
                                 - base_traces.get(k, 0)
                                 for k in PINNED},
            "traces_after_warmup": churn_traces,
            "primary_temp_bytes_full_vmap": mem_full.get("temp_bytes"),
            "primary_temp_bytes_chunked": mem_chunked.get("temp_bytes"),
            "primary_peak_bytes_full_vmap": mem_full.get("peak_bytes"),
            "primary_peak_bytes_chunked": mem_chunked.get("peak_bytes"),
            "baseline_temp_bytes_full_vmap": bmem_full.get("temp_bytes"),
            "baseline_temp_bytes_chunked": bmem_chunked.get("temp_bytes"),
            "device_memory": live_memory_stats()}


def check_against(committed_path: str, result: dict, mem_band: float,
                  latency_band: float) -> None:
    """Tolerance-banded regression against a committed trajectory
    (``bench-smoke`` fails on regression instead of being
    informational). Trace counts and compiled calls are deterministic —
    exact match; AOT memory is buffer assignment — a tight relative
    band; wall-clock latency is noisy on shared runners — an upper
    bound only. Series rows match on ``(peers, mesh_devices)`` (older
    schema-2 files carry no mesh column and compare as mesh 0)."""
    with open(committed_path) as f:
        committed = json.load(f)
    ccfg, cfg = committed["config"], result["config"]
    for key in ("eval_chunk", "model", "batch", "seq_len", "scheme"):
        assert ccfg.get(key, "demo" if key == "scheme" else None) \
            == cfg[key], (
            f"config mismatch on {key!r}: committed {ccfg.get(key)!r} vs "
            f"measured {cfg[key]!r} — regenerate {committed_path}")
    by_key = {(r["peers"], r.get("mesh_devices", 0)): r
              for r in committed["series"]}
    compared = 0
    for row in result["series"]:
        ref = by_key.get((row["peers"], row.get("mesh_devices", 0)))
        if ref is None:
            continue
        compared += 1
        p = (row["peers"], row.get("mesh_devices", 0))
        assert row["traces_per_entry"] == ref["traces_per_entry"], (
            p, row["traces_per_entry"], ref["traces_per_entry"])
        assert row["traces_after_warmup"] == ref["traces_after_warmup"], (
            p, row["traces_after_warmup"])
        assert (row["compiled_calls_per_round"]
                == ref["compiled_calls_per_round"]), (
            p, row["compiled_calls_per_round"],
            ref["compiled_calls_per_round"])
        for key in ("primary_temp_bytes_full_vmap",
                    "primary_temp_bytes_chunked",
                    "primary_peak_bytes_full_vmap",
                    "primary_peak_bytes_chunked",
                    "baseline_temp_bytes_full_vmap",
                    "baseline_temp_bytes_chunked"):
            got, want = row.get(key), ref.get(key)
            if want and got is not None:
                assert got <= want * (1.0 + mem_band), (
                    f"{key}@{p} regressed: {got} vs committed "
                    f"{want} (band {mem_band:.0%})")
        assert (row["steady_round_ms"]
                <= ref["steady_round_ms"] * latency_band), (
            f"steady_round_ms@{p} regressed: "
            f"{row['steady_round_ms']:.1f} vs committed "
            f"{ref['steady_round_ms']:.1f} (band {latency_band:.1f}x)")
    assert compared, (
        f"no comparable (peers, mesh_devices) rows between the measured "
        f"series and {committed_path} — regenerate the committed "
        f"trajectory")
    print(f"regression check vs {committed_path}: {compared} row(s) "
          f"within bands (mem {mem_band:.0%}, "
          f"latency {latency_band:.1f}x)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--peers", type=int, nargs="*",
                    default=[8, 16, 32, 64])
    ap.add_argument("--mesh-devices", type=int, nargs="*", default=[0],
                    help="validator mesh sizes to sweep (0 = no mesh; "
                         "each N>0 shards rounds over min(N, visible "
                         "devices) — force host devices with "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N before launch)")
    ap.add_argument("--mesh-peers", type=int, nargs="*", default=None,
                    help="peer counts for the mesh legs (defaults to "
                         "--peers)")
    ap.add_argument("--eval-chunk", type=int, default=8,
                    help="peers per fused decompress→loss block "
                         "(0 = full vmap)")
    ap.add_argument("--scheme", default="demo",
                    help="gradient scheme (repro.schemes registry name)")
    ap.add_argument("--compile-cache", action="store_true",
                    help="persistent XLA compilation cache in "
                         "$JAX_COMPILATION_CACHE_DIR, else .jax_cache/ "
                         "(second run compiles warm)")
    ap.add_argument("--out", default="BENCH_gauntlet.json",
                    help="schema-stable trajectory artifact "
                         "(committed at the repo root)")
    ap.add_argument("--check", default=None, metavar="PATH",
                    help="committed trajectory to regress against "
                         "(fails on regression)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the span tracer's Chrome trace JSON for "
                         "the LAST bench leg (open in ui.perfetto.dev) "
                         "— bench regressions come with a profile")
    ap.add_argument("--mem-band", type=float, default=0.25,
                    help="allowed relative growth of AOT memory bytes")
    ap.add_argument("--latency-band", type=float, default=4.0,
                    help="allowed steady-round latency multiple")
    ap.add_argument("--expect-mesh-speedup", type=float, default=None,
                    metavar="X",
                    help="assert mesh ms_per_peer beats no-mesh by ≥X "
                         "at the largest shared peer count (run on a "
                         "multi-device host)")
    args = ap.parse_args()
    if args.compile_cache:
        enable_compile_cache()
    legs = []
    for md in args.mesh_devices:
        peer_list = (args.mesh_peers if md and args.mesh_peers is not None
                     else args.peers)
        legs.extend((md, n) for n in peer_list)
    # --trace-out: attach the flight recorder's span tracer to the last
    # leg only — one profiled leg, zero overhead on the timed sweep
    trace_obs = None
    if args.trace_out:
        from repro.obs import FlightRecorder
        trace_obs = FlightRecorder(trace=True)
    rows = []
    for i, (md, n) in enumerate(legs):
        obs = trace_obs if (trace_obs is not None
                            and i == len(legs) - 1) else None
        rows.append(bench(n, args.rounds, args.eval_chunk,
                          args.scheme, mesh_devices=md, obs=obs))
    if trace_obs is not None:
        trace_obs.tracer.to_chrome_json(args.trace_out)
        print(f"Chrome trace of leg {legs[-1]} -> {args.trace_out} "
              f"({trace_obs.tracer.xla_compile_s:.1f}s attributed "
              f"compile; open in https://ui.perfetto.dev)")
    common.emit("gauntlet_bench", rows,
                ["peers", "mesh_devices", "compile_round_ms",
                 "steady_round_ms", "ms_per_peer",
                 "compiled_calls_per_round",
                 "primary_temp_bytes_full_vmap",
                 "primary_temp_bytes_chunked"])
    no_mesh = [r for r in rows if not r["mesh_devices"]]
    top = max(no_mesh or rows, key=lambda r: r["peers"])
    if args.eval_chunk and top["peers"] > args.eval_chunk:
        # bounded-memory acceptance at the largest peer count, for the
        # primary AND the streamed unique-batch baseline stacks
        assert (top["primary_temp_bytes_chunked"]
                < top["primary_temp_bytes_full_vmap"]), top
        assert (top["baseline_temp_bytes_chunked"]
                < top["baseline_temp_bytes_full_vmap"]), top
    result = {
        "benchmark": "gauntlet_bench",
        "schema_version": 3,
        "config": {"rounds": args.rounds, "eval_chunk": args.eval_chunk,
                   "model": "tiny", "batch": BATCH, "seq_len": SEQ,
                   "scheme": args.scheme,
                   "xla_devices": len(jax.devices()),
                   "compile_cache": bool(args.compile_cache)},
        "series": rows,
    }
    if args.check:
        check_against(args.check, result, args.mem_band,
                      args.latency_band)
    if args.expect_mesh_speedup:
        mesh_rows = [r for r in rows if r["mesh_devices"] > 1]
        assert mesh_rows and no_mesh, (
            "--expect-mesh-speedup needs a no-mesh leg and a >1-device "
            "mesh leg (is XLA_FLAGS forcing host devices?)")
        shared = (set(r["peers"] for r in mesh_rows)
                  & set(r["peers"] for r in no_mesh))
        assert shared, "mesh and no-mesh legs share no peer count"
        p = max(shared)
        base = next(r for r in no_mesh if r["peers"] == p)
        best = min((r for r in mesh_rows if r["peers"] == p),
                   key=lambda r: r["ms_per_peer"])
        speedup = base["ms_per_peer"] / best["ms_per_peer"]
        assert speedup >= args.expect_mesh_speedup, (
            f"mesh speedup at {p} peers = {speedup:.2f}x "
            f"({base['ms_per_peer']:.1f} → {best['ms_per_peer']:.1f} "
            f"ms/peer), expected ≥{args.expect_mesh_speedup:.2f}x")
        print(f"mesh speedup at {p} peers: {speedup:.2f}x "
              f"({best['mesh_devices']} devices)")
    common.emit_root_json(args.out, result)
    flat = {r["peers"]: r for r in (no_mesh or rows)}
    lo, hi = min(flat), max(flat)
    shrink = (flat[lo]["steady_round_ms"] / lo) / (
        flat[hi]["steady_round_ms"] / hi)
    mem_x = (top["primary_temp_bytes_full_vmap"]
             / max(top["primary_temp_bytes_chunked"] or 1, 1))
    print(f"\nper-peer cost {lo}→{hi} peers shrinks {shrink:.2f}x; "
          f"compiled calls/round: "
          f"{sorted(set(r['compiled_calls_per_round'] for r in rows))}; "
          f"churn retraces: 0/entry; primary temp memory at {hi} peers: "
          f"full-vmap/chunked = {mem_x:.1f}x")


if __name__ == "__main__":
    main()

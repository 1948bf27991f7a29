"""On-chip smoke test: the Gauntlet's main path on one TPU, at the
published widths of the paper's own model (templar-1b: d_model 2048, 32
heads, d_ff 8192, vocab 32000).

  python chip_smoke.py                # phase A, then phase B, one chip
  python chip_smoke.py --four-chips   # phase B sharded over 4 chips vs
                                      # the same rounds with no mesh

Phase A runs the peer's DeMo train step that ``repro.launch.train``
builds, for 3 steps at seq 2048. Phase B runs 6 Gauntlet rounds through
``build_sim`` / ``run_rounds``: one validator, two honest peers and one
lazy peer, with the uniqueness audit replaying local steps. Depth is cut
(see ``PHASE_A_LAYERS``, ``PHASE_B_LAYERS``); widths never are. Weights
are random, made from the config's seed, and data is the seeded corpus.

Everything runs in this one process (a chip belongs to one process).
The script refuses to run without a TPU, fails on any non-finite loss or
score, and prints as its last line one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

import jax

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# templar-1b at all 16 layers needs 17.07 GB for its train step (AOT
# memory_analysis for one v5e chip: 9.64 GB of params + error feedback,
# 7.43 GB of temporaries), more than the chip's 16.91 GB bytes_limit;
# 8 layers need 9.65 GB.
PHASE_A_LAYERS = 8
PHASE_A_STEPS = 3
PHASE_A_SEQ = 2048
PHASE_B_LAYERS = 2
PHASE_B_ROUNDS = 6
PHASE_B_BATCH = 2
PHASE_B_SEQ = 512
PEERS = (("honest-0", "honest"), ("honest-1", "honest"), ("lazy-0", "lazy"))


def templar(layers: int):
    from repro.configs.registry import get_config
    return get_config("templar-1b").with_overrides(
        num_layers=layers, peer_axes=("data",))


def phase_b_hp():
    from repro.configs.base import TrainConfig
    return TrainConfig(warmup_steps=2, total_steps=100, top_g=2,
                       eval_set_size=3, eval_chunk=1, poc_gamma=0.6)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(what)


def phase_a() -> None:
    from repro.configs.base import TrainConfig
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import peak_bytes_in_use, run_training

    cfg = templar(PHASE_A_LAYERS)
    print(f"[A] templar-1b cut to {PHASE_A_LAYERS} of 16 layers "
          f"({cfg.param_count() / 1e9:.3f}B params), batch 1 x seq "
          f"{PHASE_A_SEQ}, DeMo chunk 64 top-k 32", flush=True)
    hp = TrainConfig(warmup_steps=2, total_steps=100)
    out = run_training(cfg, hp, make_host_mesh(data=1),
                       steps=PHASE_A_STEPS, batch=1, seq=PHASE_A_SEQ)
    _check(all(math.isfinite(x) for x in out["losses"]),
           f"phase A: non-finite loss {out['losses']}")
    print(f"[A] ok: compile {out['compile_s']:.1f}s, step seconds "
          f"{out['seconds']}, peak_bytes_in_use {peak_bytes_in_use()}",
          flush=True)


def run_phase_b(cfg, mesh=None, tag: str = "B") -> dict:
    """Six rounds at templar width; returns host copies of what the
    four-chip comparison needs."""
    import numpy as np
    from repro.data import pipeline
    from repro.launch.train import peak_bytes_in_use
    from repro.training.peer import PeerConfig
    from repro.training.round_loop import build_sim, run_rounds

    hp = phase_b_hp()
    print(f"[{tag}] templar-1b widths at {cfg.num_layers} layers "
          f"({cfg.param_count() / 1e9:.3f}B params, {cfg.dtype} compute), "
          f"{len(PEERS)} peers x "
          f"batch {PHASE_B_BATCH} x seq {PHASE_B_SEQ}, mesh "
          f"{None if mesh is None else dict(mesh.shape)}", flush=True)
    pcs = [PeerConfig(uid=u, behavior=b) for u, b in PEERS]
    v, peers, chain, _, corpus = build_sim(
        cfg, hp, pcs, batch=PHASE_B_BATCH, seq_len=PHASE_B_SEQ,
        eval_batch=PHASE_B_BATCH, mesh=mesh)

    def heldout(rnd):
        return pipeline.unassigned_data(corpus, hp.seed, "heldout", rnd,
                                        PHASE_B_BATCH, PHASE_B_SEQ)

    reports = []
    for rnd in range(PHASE_B_ROUNDS):
        t0 = time.time()
        res = run_rounds(v, peers, chain, 1, eval_every=1,
                         eval_batch_fn=heldout)
        jax.block_until_ready((v.params, [p.params for p in peers.values()]))
        wall = time.time() - t0
        rep = res.reports[-1]
        reports.append(rep)
        scores = {**rep.loss_scores_assigned, **rep.loss_scores_rand}
        _check(all(math.isfinite(s) for s in scores.values())
               and all(math.isfinite(s) for s in rep.norm_scores.values()),
               f"round {rnd}: non-finite score {rep}")
        _check(math.isfinite(res.val_losses[-1]),
               f"round {rnd}: non-finite val loss {res.val_losses[-1]}")
        paid = [w for w in rep.weights.values() if w > 0]
        _check(not paid or abs(sum(paid) - 1.0) < 1e-6,
               f"round {rnd}: weights {rep.weights} do not sum to 1")
        mu = {u: round(v.peer_state[u].mu, 6) for u, _ in PEERS
              if u in v.peer_state}
        print(f"[{tag}] round {rnd}: {wall:.2f}s val_loss="
              f"{res.val_losses[-1]:.4f} "
              f"assigned={rep.loss_scores_assigned} "
              f"rand={rep.loss_scores_rand} mu={mu} "
              f"weights={rep.weights} flagged={rep.audit_flagged}",
              flush=True)
    traces = v.trace_counts_all()
    _check(all(n <= 1 for n in traces.values()),
           f"retraced entry point: {traces}")
    print(f"[{tag}] ok: traces {traces}, peak_bytes_in_use "
          f"{peak_bytes_in_use()} (process high-water mark)", flush=True)
    return {"reports": reports,
            "params": [np.asarray(x) for x in jax.tree.leaves(v.params)]}


def four_chips() -> None:
    """Phase B on a 4-device peer mesh against the same rounds unsharded:
    weights, audit flags and params exactly, loss scores within
    ``repro.sharding.MESH_SCORE_ATOL``.

    Both legs compute in float32 (activations and matmuls), as the
    CPU parity test does: templar-1b computes in bf16, and there the row
    split moves each loss by bf16 roundings. On a v5e the bf16 legs
    differed by up to 1.4e-4 in loss scores, with weights, flags and
    params equal."""
    import numpy as np
    from repro.launch.mesh import make_peer_mesh
    from repro.sharding import MESH_SCORE_ATOL

    cfg = templar(PHASE_B_LAYERS).with_overrides(dtype="float32")
    with jax.default_matmul_precision("highest"):
        ref = run_phase_b(cfg, mesh=None, tag="B/no-mesh")
        gc.collect()
        got = run_phase_b(cfg, mesh=make_peer_mesh(4), tag="B/4-chip")
    same_weights = same_flags = True
    worst = 0.0
    for a, b in zip(ref["reports"], got["reports"]):
        same_weights &= a.weights == b.weights
        same_flags &= a.audit_flagged == b.audit_flagged
        for s0, s1 in ((a.loss_scores_assigned, b.loss_scores_assigned),
                       (a.loss_scores_rand, b.loss_scores_rand)):
            _check(s0.keys() == s1.keys(), "eval sets differ")
            worst = max([worst] + [abs(s0[p] - s1[p]) for p in s0])
    same_params = all(np.array_equal(x, y)
                      for x, y in zip(ref["params"], got["params"]))
    print(f"[B/4-chip] vs no-mesh: weights equal {same_weights}, flags "
          f"equal {same_flags}, params equal {same_params}, largest "
          f"loss-score gap {worst} (bound {MESH_SCORE_ATOL})", flush=True)
    _check(same_weights and same_flags and same_params,
           "weights, flags or params differ")
    _check(worst <= MESH_SCORE_ATOL,
           f"loss scores differ by {worst} > {MESH_SCORE_ATOL}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only phase B, on a 4-chip peer mesh and "
                         "with no mesh, and compare")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX sees {dev.platform}); "
                 f"this script runs only on the chip")
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device {dev.device_kind} x {len(jax.devices())}, "
          f"bytes_limit {dev.memory_stats().get('bytes_limit')}, "
          f"compile cache {enable_compile_cache()}", flush=True)
    if args.four_chips:
        four_chips()
    else:
        phase_a()
        gc.collect()
        run_phase_b(templar(PHASE_B_LAYERS))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()

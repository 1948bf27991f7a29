"""Training launcher: run the production DeMo (or DDP) train step for
real steps on whatever devices exist.

On a CPU host it runs reduced configs on the host mesh; on a TPU pod the
same command with ``--mesh single|multi`` builds the production mesh and
executes the identical StepPlan that the dry-run compiles.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
      --steps 5 --reduced                         # CPU smoke
  python -m repro.launch.train --arch yi-34b --mesh single ...  # on TPU
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig, TrainConfig
from repro.configs.registry import (ASSIGNED_ARCHS, get_config,
                                    reduced_config)
from repro.data import pipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.steps import make_step
from repro.models import model as M
from repro.training.checkpoint import save_checkpoint


def peak_bytes_in_use() -> Optional[int]:
    """Device 0's peak allocation so far, where the backend reports it."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_training(cfg: ModelConfig, hp: TrainConfig, mesh, *, steps: int,
                 batch: int, seq: int, variant: str = "demo",
                 microbatch: int = 1) -> Dict:
    """Compile the ``variant`` train step for ``mesh`` and run ``steps``
    steps of it on the seeded corpus, one line printed per step.

    Params and optimizer state are donated to the step (its inputs are
    rebound every step). Returns the final params, per-step losses and
    wall seconds (after ``block_until_ready``) and the compile seconds.
    """
    shape = InputShape("cli", seq_len=seq, global_batch=batch,
                       kind="train")
    plan = make_step(cfg, hp, mesh, shape, variant=variant, ce_chunks=0,
                     microbatch=microbatch)
    print(f"lowering {plan.name} on mesh {dict(mesh.shape)} ...",
          flush=True)
    t0 = time.time()
    compiled = plan.lower(mesh).compile()
    compile_s = time.time() - t0
    print(f"compiled in {compile_s:.1f}s", flush=True)

    key = jax.random.PRNGKey(hp.seed)
    params = (M.init_params_stacked(cfg, key)
              if "groups" in plan.args[0] else M.init_params(cfg, key))
    corpus = pipeline.MarkovCorpus(cfg.vocab_size, seed=hp.seed)

    # state arg: EF buffers (demo) / AdamW moments (ddp), zeros like SDS
    state = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         plan.args[1])
    text_len = plan.args[2]["tokens"].shape[1]
    losses: List[float] = []
    seconds: List[float] = []
    with jax.set_mesh(mesh):
        for step_i in range(steps):
            data = pipeline.select_data(corpus, hp.seed, "launcher",
                                        step_i, batch, seq)
            data = {k: v[:, :text_len] for k, v in data.items()}
            if cfg.frontend is not None:
                data.update({
                    k: v for k, v in pipeline.synthetic_batch(
                        jax.random.fold_in(key, step_i), cfg.vocab_size,
                        batch, seq, cfg).items()
                    if k in ("patch_embeds", "frames")})
            t0 = time.time()
            params, state, loss = compiled(params, state, data,
                                           jnp.int32(step_i))
            jax.block_until_ready(loss)
            seconds.append(time.time() - t0)
            losses.append(float(loss))
            print(f"step {step_i}: loss={losses[-1]:.4f} "
                  f"({seconds[-1]:.2f}s, peak_bytes_in_use="
                  f"{peak_bytes_in_use()})", flush=True)
    return {"params": params, "losses": losses, "seconds": seconds,
            "compile_s": compile_s, "name": plan.name}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b",
                    choices=list(ASSIGNED_ARCHS) + ["templar-1b"])
    ap.add_argument("--variant", default="demo", choices=["demo", "ddp"])
    ap.add_argument("--mesh", default="host",
                    choices=["host", "single", "multi"])
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale variant of the arch (CPU-friendly)")
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--checkpoint", default="",
                    help="save a checkpoint here at the end")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = (reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    if args.mesh == "host":
        cfg = cfg.with_overrides(peer_axes=("data",))
        mesh = make_host_mesh(data=len(jax.devices()))
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
    hp = TrainConfig(learning_rate=1e-3, warmup_steps=2,
                     total_steps=max(args.steps, 4),
                     demo_chunk=16, demo_topk=8, demo_beta=0.9)
    out = run_training(cfg, hp, mesh, steps=args.steps, batch=args.batch,
                       seq=args.seq, variant=args.variant,
                       microbatch=args.microbatch)
    if args.checkpoint:
        save_checkpoint(args.checkpoint, out["params"], args.steps)
        print(f"checkpoint -> {args.checkpoint}")
    print("ok")


if __name__ == "__main__":
    main()

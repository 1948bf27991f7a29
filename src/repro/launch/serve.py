"""Serving launcher: execute the production ``serve_step`` (single-token
decode against a KV/state cache) for real tokens.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b --reduced
  python -m repro.launch.serve --arch yi-34b --mesh single   # on TPU
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape
from repro.configs.registry import (ASSIGNED_ARCHS, get_config,
                                    reduced_config)
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.steps import make_serve_step, use_scan
from repro.models import model as M


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b",
                    choices=list(ASSIGNED_ARCHS) + ["templar-1b"])
    ap.add_argument("--mesh", default="host",
                    choices=["host", "single", "multi"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--reduced", action="store_true")
    args = ap.parse_args(argv)

    cfg = (reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    if args.mesh == "host":
        cfg = cfg.with_overrides(peer_axes=("data",))
        mesh = make_host_mesh(data=len(jax.devices()))
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
    shape = InputShape("cli", seq_len=args.cache_len,
                       global_batch=args.batch, kind="decode")
    plan = make_serve_step(cfg, mesh, shape)
    print(f"lowering {plan.name} on mesh {dict(mesh.shape)} ...")
    t0 = time.time()
    compiled = plan.lower(mesh).compile()
    print(f"compiled in {time.time() - t0:.1f}s")

    key = jax.random.PRNGKey(0)
    scan = use_scan(cfg)
    params = (M.init_params_stacked(cfg, key) if scan
              else M.init_params(cfg, key))
    cache = M.init_cache(cfg, args.batch, args.cache_len)
    if scan:
        cache = M.group_cache(cache, cfg)
    tok = jax.random.randint(key, (args.batch, 1), 0, cfg.vocab_size)
    outs = []
    with jax.set_mesh(mesh):
        t0 = time.time()
        for _ in range(args.tokens):
            logits, cache = compiled(params, cache, tok)
            tok = jnp.argmax(logits[:, -1:, :cfg.vocab_size], axis=-1)
            outs.append(int(tok[0, 0]))
        jax.block_until_ready(tok)
    dt = time.time() - t0
    print(f"decoded {args.tokens} steps x {args.batch} seqs in {dt:.2f}s "
          f"({args.tokens * args.batch / dt:.1f} tok/s)")
    print("seq0 continuation:", outs)
    assert all(jnp.isfinite(logits).all() for _ in [0])
    print("ok")


if __name__ == "__main__":
    main()

import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input-shape x mesh) combination on 512 placeholder
devices, print memory/cost analysis, and dump roofline JSON.

Usage:
  python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all --mesh single
  python -m repro.launch.dryrun ... --multi-pod          # 2x16x16
  python -m repro.launch.dryrun ... --variant ddp        # AdamW baseline
"""
import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs.base import TrainConfig
from repro.configs.registry import ASSIGNED_ARCHS, get_config, get_shape
from repro.configs.shapes import SHAPES
from repro.launch import analysis
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, make_production_mesh
from repro.launch.steps import make_step

# combos that are skipped by design (DESIGN.md §5)
SKIPS = {
    ("whisper-base", "long_500k"):
        "enc-dec ASR decoder capped at 448 positions; 524k decode out of "
        "domain",
}


def run_one(arch: str, shape_name: str, *, multi_pod: bool, variant: str,
            out_dir: str, remat: bool = True, ce_chunks: int = 16,
            agg_sharding: str = "param", donate: bool = True,
            ef_dtype: str = None, tag: str = "", microbatch: int = 1,
            chunk_len: int = 0, intra_dtype: str = "",
            verbose: bool = True) -> dict:
    cfg = get_config(arch)
    if cfg.ssm is not None and (chunk_len or intra_dtype):
        import dataclasses as _dc
        ssm = cfg.ssm
        if chunk_len:
            ssm = _dc.replace(ssm, chunk_len=chunk_len)
        if intra_dtype:
            ssm = _dc.replace(ssm, intra_dtype=intra_dtype)
        cfg = cfg.with_overrides(ssm=ssm)
    shape = get_shape(shape_name)
    if (arch, shape_name) in SKIPS:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": SKIPS[(arch, shape_name)]}
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "multi" if multi_pod else "single"
    chips = mesh.devices.size
    hp = TrainConfig()
    t0 = time.time()
    train_kw = {}
    if shape.kind == "train":
        train_kw = {"remat": remat, "ce_chunks": ce_chunks,
                    "donate": donate, "microbatch": microbatch}
        if variant == "demo":
            train_kw.update(agg_sharding=agg_sharding, ef_dtype=ef_dtype)
    plan = make_step(cfg, hp, mesh, shape, variant=variant, **train_kw)
    lowered = plan.lower(mesh)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    memstats = compiled.memory_analysis()
    roof = analysis.analyze(
        compiled, lowered, arch=arch, shape_name=shape_name,
        mesh_name=mesh_name, variant=variant, chips=chips,
        model_flops=analysis.model_flops(cfg, shape),
        device_kind=PRODUCTION_DEVICE_KIND)
    rec = roof.to_dict()
    rec.update(status="ok", lower_s=round(t_lower, 1),
               compile_s=round(t_compile, 1),
               memory_analysis=str(memstats))
    if verbose:
        print(f"== {plan.name} mesh={mesh_name}({chips}) variant={variant}")
        print(f"   memory_analysis: {memstats}")
        print(f"   cost: {roof.hlo_gflops:.1f} GFLOP, "
              f"{roof.hlo_gbytes:.1f} GB accessed, "
              f"{roof.collective_gbytes:.3f} GB collectives "
              f"{roof.collective_breakdown}")
        print(f"   roofline: compute={roof.compute_s*1e3:.2f}ms "
              f"memory={roof.memory_s*1e3:.2f}ms "
              f"collective={roof.collective_s*1e3:.2f}ms "
              f"-> dominant={roof.dominant} "
              f"useful_flops={roof.useful_flops_ratio:.2f}")
        print(f"   lower={t_lower:.1f}s compile={t_compile:.1f}s",
              flush=True)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        fn = f"{arch}__{shape_name}__{mesh_name}__{variant}{suffix}.json"
        with open(os.path.join(out_dir, fn), "w") as f:
            json.dump(rec, f, indent=2)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--variant", default="demo", choices=["demo", "ddp"])
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--ce-chunks", type=int, default=16,
                    help="chunked CE (production default; 0 = naive full "
                         "logits, the paper-faithful baseline)")
    ap.add_argument("--agg-sharding", default="param",
                    choices=["param", "replicated"])
    ap.add_argument("--no-donate", action="store_true")
    ap.add_argument("--ef-dtype", default=None,
                    help="error-feedback buffer dtype (default param_dtype)")
    ap.add_argument("--tag", default="",
                    help="suffix for the output JSON (perf iterations)")
    ap.add_argument("--microbatch", type=int, default=1,
                    help="gradient-accumulation microbatches per round")
    ap.add_argument("--chunk-len", type=int, default=0,
                    help="override ssm chunked-scan length (perf knob)")
    ap.add_argument("--intra-dtype", default="",
                    help="override ssm intra-chunk matmul dtype")
    args = ap.parse_args(argv)

    archs = list(ASSIGNED_ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    failures = []
    for arch in archs:
        for shape in shapes:
            try:
                rec = run_one(arch, shape, multi_pod=args.multi_pod,
                              variant=args.variant, out_dir=args.out_dir,
                              remat=not args.no_remat,
                              ce_chunks=args.ce_chunks,
                              agg_sharding=args.agg_sharding,
                              donate=not args.no_donate,
                              ef_dtype=args.ef_dtype, tag=args.tag,
                              microbatch=args.microbatch,
                              chunk_len=args.chunk_len,
                              intra_dtype=args.intra_dtype)
                if rec["status"] == "skipped":
                    print(f"-- skip {arch} x {shape}: {rec['reason']}")
            except Exception as e:
                failures.append((arch, shape, repr(e)))
                print(f"!! FAIL {arch} x {shape}: {e}")
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} failures:")
        for f in failures:
            print("  ", f)
        sys.exit(1)
    print("\nall dry-runs passed")


if __name__ == "__main__":
    main()

"""Lowered StableHLO of the train step, for comparing two trees.

``dump`` lowers ``make_step`` (variant "demo") on the CPU for

* the four benchmark cells at test size (widths cut to 64, sequence 64,
  each cell's batch, ``microbatch`` and ``scan_layers``, ``ce_chunks``
  0, on ``make_host_mesh(data=1)`` as ``bench/entries`` builds it);
* the (4,4) and (16,1) host meshes of ``tests/test_steps_distributed.py``;
* the described v5e meshes of ``tests/test_chip_compile.py``: (1,1),
  (2,2), (4,1), (1,4), the DeepSeek step with no peer axis on (2,2), and
  the MLA step at (1,1) and (2,2);

and writes each program's text, locations stripped, to
``OUT/<name>.mlir``. ``compare`` reads two such directories and reports,
per program, SAME or DIFF, with every Mosaic kernel body parsed and
printed without debug info (a serialized kernel carries its source
paths, qualnames and line numbers).

Run (the tree under test and its parent, unpacked with ``git archive``):

    JAX_PLATFORMS=cpu python benchmarks/lowered_steps.py dump OUT_A \\
        --root PARENT_TREE
    JAX_PLATFORMS=cpu python benchmarks/lowered_steps.py dump OUT_B
    python benchmarks/lowered_steps.py compare OUT_A OUT_B
"""
from __future__ import annotations

import argparse
import base64
import copy
import dataclasses
import difflib
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = [("templar-1b", "peer-accum16"), ("qwen2-1.5b", "peer-accum16"),
         ("templar-1b", "peer-step2k"), ("deepseek-v2-lite", "peer-moe-4k")]
KERNEL_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def dump(root: str, out: str) -> None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    sys.path[:0] = [os.path.join(root, "src"), os.path.join(root, "bench"),
                    os.path.join(root, "bench", "entries")]
    os.makedirs(out, exist_ok=True)

    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    import peer_step
    import peer_step_moe
    from repro.configs.base import InputShape, TrainConfig
    from repro.configs.registry import get_config, reduced_config, tiny_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import make_step

    def write(name, plan, mesh):
        text = re.sub(r"\s*loc\([^)]*\)", "", plan.lower(mesh).as_text())
        with open(os.path.join(out, name + ".mlir"), "w") as f:
            f.write(text)
        print("wrote", name, flush=True)

    def load(kind, name):
        with open(os.path.join(root, "bench", kind, name + ".json")) as f:
            return json.load(f)

    def small(c, moe):
        c = copy.deepcopy(c)
        if moe:
            c.update(hidden_size=64, num_attention_heads=4,
                     num_key_value_heads=4, intermediate_size=128,
                     vocab_size=256, kv_lora_rank=16, qk_rope_head_dim=8,
                     qk_nope_head_dim=16, v_head_dim=16,
                     moe_intermediate_size=32)
        else:
            gqa = c["num_attention_heads"] != c["num_key_value_heads"]
            c.update(hidden_size=64, num_attention_heads=4,
                     num_key_value_heads=2 if gqa else 4, head_dim=16,
                     intermediate_size=128, vocab_size=256)
        cfg = get_config(c["registry"]).with_overrides(vocab_size=256)
        c["logit_rows"] = cfg.padded_vocab
        return c

    for cname, tname in CELLS:
        c, t = load("configs", cname), load("traffic", tname)
        moe = t["entry"] == "peer_step_moe"
        cfg = (peer_step_moe if moe else peer_step).program_config(
            small(c, moe))
        hp = TrainConfig(
            learning_rate=t["learning_rate"], warmup_steps=t["warmup_steps"],
            total_steps=t["total_steps"], weight_decay=t["weight_decay"],
            demo_beta=t["demo_beta"], demo_chunk=16, demo_topk=4)
        mesh = make_host_mesh(data=1)
        shape = InputShape(tname, seq_len=64, global_batch=t["batch"],
                           kind="train")
        kw = dict(scan_layers=True) if moe else {}
        write(f"cell__{cname}.{tname}",
              make_step(cfg, hp, mesh, shape, variant="demo", ce_chunks=0,
                        microbatch=t["microbatch"], **kw), mesh)

    cfg = tiny_config(num_layers=2, d_model=128, d_ff=256, vocab_size=512
                      ).with_overrides(peer_axes=("data",))
    hp = TrainConfig(learning_rate=1e-2, warmup_steps=0, total_steps=100,
                     demo_chunk=16, demo_topk=8)
    for data, model, B in ((4, 4, 8), (16, 1, 16)):
        mesh = make_host_mesh(data=data, model=model)
        shape = InputShape("t", seq_len=128, global_batch=B, kind="train")
        write(f"host__{data}x{model}",
              make_step(cfg, hp, mesh, shape, variant="demo", remat=False),
              mesh)

    devs = list(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices)

    def v5e(data, model):
        return Mesh(np.array(devs[:data * model]).reshape(data, model),
                    ("data", "model"))

    hp = TrainConfig(demo_chunk=16, demo_topk=4)
    for data, model in ((1, 1), (2, 2), (4, 1), (1, 4)):
        mesh = v5e(data, model)
        write(f"v5e__{data}x{model}",
              make_step(tiny_config(peer_axes=("data",)), hp, mesh,
                        InputShape("t", seq_len=32, global_batch=8,
                                   kind="train"),
                        variant="demo", microbatch=2, scan_layers=True),
              mesh)
    mesh = v5e(2, 2)
    cfg = reduced_config("deepseek-v2-lite").with_overrides(
        peer_axes=("pod",))
    write("v5e__dsv2_k1_2x2",
          make_step(cfg, hp, mesh,
                    InputShape("t", seq_len=64, global_batch=8,
                               kind="train"),
                    variant="demo", microbatch=2, scan_layers=True), mesh)
    cfg = cfg.with_overrides(mla=dataclasses.replace(
        cfg.mla, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128))
    for data, model in ((1, 1), (2, 2)):
        mesh = v5e(data, model)
        write(f"v5e__mla_{data}x{model}",
              make_step(cfg, hp, mesh,
                        InputShape("t", seq_len=1024, global_batch=4,
                                   kind="train"),
                        variant="demo", microbatch=2, scan_layers=True),
              mesh)


def _kernels_without_debug_info(text: str) -> str:
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = ir.Context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True

    def body(m):
        mod = ir.Module.parse(base64.b64decode(m.group(1)), context=ctx)
        asm = mod.operation.get_asm(enable_debug_info=False)
        return "BODY<" + asm.replace("\n", "\\n") + ">"
    return KERNEL_BODY.sub(body, text)


def compare(a_dir: str, b_dir: str) -> int:
    names = sorted(set(os.listdir(a_dir)) | set(os.listdir(b_dir)))
    differ = 0
    for name in names:
        a_path, b_path = (os.path.join(d, name) for d in (a_dir, b_dir))
        if not (os.path.exists(a_path) and os.path.exists(b_path)):
            print("MISSING", name)
            differ += 1
            continue
        a, b = (_kernels_without_debug_info(open(p).read())
                for p in (a_path, b_path))
        if a == b:
            print("SAME", name)
            continue
        differ += 1
        lines = [l for l in difflib.unified_diff(
            a.splitlines(), b.splitlines(), lineterm="", n=0)
            if l[:1] in "+-" and l[:3] not in ("---", "+++")]
        print("DIFF", name, f"({len(lines)} changed lines)")
        for l in lines[:10]:
            print("   ", l[:300])
    return 1 if differ else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    d = sub.add_parser("dump")
    d.add_argument("out")
    d.add_argument("--root", default=HERE,
                   help="tree whose src/ and bench/ are lowered")
    c = sub.add_parser("compare")
    c.add_argument("a")
    c.add_argument("b")
    args = ap.parse_args(argv)
    if args.cmd == "dump":
        dump(os.path.abspath(args.root), args.out)
        return 0
    return compare(args.a, args.b)


if __name__ == "__main__":
    sys.exit(main())

"""Entry ``peer_step_moe``: the peer's DeMo training step on a
DeepSeek-V2 configuration (latent attention, then expert layers that
hold a share of the experts), the program
``repro.launch.train.run_training`` compiles (``launch.steps.make_step``,
variant "demo", remat and donation on, the layers scanned as the dense
cells' are), on a one-chip host mesh.

The window, its timing and the check are ``peer_step``'s. What a dense
configuration does not have is here: the configuration file to
``ModelConfig``, the canonical weights of the two layer groups (the
dense layer 0 and the stacked expert layers) and their names in the
program, and the FLOPs (``moe_yardstick``). A traced run also reads the
device time of the block's scopes and, after the window, the held
assignments the program routed (``loss_fn``'s counts, on the pool's
micro-batches at the window's last parameters), for the expert
matmuls' roofline.
"""
from __future__ import annotations

import functools
import gc
import json
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

import compare
import harness
import moe_yardstick
import scopecut
import seeded
import tracecut
import yardstick

peer_step = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "peer_step.py"),
    "bench_entry_peer_step")


def log(msg: str) -> None:
    print(f"[peer_step_moe] {msg}", file=sys.stderr, flush=True)


GROUPS = ("dense", "moe_layers")
# what the program implements and has no option for
FIXED = {"model_type": "deepseek_v2", "hidden_act": "silu",
         "attention_bias": False, "tie_word_embeddings": False,
         "scoring_func": "softmax", "topk_method": "greedy",
         "norm_topk_prob": False, "routed_scaling_factor": 1,
         "seq_aux": True, "n_group": 1, "topk_group": 1,
         "moe_layer_freq": 1, "first_k_dense_replace": 1}


def program_config(c: dict):
    """The program's ModelConfig for config file ``c``: the registry's
    architecture with every stated size and precision applied."""
    from repro.configs.base import MLAConfig, MoEConfig, YarnConfig
    from repro.configs.registry import get_config
    for k, v in FIXED.items():
        if c[k] != v:
            raise ValueError(f"{c['registry']}: {k} {c[k]!r}; the program "
                             f"implements {v!r}")
    y = c["rope_scaling"]
    if y["type"] != "yarn":
        raise ValueError(f"rope_scaling {y['type']!r}; the program has yarn")
    cfg = get_config(c["registry"]).with_overrides(
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        rope_scaling=YarnConfig(
            factor=float(y["factor"]),
            original_max_position=y["original_max_position_embeddings"],
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=y["mscale"], mscale_all_dim=y["mscale_all_dim"]),
        mla=MLAConfig(kv_lora_rank=c["kv_lora_rank"],
                      q_lora_rank=c["q_lora_rank"] or 0,
                      qk_rope_head_dim=c["qk_rope_head_dim"],
                      qk_nope_head_dim=c["qk_nope_head_dim"],
                      v_head_dim=c["v_head_dim"]),
        moe=MoEConfig(num_experts=c["router_outputs"],
                      num_shared_experts=c["n_shared_experts"],
                      top_k=c["num_experts_per_tok"],
                      expert_d_ff=c["moe_intermediate_size"],
                      router_aux_coef=c["aux_loss_alpha"],
                      first_dense_layers=c["first_k_dense_replace"],
                      experts_held=c["n_routed_experts"],
                      expert_offset=c["expert_offset"]),
        tie_embeddings=False, dtype=c["compute_dtype"],
        param_dtype=c["param_dtype"], peer_axes=("data",)).validate()
    if cfg.padded_vocab != c["logit_rows"] or c["q_lora_rank"]:
        raise ValueError(f"{c['registry']}: {cfg.padded_vocab} logit rows, "
                         f"q_lora_rank {c['q_lora_rank']}; the configuration "
                         f"states {c['logit_rows']}, and the reference has "
                         f"no query low rank")
    return cfg


# ------------------------------------------------- the canonical tree


def canonical_shapes(c: dict) -> dict:
    """Leaf shapes of the canonical tree for config file ``c``: ``dense``
    is layer 0, ``moe_layers`` the expert layers with a leading layer
    axis."""
    d, V = c["hidden_size"], c["logit_rows"]
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    f, n, E = (c["moe_intermediate_size"], c["n_routed_experts"],
               c["router_outputs"])

    def layer(*lead):
        def w(*shape):
            return {"w": lead + shape}
        return {"norm1": {"g": lead + (d,)}, "norm2": {"g": lead + (d,)},
                "attn": {"wq_b": w(d, H * (dn + dr)), "wkv_a": w(d, r + dr),
                         "kv_norm": {"g": lead + (r,)},
                         "wkv_b": w(r, H * (dn + dv)), "wo": w(H * dv, d)}}

    dense = layer()
    ff = c["intermediate_size"]
    dense["mlp"] = {"gate": {"w": (d, ff)}, "up": {"w": (d, ff)},
                    "down": {"w": (ff, d)}}
    L = c["num_hidden_layers"] - c["first_k_dense_replace"]
    moe = layer(L)
    fs = c["n_shared_experts"] * f
    moe["moe"] = {
        "router": {"w": (L, d, E)},
        "experts": {"gate": (L, n, d, f), "up": (L, n, d, f),
                    "down": (L, n, f, d)},
        "shared": {"gate": {"w": (L, d, fs)}, "up": {"w": (L, d, fs)},
                   "down": {"w": (L, fs, d)}}}
    return {"embed": {"w": (V, d)}, "final_norm": {"g": (d,)},
            "lm_head": {"w": (d, V)}, "dense": dense, "moe_layers": moe}


@functools.lru_cache(maxsize=None)
def _weights_fn(config_json: str):
    c = json.loads(config_json)
    shapes = canonical_shapes(c)

    @jax.jit
    def make(lo, hi):
        key = seeded._key(lo, hi, 1)
        return jax.tree_util.tree_map_with_path(
            lambda p, s: seeded._init_leaf(key, seeded._path(p), s,
                                           c["param_dtype"]),
            shapes, is_leaf=lambda x: isinstance(x, tuple))
    return make


def canonical_weights(c: dict, seed: int):
    """The canonical weight tree for ``c`` and ``seed`` (``seeded``'s
    recipe), in the configuration's parameter dtype."""
    return _weights_fn(json.dumps(c, sort_keys=True))(
        *seeded.seed_words(seed))


def _program_names(prog_tree):
    """``{program leaf path: canonical path}``: scanned group i holds the
    canonical ``GROUPS[i]`` whole."""
    out = {}
    for path, _ in jax.tree_util.tree_flatten_with_path(prog_tree)[0]:
        p = seeded._path(path)
        parts = p.split("/")
        if parts[0] == "groups":
            out[p] = "/".join((GROUPS[int(parts[1])],) + tuple(parts[2:]))
        else:
            out[p] = p
    return out


def leaf_names(prog_tree):
    """The program's leaves' names, in flatten order, as the reference
    names its tensors (``moe_layers.moe.experts.gate``)."""
    return [p.replace("/", ".") for p in _program_names(prog_tree).values()]


def to_program(canon, prog_sds):
    """The canonical tree laid out as the program's parameter tree;
    raises where a leaf's shape or dtype differs."""
    names = _program_names(prog_sds)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(prog_sds)
    out = []
    for path, sds in leaves:
        x = seeded._get(canon, names[seeded._path(path)])
        if tuple(x.shape) != tuple(sds.shape) or x.dtype != sds.dtype:
            raise ValueError(
                f"program leaf {seeded._path(path)} is {sds.shape} "
                f"{sds.dtype}; the configuration states {x.shape} {x.dtype}")
        out.append(x)
    return jax.tree.unflatten(treedef, out)


# ------------------------------------------------------------ program


class Program(peer_step.Program):
    """The compiled step of one cell, and what feeds and reads it."""

    def __init__(self, cell: harness.Cell):
        from repro.configs.base import InputShape, TrainConfig
        from repro.launch.mesh import make_host_mesh
        from repro.launch.steps import make_step
        c, t = cell.config, cell.traffic
        self.c, self.t, self.h = c, t, peer_step.hyper(t)
        self.cfg = program_config(c)
        hp = TrainConfig(
            learning_rate=t["learning_rate"], warmup_steps=t["warmup_steps"],
            total_steps=t["total_steps"], weight_decay=t["weight_decay"],
            demo_beta=t["demo_beta"], demo_chunk=t["demo_chunk"],
            demo_topk=t["demo_topk"])
        self.mesh = make_host_mesh(data=1)
        shape = InputShape(cell.traffic_name, seq_len=t["seq"],
                           global_batch=t["batch"], kind="train")
        plan = make_step(self.cfg, hp, self.mesh, shape, variant="demo",
                         ce_chunks=0, microbatch=t["microbatch"],
                         scan_layers=True)
        t0 = time.perf_counter()
        self.step = plan.lower(self.mesh).compile()
        self.compile_s = time.perf_counter() - t0
        self.param_sds, state_sds = plan.args[0], plan.args[1]
        self.stacked = True
        self.names = leaf_names(self.param_sds)
        self._zeros = jax.jit(lambda: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), state_sds))
        self._norms = jax.jit(peer_step._norms)
        self._change = jax.jit(lambda a, b: peer_step._norms(
            jax.tree.map(jnp.subtract, a, b)))
        self._ef_bf16 = jax.jit(lambda t: jax.tree.map(
            lambda x, p: x.reshape(p.shape).astype(jnp.bfloat16), t,
            self.param_sds))
        self._delta = jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: (x - y).astype(jnp.bfloat16), a, b))

    def weights(self, seed: int):
        return to_program(canonical_weights(self.c, seed), self.param_sds)

    def reference(self, cell: harness.Cell, seed: int, mode: str,
                  flip_update: bool = False) -> dict:
        ref = harness.reference(cell)
        first = self.batches(seed)[:self.t["check_steps"]]
        return ref.train_readings(
            self.c, self.h, canonical_weights(self.c, seed), first,
            mode=mode, initial=lambda: canonical_weights(self.c, seed),
            flip_update=flip_update)

    def held_rows(self, params, pool) -> float:
        """Held assignments the program routes per step, over the pool's
        micro-batches at ``params``: the sum over the expert layers and
        micro-batches of ``loss_fn``'s ``moe_held``, averaged over the
        pool's batches."""
        from repro.models import model as M
        cfg, mb = self.cfg, self.t["microbatch"]
        held = jax.jit(lambda p, b: jnp.sum(M.loss_fn(
            p, b, cfg, scan_layers=True)[1]["moe_held"]))
        total = 0
        for batch in pool:
            for i in range(mb):
                part = {k: v.reshape((mb, -1) + v.shape[1:])[i]
                        for k, v in batch.items()}
                total += int(held(params, part))
        return total / len(pool)


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, devices, step_fault=None) -> harness.Outcome:
    """One run of a ``peer_step_moe`` cell, as ``peer_step.run`` makes
    one; a traced run also reads the block's split and the held rows."""
    watch = harness.CompileWatch()
    t = cell.traffic
    prog = Program(cell)
    log(f"compiled in {prog.compile_s:.3f}s")
    step = step_fault(prog.step) if step_fault else prog.step
    pool = prog.batches(seed)
    with jax.set_mesh(prog.mesh):
        t_check = time.perf_counter()
        params, state, got = prog.first_steps(seed, pool, step)
        log(f"checked steps and their readings in "
            f"{time.perf_counter() - t_check:.3f}s")
        tokens = t["batch"] * t["seq"]
        i, losses = t["check_steps"], []
        log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        if trace:
            jax.profiler.start_trace(log_dir)
        with watch, jax.profiler.TraceAnnotation(tracecut.WINDOW_SPAN):
            t0 = time.perf_counter()
            setup_s = t0 - t_start
            ends = []
            while True:
                with jax.profiler.TraceAnnotation("bench.step"):
                    params, state, loss = step(params, state,
                                               pool[i % len(pool)],
                                               np.int32(i))
                    jax.block_until_ready((params, state, loss))
                t_last = time.perf_counter()
                ends.append(t_last)
                losses.append(loss)
                i += 1
                if t_last - t0 >= seconds:
                    break
        if trace:
            jax.profiler.stop_trace()
            held = prog.held_rows(params, pool)
    done = len(losses)
    window_s = t_last - t0
    device = harness.device_info(devices)
    failed = sum(not np.isfinite(float(x)) for x in losses)
    log(f"setup {setup_s:.3f}s; {done} steps in {window_s:.3f}s; "
        f"peak_bytes_in_use {device['memory_peak_bytes']} "
        f"(AOT {prog.aot_bytes()})")
    step_ms = (np.diff([t0] + ends) * 1e3).round(3).tolist()
    log(f"step wall ms {json.dumps(step_ms)}")
    del params, state, pool, losses
    gc.collect()
    reduced = ctx = None
    if trace:
        planes = list(jax.profiler.ProfileData.from_file(
            tracecut.find_trace(log_dir)).planes)
        shutil.rmtree(log_dir, ignore_errors=True)
        reduced = tracecut.reduce(planes)
        block = moe_yardstick.by_block(scopecut.self_times(planes),
                                       scopecut.op_names(prog.step.as_text()))
        block_ms = {s: v * 1e3 / done for s, v in block.items()}
        c = cell.config
        layer_batches = ((c["num_hidden_layers"] - c["first_k_dense_replace"])
                         * t["microbatch"])
        peaks = yardstick.peaks(devices[0].device_kind)
        ctx = {"window_s": reduced["window_s"],
               "busy_s": reduced["busy_s"], "steps": done,
               "flops_per_step": moe_yardstick.train_step_flops(
                   c, t["batch"], t["seq"]),
               "peak_flops": peaks.flops_bf16, "peak_bw": peaks.hbm_bw,
               "block_ms": block_ms, "held_rows": held,
               "experts": moe_yardstick.grouped_work(c, held, layer_batches)}
        log(f"block self ms per step {json.dumps(block_ms)}; held rows "
            f"per step {held}")
    t_ref = time.perf_counter()
    ref = prog.reference(cell, seed, "float32")
    log(f"reference in {time.perf_counter() - t_ref:.3f}s")
    gaps = compare.gaps(got, ref)
    for name, (gap, where) in gaps.items():
        log(f"{name} {gap!r} at {where}")
    return harness.Outcome(
        attempted=done, failed=failed,
        metrics={"peer_tokens_per_s": done * tokens / window_s,
                 "setup_s": setup_s},
        checks=compare.checks(gaps, cell.limits), device=device,
        reader_ctx=ctx, trace=reduced)

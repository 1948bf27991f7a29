"""Entry ``peer_step``: the peer's DeMo training step, the program
``repro.launch.train.run_training`` compiles (``launch.steps.make_step``,
variant "demo", remat and donation on), on a one-chip host mesh.

Set-up builds one object, the compiled step with its parameters and
error feedback, drives it through the first ``check_steps`` steps of
the seeded batch pool, and hands it on to the measured window, which
keeps stepping through the pool until ``--seconds`` have passed. Every
step ends in ``block_until_ready``. ``peer_tokens_per_s`` is all tokens
of the steps completed in the window over the time from the window's
start to the last completion.

Once the window has closed and the program's state is freed, the plain
reference runs the same first steps from the same seed in float32, and
``bench/compare.py`` decides ``correct``.
"""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

import compare
import harness
import seeded
import tracecut
import yardstick


def log(msg: str) -> None:
    print(f"[peer_step] {msg}", file=sys.stderr, flush=True)


def hyper(traffic: dict) -> dict:
    """The step's hyperparameters as the traffic file states them."""
    keys = ("learning_rate", "warmup_steps", "total_steps", "lr_min_frac",
            "weight_decay", "demo_beta", "demo_chunk", "demo_topk",
            "microbatch")
    return {k: traffic[k] for k in keys}


def program_config(c: dict):
    """The program's ModelConfig for config file ``c``: the registry's
    architecture with every stated size and precision applied."""
    from repro.configs.registry import get_config
    cfg = get_config(c["registry"]).with_overrides(
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        qkv_bias=c["attention_bias"],
        tie_embeddings=c["tie_word_embeddings"], dtype=c["compute_dtype"],
        param_dtype=c["param_dtype"], peer_axes=("data",)).validate()
    if cfg.family != "dense" or cfg.padded_vocab != c["logit_rows"]:
        raise ValueError(f"{c['registry']}: family {cfg.family}, "
                         f"{cfg.padded_vocab} logit rows; the configuration "
                         f"states dense and {c['logit_rows']}")
    return cfg


def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


class Program:
    """The compiled step of one cell, and what feeds and reads it."""

    def __init__(self, cell: harness.Cell):
        from repro.configs.base import InputShape, TrainConfig
        from repro.launch.mesh import make_host_mesh
        from repro.launch.steps import make_step
        c, t = cell.config, cell.traffic
        self.c, self.t, self.h = c, t, hyper(t)
        hp = TrainConfig(
            learning_rate=t["learning_rate"], warmup_steps=t["warmup_steps"],
            total_steps=t["total_steps"], weight_decay=t["weight_decay"],
            demo_beta=t["demo_beta"], demo_chunk=t["demo_chunk"],
            demo_topk=t["demo_topk"])
        self.mesh = make_host_mesh(data=1)
        shape = InputShape(cell.traffic_name, seq_len=t["seq"],
                           global_batch=t["batch"], kind="train")
        plan = make_step(program_config(c), hp, self.mesh, shape,
                         variant="demo", ce_chunks=0,
                         microbatch=t["microbatch"])
        t0 = time.perf_counter()
        self.step = plan.lower(self.mesh).compile()
        self.compile_s = time.perf_counter() - t0
        self.param_sds, state_sds = plan.args[0], plan.args[1]
        self.stacked = "groups" in self.param_sds
        self.names = seeded.leaf_names(self.param_sds)
        self._zeros = jax.jit(lambda: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), state_sds))
        self._norms = jax.jit(_norms)
        self._change = jax.jit(lambda a, b: _norms(
            jax.tree.map(jnp.subtract, a, b)))
        # the error feedback carries a leading axis of one peer
        self._ef_bf16 = jax.jit(lambda t: jax.tree.map(
            lambda x, p: x.reshape(p.shape).astype(jnp.bfloat16), t,
            self.param_sds))
        self._delta = jax.jit(lambda a, b: jax.tree.map(
            lambda x, y: (x - y).astype(jnp.bfloat16), a, b))

    def aot_bytes(self) -> int:
        ma = self.step.memory_analysis()
        return int(ma.temp_size_in_bytes + ma.argument_size_in_bytes
                   + ma.output_size_in_bytes - ma.alias_size_in_bytes)

    def weights(self, seed: int):
        return seeded.to_program(seeded.canonical_weights(self.c, seed),
                                 self.param_sds)

    def batches(self, seed: int):
        t = self.t
        return seeded.token_batches(seed, t["pool"], t["batch"], t["seq"],
                                    self.c["vocab_size"])

    def _named(self, norms) -> dict:
        return dict(zip(self.names, (float(x) for x in norms)))

    def _host(self, tree) -> dict:
        """The tree's leaves on the host by name (bfloat16 copies, so a
        set-up's two of them stay small)."""
        return dict(zip(self.names, jax.device_get(jax.tree.leaves(tree))))

    def first_steps(self, seed: int, pool, step=None):
        """Fresh weights and error feedback from ``seed``, driven through
        the first ``check_steps`` batches of ``pool`` by ``step`` (the
        compiled step unless a test breaks it). Returns the state to
        carry on with and the readings the check compares."""
        step = step or self.step
        params, state = self.weights(seed), self._zeros()
        losses, readings = [], {}
        for i in range(self.t["check_steps"]):
            params, state, loss = step(params, state, pool[i], np.int32(i))
            losses.append(float(loss))
            if i == 0:
                readings["ef1"] = self._named(self._norms(state))
                readings["ef1_vec"] = self._host(self._ef_bf16(state))
        start = self.weights(seed)
        readings["change"] = self._named(self._change(params, start))
        readings["change_vec"] = self._host(self._delta(params, start))
        del start
        readings["losses"] = losses
        return params, state, readings

    def reference(self, cell: harness.Cell, seed: int, mode: str,
                  flip_update: bool = False) -> dict:
        """The reference's readings for the same seed and steps, in
        ``mode`` arithmetic; call once the program's state is freed.
        ``flip_update`` plants a fault in the reference (calibration)."""
        ref = harness.reference(cell)
        first = self.batches(seed)[:self.t["check_steps"]]
        return ref.train_readings(
            self.c, self.h, seeded.canonical_weights(self.c, seed), first,
            mode=mode, stacked=self.stacked,
            initial=lambda: seeded.canonical_weights(self.c, seed),
            flip_update=flip_update)


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, devices, step_fault=None) -> harness.Outcome:
    """One run of a ``peer_step`` cell. ``step_fault`` (tests only)
    wraps the compiled step to break the timed path."""
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
            while True:
                with jax.profiler.TraceAnnotation("bench.step"):
                    params, state, loss = step(params, state,
                                               pool[i % len(pool)],
                                               np.int32(i))
                    jax.block_until_ready((params, state, loss))
                t_last = time.perf_counter()
                losses.append(loss)
                i += 1
                if t_last - t0 >= seconds:
                    break
        if trace:
            jax.profiler.stop_trace()
    done = len(losses)
    window_s = t_last - t0
    device = harness.device_info(devices)
    failed = sum(not np.isfinite(float(x)) for x in losses)
    log(f"setup {setup_s:.3f}s; {done} steps in {window_s:.3f}s; "
        f"peak_bytes_in_use {device['memory_peak_bytes']} "
        f"(AOT {prog.aot_bytes()})")
    del params, state, pool, losses
    gc.collect()
    reduced = ctx = None
    if trace:
        reduced = tracecut.reduce_file(tracecut.find_trace(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        ctx = {"window_s": reduced["window_s"],
               "busy_s": reduced["busy_s"], "steps": done,
               "flops_per_step": yardstick.train_step_flops(
                   cell.config, t["batch"], t["seq"]),
               "peak_flops": yardstick.peaks(
                   devices[0].device_kind).flops_bf16}
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

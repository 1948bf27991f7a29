"""Production step builders: the paper's communication round as a single
pjit/shard_map program, plus the DDP baseline and serving steps.

The train step IS the paper's protocol mapped onto the mesh (DESIGN §3):
peers = data-parallel shard groups; each peer computes its local
gradient with NO cross-peer psum (partial-manual shard_map over the peer
axes), runs its gradient scheme's ``local_step`` (for DeMo: error
feedback, chunked DCT, top-k), and the only cross-peer collective is an
all-gather of the *compressed* payloads — the S3 broadcast of the live
system, expressed as an ICI collective. The scheme's ``aggregate`` (for
DeMo: per-peer DCT-domain normalization, mean, sign) is computed
redundantly on every peer, which keeps replicas bit-identical — the
property the paper's §6 "Synchronous Model States Simplify Validation"
argues is essential. The codec is the scheme's own, the one the
validator replays and every ``PeerNode`` runs.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import sharding as sh
from repro.configs.base import InputShape, ModelConfig, TrainConfig
from repro.demo import adamw
from repro.demo.schedules import warmup_cosine
from repro.hints import axis_hints
from repro.models import model as M
from repro.obs import trace
from repro.schemes import apply_update, make_scheme


# ----------------------------------------------------------------- inputs


def input_specs(cfg: ModelConfig, shape: InputShape) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this shape —
    weak-type-correct, shardable, no device allocation."""
    B, S = shape.global_batch, shape.seq_len
    f32 = jnp.float32
    i32 = jnp.int32
    out: Dict[str, Any] = {}
    if shape.kind in ("train", "prefill"):
        text = S
        if cfg.frontend is not None and cfg.frontend.kind == "vision":
            text = S - cfg.frontend.num_prefix_tokens
        out["tokens"] = jax.ShapeDtypeStruct((B, text), i32)
        if shape.kind == "train":
            out["labels"] = jax.ShapeDtypeStruct((B, text), i32)
        if cfg.frontend is not None:
            P_, e = cfg.frontend.num_prefix_tokens, cfg.frontend.embed_dim
            name = ("patch_embeds" if cfg.frontend.kind == "vision"
                    else "frames")
            out[name] = jax.ShapeDtypeStruct((B, P_, e), f32)
    else:  # decode: one new token against a seq_len cache
        out["tokens"] = jax.ShapeDtypeStruct((B, 1), i32)
    return out


def param_shapes(cfg: ModelConfig):
    return jax.eval_shape(
        functools.partial(M.init_params, cfg), jax.random.PRNGKey(0))


# Scan-over-layers threshold: unrolled trunks make XLA compile time (and
# SPMD partitioning) O(layers); beyond this depth the production steps
# lower the lax.scan trunk over stacked params (numerically identical —
# tests assert it). Shallow models stay unrolled for better fusion.
SCAN_LAYERS_MIN = 8


def use_scan(cfg: ModelConfig) -> bool:
    return cfg.num_layers >= SCAN_LAYERS_MIN


def stacked_param_shapes(cfg: ModelConfig):
    return jax.eval_shape(
        functools.partial(M.init_params_stacked, cfg), jax.random.PRNGKey(0))


def grouped_cache_shapes(cfg: ModelConfig, shape: InputShape):
    return jax.eval_shape(
        lambda: M.group_cache(
            M.init_cache(cfg, shape.global_batch, shape.seq_len), cfg))


def cache_shapes(cfg: ModelConfig, shape: InputShape):
    return jax.eval_shape(
        functools.partial(M.init_cache, cfg, shape.global_batch,
                          shape.seq_len))


def _sds_like(spec_tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                        spec_tree)


# ----------------------------------------------------------------- plan


@dataclasses.dataclass
class StepPlan:
    """A lowerable step: fn + arg ShapeDtypeStructs + shardings."""
    name: str
    fn: Callable
    args: Tuple
    in_specs: Tuple
    out_specs: Any = None
    donate: Tuple[int, ...] = ()   # state args aliased in/out (perf: halves
                                   # the params/EF/opt temp footprint)

    def lower(self, mesh):
        in_shardings = jax.tree.map(
            lambda s: jax.NamedSharding(mesh, s), self.in_specs,
            is_leaf=lambda x: isinstance(x, P))
        kw = {}
        if self.out_specs is not None:
            kw["out_shardings"] = jax.tree.map(
                lambda s: jax.NamedSharding(mesh, s), self.out_specs,
                is_leaf=lambda x: isinstance(x, P))
        if self.donate:
            kw["donate_argnums"] = self.donate
        # attention heads over 'model' (repro.hints.constrain_heads)
        with jax.set_mesh(mesh), axis_hints():
            return jax.jit(self.fn, in_shardings=in_shardings,
                           **kw).lower(*self.args)


def make_grad_fn(loss_of, microbatch: int):
    """value_and_grad, optionally accumulated over microbatches with a
    lax.scan (gradient accumulation: peak activation memory scales with
    the microbatch, not the per-peer batch)."""
    if microbatch <= 1:
        return jax.value_and_grad(loss_of)

    def grad_of(params, batch):
        def slice_mb(x):
            return x.reshape((microbatch, x.shape[0] // microbatch)
                             + x.shape[1:])

        def body(carry, mb):
            loss_acc, g_acc = carry
            l, g = jax.value_and_grad(loss_of)(params, mb)
            g_acc = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32), g_acc, g)
            return (loss_acc + l, g_acc), None

        with jax.named_scope(trace.SCOPE_ACCUMULATE):
            mbs = jax.tree.map(slice_mb, batch)
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            (loss, grads), _ = jax.lax.scan(
                body, (jnp.float32(0.0), g0), mbs)
            inv = 1.0 / microbatch
            return loss * inv, jax.tree.map(lambda g: g * inv, grads)

    return grad_of


def _inner_groups(cfg: ModelConfig, mesh) -> int:
    """MoE dispatch groups inside one peer = token-sharding axes that are
    neither peer nor model axes (e.g. 'data' for deepseek-v2)."""
    peers = set(sh.effective_peer_axes(cfg, mesh))
    shape = dict(mesh.shape)
    g = 1
    for a in mesh.axis_names:
        if a not in peers and a != "model":
            g *= shape[a]
    return g


# ------------------------------------------------------------ peer round


def make_train_step(cfg: ModelConfig, hp: TrainConfig, mesh,
                    shape: InputShape, *, remat: bool = True,
                    ce_chunks: int = 0, scan_layers: Optional[bool] = None,
                    donate: bool = True, microbatch: int = 1) -> StepPlan:
    """One Gauntlet communication round (cooperative fast path, eq. 1)
    for any :class:`repro.schemes.GradScheme`: per-peer gradient →
    ``scheme.local_step`` → all_gather of the payload pytree over the
    peer axes → ``scheme.aggregate`` → θ ← (1 − αλ)θ − αΔ.

    ``hp.scheme`` picks the scheme. Params are replicated across peers;
    each peer's error feedback and batch ride the leading peer axis, the
    error feedback sharded like the params under it. The dense Δ is
    sharded like the params; how the gathered payloads are laid out
    before the decode is the scheme's own business (``aggregate``). With
    no peer axis in the mesh (e.g. deepseek-v2 on one pod) the gradient
    spans the whole mesh (GSPMD all-reduces over 'data') and the round
    runs with K = 1 and no collective.
    """
    scan = use_scan(cfg) if scan_layers is None else scan_layers
    peers = sh.effective_peer_axes(cfg, mesh)
    K = sh.num_peers(cfg, mesh)
    p_sds = stacked_param_shapes(cfg) if scan else param_shapes(cfg)
    pspec_fn = sh.stacked_param_specs if scan else sh.param_specs
    pspecs = pspec_fn(cfg, p_sds, mesh)
    batch_sds = input_specs(cfg, shape)
    ng = _inner_groups(cfg, mesh)
    scheme = make_scheme(hp, p_sds)

    def loss_of(params, batch):
        return M.loss_fn(params, batch, cfg, num_groups=ng, remat=remat,
                         ce_chunks=ce_chunks, scan_layers=scan)[0]

    grad_of = make_grad_fn(loss_of, microbatch)

    def round_body(params, ef, batch, step_idx):
        lr = warmup_cosine(step_idx, base_lr=hp.learning_rate,
                           warmup_steps=hp.warmup_steps,
                           total_steps=hp.total_steps)
        if peers:   # this peer's slice of the leading peer axis
            ef = jax.tree.map(lambda e: e[0], ef)
        loss, grads = grad_of(params, batch)
        payload, ef = scheme.local_step(grads, ef, batch=batch)
        if peers:
            gathered = jax.tree.map(
                lambda x: jax.lax.all_gather(x, peers, axis=0, tiled=False),
                payload)
        else:
            gathered = jax.tree.map(lambda x: x[None], payload)
        delta = jax.tree.map(jax.lax.with_sharding_constraint,
                             scheme.aggregate(gathered), pspecs)
        params = apply_update(params, delta, lr,
                              weight_decay=hp.weight_decay)
        if peers:
            loss = jax.lax.pmean(loss, peers)
            ef = jax.tree.map(lambda e: e[None], ef)
        return params, ef, loss

    ef_sds = jax.eval_shape(scheme.init_state, p_sds)
    if peers:
        ef_sds = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((K,) + l.shape, l.dtype), ef_sds)
        efspecs = jax.tree.map(lambda s: P(peers, *s), pspecs)
        bspecs = sh.batch_specs(cfg, batch_sds, peers, mesh)
        manual_p = jax.tree.map(lambda _: P(), p_sds)
        manual_e = jax.tree.map(lambda _: P(peers), ef_sds)
        manual_b = jax.tree.map(
            lambda l: P(peers, *(None,) * (l.ndim - 1)), batch_sds)

        def step(params, ef, batch, step_idx):
            return jax.shard_map(
                round_body, mesh=mesh,
                in_specs=(manual_p, manual_e, manual_b, P()),
                out_specs=(manual_p, manual_e, P()),
                axis_names=set(peers), check_vma=False)(
                    params, ef, batch, step_idx)
    else:
        efspecs = pspecs
        bspecs = sh.batch_specs(cfg, batch_sds,
                                sh.dp_axes_for_serving(mesh))
        step = round_body

    return StepPlan(
        name=f"{scheme.name}_train[{cfg.name}|{shape.name}]", fn=step,
        args=(_sds_like(p_sds), ef_sds, batch_sds,
              jax.ShapeDtypeStruct((), jnp.int32)),
        in_specs=(pspecs, efspecs, bspecs, P()),
        out_specs=(pspecs, efspecs, P()),
        donate=(0, 1) if donate else ())


# ----------------------------------------------------------------- DDP


def make_ddp_train_step(cfg: ModelConfig, hp: TrainConfig, mesh,
                        shape: InputShape, remat: bool = True,
                        ce_chunks: int = 0,
                        scan_layers: Optional[bool] = None,
                        donate: bool = True,
                        microbatch: int = 1) -> StepPlan:
    """AdamW-DDP baseline (paper Fig. 1): batch sharded over all non-model
    axes, gradients all-reduced by GSPMD — the collective-bytes comparator
    for the DeMo step."""
    scan = use_scan(cfg) if scan_layers is None else scan_layers
    p_sds = stacked_param_shapes(cfg) if scan else param_shapes(cfg)
    pspec_fn = sh.stacked_param_specs if scan else sh.param_specs
    batch_sds = input_specs(cfg, shape)
    dp = sh.dp_axes_for_serving(mesh)
    ng = _inner_groups(cfg, mesh) * sh.num_peers(cfg, mesh)

    def loss_of(params, batch):
        return M.loss_fn(params, batch, cfg, num_groups=ng, remat=remat,
                         ce_chunks=ce_chunks, scan_layers=scan)[0]

    grad_of = make_grad_fn(loss_of, microbatch)

    def step(params, opt, batch, step_idx):
        lr = warmup_cosine(step_idx, base_lr=hp.learning_rate,
                           warmup_steps=hp.warmup_steps,
                           total_steps=hp.total_steps)
        loss, grads = grad_of(params, batch)
        new_params, new_opt = adamw.step(params, grads, opt, lr=lr,
                                         weight_decay=hp.weight_decay)
        return new_params, new_opt, loss

    pspecs = pspec_fn(cfg, p_sds, mesh)
    opt_sds = jax.eval_shape(adamw.init_state, p_sds)
    opt_specs = adamw.AdamWState(
        mu=pspecs, nu=pspecs, step=P())
    bspecs = sh.batch_specs(cfg, batch_sds, dp, mesh)
    return StepPlan(
        name=f"ddp_train[{cfg.name}|{shape.name}]", fn=step,
        args=(_sds_like(p_sds), _sds_like(opt_sds), batch_sds,
              jax.ShapeDtypeStruct((), jnp.int32)),
        in_specs=(pspecs, opt_specs, bspecs, P()),
        out_specs=(pspecs, opt_specs, P()),
        donate=(0, 1) if donate else ())


# ----------------------------------------------------------------- serve


def make_serve_step(cfg: ModelConfig, mesh, shape: InputShape,
                    scan_layers: Optional[bool] = None) -> StepPlan:
    """Single-token decode against a seq_len cache."""
    assert shape.is_decode
    scan = use_scan(cfg) if scan_layers is None else scan_layers
    ng = min(_inner_groups(cfg, mesh) * sh.num_peers(cfg, mesh),
             shape.global_batch)

    if scan:
        p_sds = stacked_param_shapes(cfg)
        c_sds = grouped_cache_shapes(cfg, shape)
        pspecs = sh.stacked_param_specs(cfg, p_sds, mesh)
        cspecs = sh.grouped_cache_specs(cfg, c_sds, mesh, shape)

        def step(params, cache, tokens):
            return M.decode_step_stacked(params, tokens, cache, cfg,
                                         seq_len=shape.seq_len,
                                         num_groups=ng)
    else:
        p_sds = param_shapes(cfg)
        c_sds = cache_shapes(cfg, shape)
        pspecs = sh.param_specs(cfg, p_sds, mesh)
        cspecs = sh.cache_specs(cfg, c_sds, mesh, shape)

        def step(params, cache, tokens):
            return M.decode_step(params, tokens, cache, cfg,
                                 seq_len=shape.seq_len, num_groups=ng)
    dp = sh.dp_axes_for_serving(mesh)
    tspec = P(dp if shape.global_batch > 1 else None, None)
    tok_sds = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    return StepPlan(
        name=f"serve[{cfg.name}|{shape.name}]", fn=step,
        args=(_sds_like(p_sds), _sds_like(c_sds), tok_sds),
        in_specs=(pspecs, cspecs, tspec))


def make_prefill_step(cfg: ModelConfig, mesh, shape: InputShape,
                      scan_layers: Optional[bool] = None) -> StepPlan:
    """Full-sequence forward (inference prefill)."""
    scan = use_scan(cfg) if scan_layers is None else scan_layers
    p_sds = stacked_param_shapes(cfg) if scan else param_shapes(cfg)
    pspec_fn = sh.stacked_param_specs if scan else sh.param_specs
    batch_sds = input_specs(cfg, shape)
    dp = sh.dp_axes_for_serving(mesh)
    ng = _inner_groups(cfg, mesh) * sh.num_peers(cfg, mesh)

    def step(params, batch):
        return M.forward(params, batch, cfg, num_groups=ng, remat=False,
                         scan_layers=scan)

    pspecs = pspec_fn(cfg, p_sds, mesh)
    bspecs = sh.batch_specs(cfg, batch_sds, dp, mesh)
    return StepPlan(
        name=f"prefill[{cfg.name}|{shape.name}]", fn=step,
        args=(_sds_like(p_sds), batch_sds),
        in_specs=(pspecs, bspecs))


# ----------------------------------------------------------------- picker


def long_context_variant(cfg: ModelConfig) -> ModelConfig:
    """SWA variant for long_500k on archs without native sub-quadratic
    support (DESIGN.md §5)."""
    if cfg.long_context_ok or cfg.family == "ssm" or cfg.attn_window:
        return cfg
    return cfg.with_overrides(attn_window=4096)


def make_step(cfg: ModelConfig, hp: TrainConfig, mesh, shape: InputShape,
              variant: str = "demo", **kw) -> StepPlan:
    """The step for ``shape``. On a train shape, variant "demo" is the
    peers' round of the scheme ``hp.scheme`` names (DeMo by default) and
    variant "ddp" the AdamW-DDP baseline."""
    if shape.kind == "train":
        if variant == "ddp":
            return make_ddp_train_step(cfg, hp, mesh, shape, **kw)
        if variant != "demo":
            raise ValueError(
                f"unknown train variant {variant!r}; known: demo, ddp")
        return make_train_step(cfg, hp, mesh, shape, **kw)
    if shape.kind == "prefill":
        return make_prefill_step(cfg, mesh, shape)
    if shape.name == "long_500k":
        cfg = long_context_variant(cfg)
    return make_serve_step(cfg, mesh, shape)

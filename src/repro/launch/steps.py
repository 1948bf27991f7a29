"""Production step builders: the paper's communication round as a single
pjit/shard_map program, plus the DDP baseline and serving steps.

The DeMo train step IS the paper's protocol mapped onto the mesh (DESIGN
§3): peers = data-parallel shard groups; each peer computes its local
gradient with NO cross-peer psum (partial-manual shard_map over the peer
axes), compresses it (error feedback + chunked DCT + top-k), and the only
cross-peer collective is an all-gather of the *compressed* payloads —
the S3 broadcast of the live system, expressed as an ICI collective.
Aggregation (per-peer DCT-domain normalization, mean, sign) is computed
redundantly on every peer, which keeps replicas bit-identical — the
property the paper's §6 "Synchronous Model States Simplify Validation"
argues is essential.
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
from repro.demo import adamw, dct
from repro.demo.schedules import warmup_cosine
from repro.models import model as M
from repro.obs import trace
# the tuned production step is DeMo-specific by design: it IS the demo
# scheme's codec lowered onto the mesh (all_gather of Payload trees).
# Other schemes lower through make_scheme_train_step, which reuses the
# same _peer_round_plan scaffold with the scheme's own local_step/
# aggregate_apply in the per-peer body.
from repro.schemes import demo as demo_opt


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
    hints: Optional[Dict[str, Optional[str]]] = None

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
        from repro.hints import axis_hints
        with jax.set_mesh(mesh), axis_hints(
                **(self.hints or {"head": "model"})):
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


# Axis hints published to model-code sharding constraints: attention
# heads over 'model'. "chunk" constraints measured WORSE (§Perf B2: they
# add resharding churn on top of the upstream replication instead of
# preventing it) — the fix that worked is the flatten-free reshape in
# dct.to_chunks/from_chunks (B3). Hint left off.
STEP_HINTS: Dict[str, Optional[str]] = {"head": "model", "chunk": None}


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


def _peer_round_plan(cfg: ModelConfig, mesh, *, name: str,
                     per_peer: Callable, p_sds, pspecs,
                     state_sds, state_specs, batch_sds,
                     donate: bool, hints) -> StepPlan:
    """Shared shard_map scaffolding for ONE communication round over the
    mesh peer axes: params replicated across peers, per-peer state and
    batch split on their leading axis, loss pmean'd inside ``per_peer``.

    ``per_peer(params, state, batch, step_idx)`` runs in manual mode on
    one peer's shard (state/batch leading axis = 1 locally) and returns
    ``(new_params, new_state, loss)`` with the same layout. Both the
    DeMo step and the scheme-generic step are this scaffold plus a
    different ``per_peer`` body — the specs construction, shard_map
    plumbing and StepPlan assembly are identical by construction.
    """
    peers = sh.effective_peer_axes(cfg, mesh)
    manual_p = jax.tree.map(lambda _: P(), p_sds)
    manual_s = jax.tree.map(lambda _: P(peers), state_sds)
    manual_b = jax.tree.map(
        lambda l: P(peers, *(None,) * (l.ndim - 1)), batch_sds)
    bspecs = sh.batch_specs(cfg, batch_sds, peers, mesh)

    def step(params, state, batch, step_idx):
        return jax.shard_map(
            per_peer, mesh=mesh,
            in_specs=(manual_p, manual_s, manual_b, P()),
            out_specs=(manual_p, manual_s, P()),
            axis_names=set(peers), check_vma=False)(
                params, state, batch, step_idx)

    return StepPlan(
        name=name, fn=step,
        args=(_sds_like(p_sds), _sds_like(state_sds), batch_sds,
              jax.ShapeDtypeStruct((), jnp.int32)),
        in_specs=(pspecs, state_specs, bspecs, P()),
        out_specs=(pspecs, state_specs, P()),
        donate=(0, 1) if donate else (),
        hints=hints)


# ----------------------------------------------------------------- DeMo


def make_demo_train_step(cfg: ModelConfig, hp: TrainConfig, mesh,
                         shape: InputShape, remat: bool = True,
                         ce_chunks: int = 0,
                         scan_layers: Optional[bool] = None,
                         agg_sharding: str = "param",
                         ef_dtype: Optional[str] = None,
                         donate: bool = True,
                         microbatch: int = 1) -> StepPlan:
    """One Gauntlet communication round (cooperative fast path, eq. 1).

    Perf knobs (§Perf iterations; defaults = optimized production config):
      agg_sharding  'param': the dense aggregated Δ is sharded like the
                    params (decode computed sharded; minimal temp memory).
                    'replicated': every device redundantly computes the
                    full Δ (zero resharding traffic, +params-fp32 temp).
      ef_dtype      error-feedback buffer dtype (default param_dtype).
      donate        alias params/EF in→out (halves state temp footprint).
    """
    scan = use_scan(cfg) if scan_layers is None else scan_layers
    peers = sh.effective_peer_axes(cfg, mesh)
    K = sh.num_peers(cfg, mesh)
    p_sds = stacked_param_shapes(cfg) if scan else param_shapes(cfg)
    pspec_fn = sh.stacked_param_specs if scan else sh.param_specs
    pspecs = pspec_fn(cfg, p_sds, mesh)
    metas = demo_opt.tree_meta(p_sds, hp.demo_chunk)
    batch_sds = input_specs(cfg, shape)
    ng = _inner_groups(cfg, mesh)
    ef_dtype = jnp.dtype(ef_dtype or cfg.param_dtype)

    def local_compress(grads, ef):
        """e <- beta e + g ; payload <- topk(dct(e)) ; e <- e - idct(...)"""
        from repro import hints as _hints

        def leaf(e, g, m):
            with jax.named_scope(trace.SCOPE_ENCODE):
                e32 = (hp.demo_beta * e.astype(jnp.float32)
                       + g.astype(jnp.float32))
                # keep every params-sized compression stage sharded by
                # chunk rows (the flatten/pad reshapes otherwise make
                # GSPMD replicate the whole fp32 pipeline — §Perf pair B)
                coeffs = _hints.constrain_chunks(dct.encode(e32, m))
            payload = demo_opt.topk_compress(coeffs, hp.demo_topk)
            with jax.named_scope(trace.SCOPE_DECODE):
                dense = _hints.constrain_chunks(
                    demo_opt.topk_decompress(payload, m.s * m.s))
                z = dct.decode(dense, m)
                return payload, (e32 - z).astype(ef_dtype)
        flat_e, tdef = jax.tree.flatten(ef)
        outs = [leaf(e, g, m) for e, g, m in zip(
            flat_e, jax.tree.leaves(grads), jax.tree.leaves(metas))]
        return (jax.tree.unflatten(tdef, [o[0] for o in outs]),
                jax.tree.unflatten(tdef, [o[1] for o in outs]))

    def loss_of(params, batch):
        return M.loss_fn(params, batch, cfg, num_groups=ng, remat=remat,
                         ce_chunks=ce_chunks, scan_layers=scan)[0]

    grad_of = make_grad_fn(loss_of, microbatch)

    chunk_axes = tuple(sh.tp_axes(cfg, mesh))

    def agg_and_apply(params, gathered, lr):
        # The paper's aggregation is logically computed on every peer so
        # replicas stay bit-identical (§6). Physically we either replicate
        # the computation ('replicated': zero resharding traffic, but a
        # full params-fp32 temp per device) or keep payloads, scatter
        # grids and the dense Δ sharded by chunk rows / param specs
        # ('param': the decode is chunk-local; GSPMD inserts only cheap
        # redistribution where chunk rows cross the param sharding).
        if agg_sharding == "replicated":
            gathered = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(x, P()),
                gathered)
        elif chunk_axes:
            gathered = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, P(None, chunk_axes, None)), gathered)
        delta = demo_opt.aggregate(gathered, metas, normalize=True,
                                   apply_sign=True)
        dspec = (jax.tree.map(lambda _: P(), delta) if
                 agg_sharding == "replicated" else pspecs)
        delta = jax.tree.map(jax.lax.with_sharding_constraint, delta,
                             dspec)
        return demo_opt.apply_update(params, delta, lr,
                                     weight_decay=hp.weight_decay)

    if peers:
        def per_peer(params, ef, batch, step_idx):
            lr = warmup_cosine(step_idx, base_lr=hp.learning_rate,
                               warmup_steps=hp.warmup_steps,
                               total_steps=hp.total_steps)
            ef_local = jax.tree.map(lambda e: e[0], ef)
            loss, grads = grad_of(params, batch)
            payloads, new_ef = local_compress(grads, ef_local)
            gathered = jax.tree.map(
                lambda x: jax.lax.all_gather(x, peers, axis=0, tiled=False),
                payloads)
            new_params = agg_and_apply(params, gathered, lr)
            loss = jax.lax.pmean(loss, peers)
            return new_params, jax.tree.map(lambda e: e[None], new_ef), loss

        # EF buffers ride the param sharding under the leading peer axis
        # (a DeMo-tuned layout the generic scaffold lets us keep)
        efspecs = jax.tree.map(
            lambda s: P(peers if peers else None, *s), pspecs)
        ef_sds = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((K,) + l.shape, ef_dtype), p_sds)
        return _peer_round_plan(
            cfg, mesh, name=f"demo_train[{cfg.name}|{shape.name}]",
            per_peer=per_peer, p_sds=p_sds, pspecs=pspecs,
            state_sds=ef_sds, state_specs=efspecs, batch_sds=batch_sds,
            donate=donate, hints=STEP_HINTS)

    # ---- degenerate single peer (e.g. deepseek-v2 on one pod):
    # gradient over the whole mesh (GSPMD all-reduces over 'data'); the
    # compression pipeline still runs (K=1).
    def step1(params, ef, batch, step_idx):
        lr = warmup_cosine(step_idx, base_lr=hp.learning_rate,
                           warmup_steps=hp.warmup_steps,
                           total_steps=hp.total_steps)
        loss, grads = grad_of(params, batch)
        payloads, new_ef = local_compress(grads, ef)
        stacked = jax.tree.map(
            lambda x: x[None], payloads)
        new_params = agg_and_apply(params, stacked, lr)
        return new_params, new_ef, loss

    bspecs = sh.batch_specs(cfg, batch_sds,
                            sh.dp_axes_for_serving(mesh))
    ef_sds = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, ef_dtype), p_sds)
    return StepPlan(
        name=f"demo_train[{cfg.name}|{shape.name}]", fn=step1,
        args=(_sds_like(p_sds), ef_sds, batch_sds,
              jax.ShapeDtypeStruct((), jnp.int32)),
        in_specs=(pspecs, pspecs, bspecs, P()),
        out_specs=(pspecs, pspecs, P()),
        donate=(0, 1) if donate else (),
        hints=STEP_HINTS)


# ---------------------------------------------------------- any scheme


def make_scheme_train_step(cfg: ModelConfig, hp: TrainConfig, mesh,
                           shape: InputShape, scheme=None,
                           remat: bool = True, ce_chunks: int = 0,
                           scan_layers: Optional[bool] = None,
                           donate: bool = True,
                           microbatch: int = 1) -> StepPlan:
    """Scheme-generic communication round on the mesh: per-peer grad →
    ``scheme.local_step`` → all_gather of the payload pytree →
    ``scheme.aggregate_apply`` — the same scaffold the DeMo step uses
    (:func:`_peer_round_plan`), for ANY registered
    :class:`repro.schemes.GradScheme`. rand-k's flat-index payload
    all_gathers and scatter-adds exactly like DeMo's DCT grids because
    both are pytrees of fixed-shape arrays; the peer's local batch seeds
    its index selection, so per-peer layouts differ on the mesh just as
    they do in the simulator.

    ``scheme`` defaults to ``make_scheme(hp, param_shapes)`` —
    ``hp.scheme`` picks it. Unlike the DeMo-tuned step, per-peer state
    is replicated across any model axes (P(peers) on the leading axis
    only): correct everywhere, merely less sharded than a scheme-aware
    layout could be.
    """
    from repro.schemes import make_scheme
    scan = use_scan(cfg) if scan_layers is None else scan_layers
    peers = sh.effective_peer_axes(cfg, mesh)
    K = sh.num_peers(cfg, mesh)
    p_sds = stacked_param_shapes(cfg) if scan else param_shapes(cfg)
    pspec_fn = sh.stacked_param_specs if scan else sh.param_specs
    pspecs = pspec_fn(cfg, p_sds, mesh)
    batch_sds = input_specs(cfg, shape)
    ng = _inner_groups(cfg, mesh)
    if scheme is None:
        scheme = make_scheme(hp, p_sds)

    def loss_of(params, batch):
        return M.loss_fn(params, batch, cfg, num_groups=ng, remat=remat,
                         ce_chunks=ce_chunks, scan_layers=scan)[0]

    grad_of = make_grad_fn(loss_of, microbatch)
    state_sds0 = jax.eval_shape(scheme.init_state, p_sds)
    name = f"{scheme.name}_train[{cfg.name}|{shape.name}]"

    if peers:
        def per_peer(params, state, batch, step_idx):
            lr = warmup_cosine(step_idx, base_lr=hp.learning_rate,
                               warmup_steps=hp.warmup_steps,
                               total_steps=hp.total_steps)
            state_local = jax.tree.map(lambda s: s[0], state)
            loss, grads = grad_of(params, batch)
            payload, new_state = scheme.local_step(grads, state_local,
                                                   batch=batch)
            gathered = jax.tree.map(
                lambda x: jax.lax.all_gather(x, peers, axis=0,
                                             tiled=False), payload)
            new_params = scheme.aggregate_apply(
                params, gathered, jnp.arange(K, dtype=jnp.int32), lr)
            loss = jax.lax.pmean(loss, peers)
            return (new_params,
                    jax.tree.map(lambda s: s[None], new_state), loss)

        # every state leaf (incl. scalars like a step counter) carries a
        # leading peer axis so one spec tree covers any scheme's state
        state_sds = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((K,) + l.shape, l.dtype),
            state_sds0)
        state_specs = jax.tree.map(lambda _: P(peers), state_sds)
        return _peer_round_plan(
            cfg, mesh, name=name, per_peer=per_peer, p_sds=p_sds,
            pspecs=pspecs, state_sds=state_sds, state_specs=state_specs,
            batch_sds=batch_sds, donate=donate,
            hints=STEP_HINTS)

    # degenerate single peer: K=1, no collective, same scheme math
    def step1(params, state, batch, step_idx):
        lr = warmup_cosine(step_idx, base_lr=hp.learning_rate,
                           warmup_steps=hp.warmup_steps,
                           total_steps=hp.total_steps)
        loss, grads = grad_of(params, batch)
        payload, new_state = scheme.local_step(grads, state, batch=batch)
        stacked = jax.tree.map(lambda x: x[None], payload)
        new_params = scheme.aggregate_apply(
            params, stacked, jnp.arange(1, dtype=jnp.int32), lr)
        return new_params, new_state, loss

    state_specs = jax.tree.map(lambda _: P(), state_sds0)
    bspecs = sh.batch_specs(cfg, batch_sds, sh.dp_axes_for_serving(mesh))
    return StepPlan(
        name=name, fn=step1,
        args=(_sds_like(p_sds), _sds_like(state_sds0), batch_sds,
              jax.ShapeDtypeStruct((), jnp.int32)),
        in_specs=(pspecs, state_specs, bspecs, P()),
        out_specs=(pspecs, state_specs, P()),
        donate=(0, 1) if donate else (),
        hints=STEP_HINTS)


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
        donate=(0, 1) if donate else (),
        hints=STEP_HINTS)


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
        in_specs=(pspecs, cspecs, tspec),
        hints=STEP_HINTS)


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
        in_specs=(pspecs, bspecs),
        hints=STEP_HINTS)


# ----------------------------------------------------------------- picker


def long_context_variant(cfg: ModelConfig) -> ModelConfig:
    """SWA variant for long_500k on archs without native sub-quadratic
    support (DESIGN.md §5)."""
    if cfg.long_context_ok or cfg.family == "ssm" or cfg.attn_window:
        return cfg
    return cfg.with_overrides(attn_window=4096)


def make_step(cfg: ModelConfig, hp: TrainConfig, mesh, shape: InputShape,
              variant: str = "demo", **kw) -> StepPlan:
    if shape.kind == "train":
        if variant == "ddp":
            return make_ddp_train_step(cfg, hp, mesh, shape, **kw)
        # non-demo schemes (or an explicit variant="scheme") take the
        # scheme-generic mesh round; "demo" keeps its tuned step
        if variant == "scheme" or getattr(hp, "scheme", "demo") != "demo":
            return make_scheme_train_step(cfg, hp, mesh, shape, **kw)
        return make_demo_train_step(cfg, hp, mesh, shape, **kw)
    if shape.kind == "prefill":
        return make_prefill_step(cfg, mesh, shape)
    if shape.name == "long_500k":
        cfg = long_context_variant(cfg)
    return make_serve_step(cfg, mesh, shape)

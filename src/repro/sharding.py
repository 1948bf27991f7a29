"""Partition rules: map every param/batch/cache leaf to a PartitionSpec.

Mesh axes:
  pod   — 2 pods (multi-pod only)
  data  — 16-way; for most archs this is the *peer* axis (DeMo pseudo-
          gradient producers); for deepseek-v2-236b it is a second model-
          parallel axis (peer = pod), see DESIGN.md §4
  model — 16-way tensor/expert parallelism inside a peer

Rules are name-based over tree paths, Megatron-style:
  column-parallel (out-dim sharded): wq/wk/wv/gate/up/embedding-vocab/...
  row-parallel (in-dim sharded, psum by GSPMD): wo/down/w_out/...
  expert banks: E over `model`, expert-ff over the secondary axis if free.
GSPMD handles non-divisible dims (56 heads / 16) by padding — the roofline
useful-FLOPs ratio exposes that cost.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.models import attention, mla, rwkv6, ssm
from repro.models.model import DecodeCache


# ----------------------------------------------------------------- axes


def mesh_axis_names(mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def effective_peer_axes(cfg: ModelConfig, mesh) -> Tuple[str, ...]:
    return tuple(a for a in cfg.peer_axes if a in mesh_axis_names(mesh))


def tp_axes(cfg: ModelConfig, mesh) -> Tuple[str, ...]:
    """Model-parallel axes = mesh axes not used as peers ('model' first)."""
    peers = set(effective_peer_axes(cfg, mesh))
    rest = [a for a in mesh_axis_names(mesh) if a not in peers]
    rest.sort(key=lambda a: (a != "model", a))
    return tuple(rest)


def num_peers(cfg: ModelConfig, mesh) -> int:
    shape = dict(mesh.shape)
    n = 1
    for a in effective_peer_axes(cfg, mesh):
        n *= shape[a]
    return max(n, 1)


def dp_axes_for_serving(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh_axis_names(mesh) if a != "model")


# ----------------------------------------------------------------- params


_COL = ("wq", "wk", "wv", "wg", "wr", "wq_a", "wq_b", "wkv_b", "w_in",
        "w_dt", "lm_head", "gate", "up")
_ROW = ("wo", "down", "w_out", "wv_cm")


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
    return "/".join(parts)


def _param_rule(path: str, shape: Tuple[int, ...], tp: Tuple[str, ...]):
    """PartitionSpec for one param leaf. tp = (primary, [secondary])."""
    t1 = tp[0] if tp else None
    t_all = tp if len(tp) > 1 else t1
    parts = path.split("/")
    name = parts[-2] if parts[-1] in ("w", "b") else parts[-1]
    is_bias = parts[-1] == "b"
    in_experts = "experts" in parts

    if in_experts:
        # (E, d, f) banks — Megatron-MoE EP x TP: experts over the
        # SECONDARY axis (the token axis: dispatch becomes an all-to-all
        # there), expert-ffn dim over the primary (model/TP) axis. With a
        # single tp axis, E rides it and f stays unsharded.
        t2 = tp[1] if len(tp) > 1 else None
        e_ax = t2 or t1
        f_ax = t1 if t2 else None
        if name in ("gate", "up"):
            return P(e_ax, None, f_ax)
        if name == "down":
            return P(e_ax, f_ax, None)
        return P()
    if name == "router":
        return P()
    if name == "embed":
        return P(t_all, None)                 # vocab-sharded
    if name == "projector":
        return P()
    if name in _COL or name == "lm_head":
        if is_bias:
            return P(t_all) if len(shape) == 1 else P(None, t_all)
        return P(None, t_all) if len(shape) >= 2 else P(t_all)
    if name in _ROW:
        if is_bias:
            return P()
        return P(t_all, None) if len(shape) >= 2 else P()
    if name == "conv_w":
        return P(None, t_all)
    if name in ("conv_b", "dt_bias", "d_skip"):
        return P(t_all)
    if name == "log_a":
        return P(t_all, None)
    if name == "w_bc":
        return P(t_all, None) if not is_bias else P()
    # norms, ddlerp mixes, decay loras, u/w0, shared small tensors
    return P()


def _mesh_sizes(mesh):
    return dict(mesh.shape)   # works for Mesh and AbstractMesh alike


def fit_spec(spec: P, shape: Tuple[int, ...], mesh) -> P:
    """Degrade a spec until every sharded dim divides evenly (explicit jit
    in_shardings reject uneven shards). Tuple entries drop axes from the
    RIGHT, so the primary ('model') axis survives longest."""
    sizes = _mesh_sizes(mesh)
    out = []
    for i, entry in enumerate(tuple(spec)):
        if entry is None or i >= len(shape):
            out.append(None)
            continue
        axes = tuple(entry) if isinstance(entry, tuple) else (entry,)
        axes = tuple(a for a in axes if a in sizes)
        while axes:
            prod = int(np.prod([sizes[a] for a in axes]))
            if shape[i] % prod == 0:
                break
            axes = axes[:-1]
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(axes)
    while len(out) < len(shape):
        out.append(None)
    return P(*out)


def param_specs(cfg: ModelConfig, params, mesh):
    """PartitionSpec pytree matching ``params`` (works on SDS trees too)."""
    tp = tp_axes(cfg, mesh)

    def rule(path, leaf):
        # channel-mix wv (f, d) is row-parallel but named "wv": disambiguate
        ps = _path_str(path)
        if ps.endswith("channel_mix/wv/w"):
            spec = P(tp if len(tp) > 1 else tp[0], None)
        elif ps.endswith("channel_mix/wv/b"):
            spec = P()
        else:
            spec = _param_rule(ps, leaf.shape, tp)
        return fit_spec(spec, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(rule, params)


def ef_specs(cfg: ModelConfig, params, mesh):
    """DeMo error-feedback buffers carry a leading peer axis."""
    peers = effective_peer_axes(cfg, mesh)
    pspecs = param_specs(cfg, params, mesh)
    return jax.tree.map(lambda s: P(peers if peers else None, *s), pspecs)


def stacked_param_specs(cfg: ModelConfig, params, mesh):
    """Specs for the scan-over-layers tree (``model.stack_params``):
    same name-based rules, with the leading group-stack dim replicated."""
    from repro.models.model import layer_groups
    tp = tp_axes(cfg, mesh)
    groups = layer_groups(cfg)

    def rule(path, leaf):
        ps = _path_str(path)
        parts = ps.split("/")
        stacked = (parts[0] == "groups" and len(parts) > 1
                   and parts[1].isdigit() and groups[int(parts[1])][1] > 1)
        shape = leaf.shape[1:] if stacked else leaf.shape
        if ps.endswith("channel_mix/wv/w"):
            spec = P(tp if len(tp) > 1 else tp[0], None)
        elif ps.endswith("channel_mix/wv/b"):
            spec = P()
        else:
            spec = _param_rule(ps, shape, tp)
        spec = fit_spec(spec, shape, mesh)
        return P(None, *spec) if stacked else spec

    return jax.tree_util.tree_map_with_path(rule, params)


# ----------------------------------------------------------------- batch


def batch_specs(cfg: ModelConfig, batch, dp: Tuple[str, ...], mesh=None):
    dp_spec = dp if dp else None

    def rule(path, leaf):
        if leaf.ndim == 0:
            return P()
        if leaf.shape[0] == 1:
            return P(*(None,) * leaf.ndim)
        spec = P(dp_spec, *(None,) * (leaf.ndim - 1))
        return fit_spec(spec, leaf.shape, mesh) if mesh is not None else spec

    return jax.tree_util.tree_map_with_path(rule, batch)


# ----------------------------------------------------------------- cache


def _cache_layer_spec(c, mesh, shape: InputShape):
    """Spec tree for ONE layer's decode cache (any family)."""
    dp = dp_axes_for_serving(mesh)
    sizes = _mesh_sizes(mesh)
    b1 = shape.global_batch == 1
    bspec = None if b1 else dp

    def fit(spec, shp):
        return fit_spec(spec, shp, mesh)

    def kv_spec(c: attention.KVCache):
        Hkv = c.k.shape[2]
        kv_tp = "model" if Hkv % sizes.get("model", 1) == 0 else None
        seq = []
        if b1 and "data" in sizes:
            seq.append("data")
        if kv_tp is None:
            seq.append("model")   # flash-decode style seq sharding instead
        s = P(bspec, tuple(seq) or None, kv_tp, None)
        return attention.KVCache(k=fit(s, c.k.shape), v=fit(s, c.v.shape),
                                 pos=P())

    def mla_spec(c: mla.MLACache):
        seq = ("data", "model") if b1 else ("model",)
        return mla.MLACache(
            c_kv=fit(P(bspec, seq, None), c.c_kv.shape),
            k_rope=fit(P(bspec, seq, None), c.k_rope.shape), pos=P())

    def rwkv_spec(c: rwkv6.RWKVState):
        return rwkv6.RWKVState(
            wkv=fit(P(bspec, "model", None, None), c.wkv.shape),
            shift_tm=fit(P(bspec, None), c.shift_tm.shape),
            shift_cm=fit(P(bspec, None), c.shift_cm.shape),
            step=P())

    def ssm_spec(c: ssm.SSMState):
        return ssm.SSMState(h=fit(P(bspec, "model", None), c.h.shape),
                            conv=fit(P(bspec, None, "model"), c.conv.shape))

    def one(c):
        if isinstance(c, attention.KVCache):
            return kv_spec(c)
        if isinstance(c, mla.MLACache):
            return mla_spec(c)
        if isinstance(c, rwkv6.RWKVState):
            return rwkv_spec(c)
        if isinstance(c, ssm.SSMState):
            return ssm_spec(c)
        if isinstance(c, tuple) and not hasattr(c, "_fields"):
            return tuple(one(x) for x in c)
        raise TypeError(type(c))

    return one(c)


def _cross_spec(k, mesh, shape: InputShape):
    dp = dp_axes_for_serving(mesh)
    bspec = None if shape.global_batch == 1 else dp
    return fit_spec(P(bspec, None, "model", None), k.shape, mesh)


def cache_specs(cfg: ModelConfig, cache: DecodeCache, mesh,
                shape: InputShape):
    """Decode-cache shardings. batch over the serving dp axes; kv-heads /
    states over model; for global_batch=1 long-context the cache *sequence*
    dim is sharded over `data` (flash-decode style)."""
    layer = tuple(_cache_layer_spec(c, mesh, shape)
                  for c in cache.layer_caches)
    cross = None
    if cache.cross_kv is not None:
        cross = tuple((_cross_spec(k, mesh, shape),
                       _cross_spec(v, mesh, shape))
                      for k, v in cache.cross_kv)
    return DecodeCache(layer_caches=layer, cross_kv=cross)


def _strip0(tree):
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), tree)


def _prepend_none(spec_tree):
    return jax.tree.map(lambda s: P(None, *s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def grouped_cache_specs(cfg: ModelConfig, gcache: DecodeCache, mesh,
                        shape: InputShape):
    """Specs for a ``model.group_cache`` tree (scan-over-layers decode):
    per-group leaves carry a leading stack dim, replicated."""
    from repro.models.model import layer_groups
    groups = layer_groups(cfg)
    layer = []
    for (s_, n), c in zip(groups, gcache.layer_caches):
        if n == 1:
            layer.append(_cache_layer_spec(c, mesh, shape))
        else:
            spec = _cache_layer_spec(_strip0(c), mesh, shape)
            layer.append(_prepend_none(spec))
    cross = None
    if gcache.cross_kv is not None:
        cross = []
        for (s_, n), ck in zip(groups, gcache.cross_kv):
            k, v = ck
            if n == 1:
                cross.append((_cross_spec(k, mesh, shape),
                              _cross_spec(v, mesh, shape)))
            else:
                ks = jax.ShapeDtypeStruct(k.shape[1:], k.dtype)
                vs = jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
                cross.append(
                    (P(None, *_cross_spec(ks, mesh, shape)),
                     P(None, *_cross_spec(vs, mesh, shape))))
        cross = tuple(cross)
    return DecodeCache(layer_caches=tuple(layer), cross_kv=cross)


# ------------------------------------------------------ validator mesh

# Axis name of the validator's peer mesh (see launch.mesh.make_peer_mesh).
# Distinct from the training mesh's "data" axis: the validator shards the
# *scored-peer* dimension of its round entry points, not the batch.
PEER_AXIS = "peers"

# How far a multi-device peer mesh may move a LossScore from the unsharded
# round. A LossScore is L(θ) − L(θ − βΔ) in float32; splitting the rows
# over devices changes XLA's reduction order inside each loss, which moves
# it by about an ulp (9.5e-7 below 16), so the difference can move by
# two. A relative bound means nothing on such a near-cancelling
# difference. Weights, audit flags and aggregated params stay exact.
MESH_SCORE_ATOL = 2e-6


def peer_mesh_size(mesh) -> int:
    """Device count along the validator peer axis (1 for mesh=None)."""
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get(PEER_AXIS, 1))


def shard_map_rows(mesh, fn, row_args, axis: str = PEER_AXIS):
    """Row-parallel shard_map wrapper for the Gauntlet's jitted stages.

    Positional args whose index is in ``row_args`` are split along axis 0
    over the mesh's ``axis`` (P(axis) as a pytree-prefix spec, so whole
    payload/batch pytrees shard by rows); everything else is replicated.
    Every output is row-sharded and concatenates back in device order,
    i.e. original row order. ``fn`` must be collective-free and
    row-independent — each of the validator's padded entry points is,
    because PR-4's masked padding rows are exact no-ops, so any
    row-aligned slice of the bucket computes independently.
    """
    row_args = frozenset(row_args)

    def wrapped(*args):
        in_specs = tuple(P(axis) if i in row_args else P()
                         for i in range(len(args)))
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=P(axis), axis_names={axis},
                             check_vma=False)(*args)

    return wrapped


# ----------------------------------------------------------------- utils


def named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


# re-export the trace-time hints (separate module to avoid import cycles)
from repro.hints import axis_hints, constrain_heads  # noqa: E402,F401

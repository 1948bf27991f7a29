"""DeMo (Decoupled Momentum, arXiv:2411.19870) as a :class:`GradScheme`:
the paper's codec — top-k selection over per-chunk DCT coefficients —
plus the fused local step and the normalize→mean→sign aggregation.

    local:     e ← β·e + g ;  q ← topk(dct(e)) ;  e ← e − dct⁻¹(q)
    aggregate: q_k ← q_k / ||q_k||₂ ;  Δ ← sign(dct⁻¹(Σ_k w_k q_k))
    update:    θ ← θ − α·Δ

A compressed pseudo-gradient ("payload") is, per parameter tensor:
    vals (num_chunks, k) float32   — kept DCT coefficients
    idx  (num_chunks, k) int32     — their positions within the s*s chunk
Payloads are dict pytrees mirroring the param tree, so they ride through
jit/pjit/shard_map and ``jax.lax.all_gather`` unchanged.

This module is the ONLY place that owns the DeMo payload layout: the
validator, peers, audit and simulator reach it through the scheme object
(``hp.scheme = "demo"``), and the DeMo-specific mesh step / codec tests
import the functions below directly. The aggregation accepts payloads
with a leading peer axis (as produced by ``jax.lax.all_gather`` over the
peer mesh axes) or a list of payloads (the host-level validator path).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.demo import dct
from repro.obs import trace
from repro.schemes import GradScheme, register_scheme


class Payload(NamedTuple):
    vals: jnp.ndarray   # (num_chunks, k)
    idx: jnp.ndarray    # (num_chunks, k) int32


def _is_payload(x) -> bool:
    return isinstance(x, Payload)


# ------------------------------------------------------------- codec


def topk_compress(coeffs: jnp.ndarray, k: int) -> Payload:
    """coeffs: (num_chunks, s*s) -> top-|k| by magnitude per chunk."""
    with jax.named_scope(trace.SCOPE_TOPK):
        mag = jnp.abs(coeffs)
        _, idx = jax.lax.top_k(mag, k)
        vals = jnp.take_along_axis(coeffs, idx, axis=-1)
        return Payload(vals=vals, idx=idx.astype(jnp.int32))


def topk_decompress(p: Payload, chunk_elems: int) -> jnp.ndarray:
    """Payload -> dense (num_chunks, s*s) coefficient grid (zeros filled)."""
    nc = p.vals.shape[0]
    with jax.named_scope(trace.SCOPE_DECODE):
        out = jnp.zeros((nc, chunk_elems), jnp.float32)
        return out.at[jnp.arange(nc)[:, None], p.idx].set(
            p.vals.astype(jnp.float32))


# ------------------------------------------------------------- tree utils


def stack_payloads(payload_trees: Sequence[Any]):
    """List of per-peer payload pytrees -> one pytree whose Payload leaves
    carry a leading peer axis K.

    This is THE stacking idiom for the host-level paths (the validator's
    batched round stages, peer-side coordinated aggregation) — the same
    layout ``jax.lax.all_gather`` produces on the mesh path, so everything
    downstream of it is shared.
    """
    return jax.tree.map(
        lambda *ps: Payload(vals=jnp.stack([p.vals for p in ps]),
                            idx=jnp.stack([p.idx for p in ps])),
        *payload_trees, is_leaf=_is_payload)


def pad_payloads(stacked, total: int):
    """Pad the leading peer axis of a stacked payload tree to ``total``
    rows with zero payloads (vals 0.0, idx 0 — a valid index, and the
    zero coefficients decompress to an exactly-zero delta). The static-
    shape round pipeline pads |S_t| to a sticky bucket so the jitted
    entry points compile once; padded rows are masked or sliced away."""
    return jax.tree.map(
        lambda p: Payload(
            vals=jnp.concatenate(
                [p.vals, jnp.zeros((total - p.vals.shape[0],)
                                   + p.vals.shape[1:], p.vals.dtype)]),
            idx=jnp.concatenate(
                [p.idx, jnp.zeros((total - p.idx.shape[0],)
                                  + p.idx.shape[1:], p.idx.dtype)]))
        if p.vals.shape[0] < total else p,
        stacked, is_leaf=_is_payload)


def take_payloads(stacked, rows):
    """Select ``rows`` along the leading peer axis of a stacked payload
    tree (traceable — the validator reuses its already-stacked eval-set
    payloads for top-G aggregation by gathering rows inside jit)."""
    rows = jnp.asarray(rows, jnp.int32)
    return jax.tree.map(
        lambda p: Payload(vals=jnp.take(p.vals, rows, axis=0),
                          idx=jnp.take(p.idx, rows, axis=0)),
        stacked, is_leaf=_is_payload)


def tree_meta(params, s: int) -> Dict[str, Any]:
    return jax.tree.map(lambda x: dct.chunk_meta(x.shape, s), params)


def compress_tree(tree, metas, k: int):
    """Pytree of tensors -> pytree of Payloads."""
    return jax.tree.map(
        lambda x, m: topk_compress(dct.encode(x, m), k), tree, metas)


def decompress_tree(payloads, metas):
    """Pytree of Payloads -> pytree of dense tensors."""
    return jax.tree.map(
        lambda p, m: dct.decode(topk_decompress(p, m.s * m.s), m),
        payloads, metas, is_leaf=_is_payload)


def payload_global_norm(payload_tree) -> jnp.ndarray:
    """L2 norm over every kept coefficient of a peer's payload."""
    leaves = [p.vals for p in jax.tree.leaves(
        payload_tree, is_leaf=_is_payload)]
    return jnp.sqrt(sum(jnp.sum(v.astype(jnp.float32) ** 2) for v in leaves))


def normalize_payload(payload_tree, eps: float = 1e-12):
    """Paper §4 / Algo 2 line 12: per-peer L2 normalization in the DCT
    (encoded) domain — byzantine norm-rescaling defense."""
    n = payload_global_norm(payload_tree)
    scale = 1.0 / (n + eps)
    return jax.tree.map(
        lambda p: Payload(vals=p.vals * scale, idx=p.idx), payload_tree,
        is_leaf=_is_payload)


def payload_bytes(payload_tree) -> int:
    """Wire size of one peer's compressed pseudo-gradient."""
    total = 0
    for p in jax.tree.leaves(payload_tree, is_leaf=_is_payload):
        total += p.vals.size * p.vals.dtype.itemsize
        total += p.idx.size * 2  # int16 on the wire (s*s <= 2^15)
    return total


def flatten_payloads_for_sketch(stacked) -> List[Tuple[Any, Any]]:
    """(values, position-ids) pairs for the count-sketch fingerprinter:
    each kept coefficient's id mixes its chunk row and intra-chunk
    position, so identical payloads sketch identically while independent
    ones decorrelate (``repro.audit.fingerprint.sketch_pairs``)."""
    out = []
    for p in jax.tree.leaves(stacked, is_leaf=_is_payload):
        nc = p.idx.shape[1]
        cid = jnp.arange(nc, dtype=jnp.uint32)[None, :, None]
        ids = (p.idx.astype(jnp.uint32) * jnp.uint32(2654435761)
               + cid * jnp.uint32(40503))
        out.append((p.vals, ids))
    return out


# ------------------------------------------------------------- optimizer


class DemoState(NamedTuple):
    ef: object            # error-feedback buffer, pytree like params
    step: jnp.ndarray


def init_state(params, dtype=None) -> DemoState:
    mk = (lambda x: jnp.zeros(x.shape, dtype or x.dtype))
    return DemoState(ef=jax.tree.map(mk, params),
                     step=jnp.zeros((), jnp.int32))


def local_step(grads, state: DemoState, *, beta: float, chunk: int,
               k: int, metas=None, encode_fn=None):
    """One peer's pseudo-gradient production.

    Returns (payload_tree, new_state). ``encode_fn`` lets the caller swap in
    the Pallas kernel pipeline; default is the jnp reference.
    """
    metas = metas or tree_meta(grads, chunk)

    def per_leaf(e, g, m):
        with jax.named_scope(trace.SCOPE_ENCODE):
            e = beta * e.astype(jnp.float32) + g.astype(jnp.float32)
            coeffs = (encode_fn or dct.encode)(e, m)
        payload = topk_compress(coeffs, k)
        z = dct.decode(topk_decompress(payload, m.s * m.s), m)
        with jax.named_scope(trace.SCOPE_DECODE):
            e_new = e - z
        return payload, e_new

    flat_e, treedef = jax.tree.flatten(state.ef)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(metas)
    outs = [per_leaf(e, g, m) for e, g, m in zip(flat_e, flat_g, flat_m)]
    payloads = jax.tree.unflatten(treedef, [o[0] for o in outs])
    new_ef = jax.tree.unflatten(
        treedef, [o[1].astype(e.dtype) for o, e in zip(outs, flat_e)])
    return payloads, DemoState(ef=new_ef, step=state.step + 1)


def aggregate(payloads, metas, weights: Optional[jnp.ndarray] = None,
              normalize: bool = True, apply_sign: bool = True):
    """Aggregate peer payloads into the global update Δ.

    ``payloads``: either a list (host path) of payload trees, or a single
    payload tree whose leaves carry a leading peer axis K (all_gather path).
    Returns a dense pytree Δ shaped like params.
    """
    if isinstance(payloads, (list, tuple)):
        stacked = stack_payloads(payloads)
    else:
        stacked = payloads
    K = jax.tree.leaves(stacked, is_leaf=_is_payload)[0].vals.shape[0]
    if weights is None:
        weights = jnp.full((K,), 1.0 / K, jnp.float32)

    with jax.named_scope(trace.SCOPE_APPLY):
        if normalize:
            # per-peer global L2 over the stacked payload (DCT domain)
            sq = sum(jnp.sum(p.vals.astype(jnp.float32) ** 2,
                             axis=tuple(range(1, p.vals.ndim)))
                     for p in jax.tree.leaves(stacked, is_leaf=_is_payload))
            inv = 1.0 / (jnp.sqrt(sq) + 1e-12)                # (K,)
        else:
            inv = jnp.ones((K,), jnp.float32)
        w = (weights * inv).astype(jnp.float32)               # (K,)

    def combine(p: Payload, m: dct.ChunkMeta):
        from repro import hints
        nc, k = p.vals.shape[1], p.vals.shape[2]
        with jax.named_scope(trace.SCOPE_DECODE):
            grid = jnp.zeros((nc, m.s * m.s), jnp.float32)
            # scatter-add all peers' weighted coefficients into one grid
            rows = jnp.broadcast_to(jnp.arange(nc)[None, :, None],
                                    p.idx.shape)
            grid = grid.at[rows, p.idx].add(
                p.vals.astype(jnp.float32) * w[:, None, None])
            grid = hints.constrain_chunks(grid)   # keep the dense fp32 grid
        delta = dct.decode(grid, m)               # sharded (no-op on hosts)
        if not apply_sign:
            return delta
        with jax.named_scope(trace.SCOPE_APPLY):
            return jnp.sign(delta)

    return jax.tree.map(combine, stacked, metas, is_leaf=_is_payload)


def apply_update(params, delta, lr, weight_decay: float = 0.0):
    """θ ← (1 − α·λ)·θ − α·Δ (decoupled wd, matches AdamW convention)."""
    def upd(p, d):
        p32 = p.astype(jnp.float32)
        if weight_decay:
            p32 = p32 * (1.0 - lr * weight_decay)
        return (p32 - lr * d.astype(jnp.float32)).astype(p.dtype)
    with jax.named_scope(trace.SCOPE_APPLY):
        return jax.tree.map(upd, params, delta)


def aggregate_apply(params, stacked, rows, lr, weights=None, *, metas,
                    normalize: bool = True, apply_sign: bool = True):
    """One fused coordinated-update step: gather ``rows`` (peer indices)
    from the stacked payloads, aggregate (Algo 2) and apply θ ← θ − α·Δ.

    Validator and peers both jit this exact function (with metas bound),
    so every replica runs the same compiled program and stays bit-identical.
    ``rows`` lets the validator reuse its already-stacked eval-set payloads
    for top-G aggregation without re-fetching or re-stacking. ``weights``
    (len(rows),) supports static-shape padding: callers pad ``rows`` to a
    fixed bucket and zero the padded entries' weights, which multiply
    every padded contribution down to exact ±0.0 adds — the aggregate is
    bit-identical to the unpadded call. None keeps the uniform 1/K
    default.
    """
    sub = take_payloads(stacked, rows)
    delta = aggregate(sub, metas, weights=weights, normalize=normalize,
                      apply_sign=apply_sign)
    return apply_update(params, delta, lr)


def single_peer_delta(payload_tree, metas, apply_sign: bool = True):
    """Δ for one peer's contribution (validator LossScore path, Algo 1:
    θ'_p = θ − β·Sign(Δ_p))."""
    dense = decompress_tree(payload_tree, metas)
    if apply_sign:
        dense = jax.tree.map(jnp.sign, dense)
    return dense


# ------------------------------------------------------------- scheme


@register_scheme
class DemoScheme(GradScheme):
    """DCT-top-k DeMo, bound to one param tree's chunk layout."""

    name = "demo"

    def __init__(self, hp, params):
        super().__init__(hp, params)
        self.metas = tree_meta(params, hp.demo_chunk)

    def cache_key(self) -> tuple:
        return (self.name, self.hp.demo_beta, self.hp.demo_chunk,
                self.hp.demo_topk)

    # ------------------------------------------------- peer production
    def init_state(self, params):
        return init_state(params)

    def local_step(self, grads, state, batch=None):
        return local_step(grads, state, beta=self.hp.demo_beta,
                          chunk=self.hp.demo_chunk, k=self.hp.demo_topk,
                          metas=self.metas)

    # -------------------------------------------- validator evaluation
    def single_peer_delta(self, payload):
        return single_peer_delta(payload, self.metas)

    def aggregate_apply(self, params, stacked, rows, lr, weights=None):
        return aggregate_apply(params, stacked, rows, lr, weights,
                               metas=self.metas)

    # (payload staging: the generic GradScheme stack/pad/take ops apply
    # as-is — Payload is a NamedTuple pytree node, so they stack/pad/
    # gather its vals and idx fields exactly like the Payload-aware
    # module functions above, which remain for DeMo-specific callers)

    # ------------------------------------------------------ wire format
    def payload_bytes(self, payload):
        return payload_bytes(payload)

    def estimate_payload_bytes(self) -> int:
        total = 0
        for m in jax.tree.leaves(self.metas):
            total += m.num_chunks * self.hp.demo_topk * (4 + 2)
        return total

    def format_ok(self, payload) -> bool:
        try:
            flat_p = jax.tree.leaves(payload, is_leaf=_is_payload)
            flat_m = jax.tree.leaves(self.metas)
            if len(flat_p) != len(flat_m):
                return False
            for p, m in zip(flat_p, flat_m):
                if not isinstance(p, Payload):
                    return False
                nc = m.num_chunks
                if (p.vals.shape != (nc, self.hp.demo_topk)
                        or p.idx.shape != (nc, self.hp.demo_topk)):
                    return False
                if p.idx.dtype != jnp.int32:
                    return False
            # value sanity fused into one jitted scalar (one sync total,
            # not 3 blocking reads per leaf — see GradScheme._values_ok)
            return self._values_ok(payload)
        except Exception:
            return False

    def _value_check(self, payload):
        flat_p = jax.tree.leaves(payload, is_leaf=_is_payload)
        flat_m = jax.tree.leaves(self.metas)
        ok = jnp.bool_(True)
        for p, m in zip(flat_p, flat_m):
            ok &= jnp.isfinite(p.vals).all()
            ok &= (p.idx >= 0).all() & (p.idx < m.s * m.s).all()
        return ok

    # ------------------------------------------------------------ audit
    def flatten_for_sketch(self, stacked):
        return flatten_payloads_for_sketch(stacked)

    # ----------------------------------------------------- fabrication
    def compress(self, tree, seed: int = 0):
        return compress_tree(tree, self.metas, self.hp.demo_topk)

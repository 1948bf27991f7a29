"""Random-k sparsification with sign-SGD aggregation: the second real
:class:`GradScheme`, proving the Gauntlet pipeline is scheme-generic.

    local:     e ← β·e + g ;  I ← randk(seed(D), k) ;  q ← e[I] ; e[I] ← 0
    aggregate: q_p ← q_p / ||q_p||₂ ;  Δ ← sign(Σ_p w_p scatter(q_p))
    update:    θ ← θ − α·Δ

A payload is, per parameter tensor, ``vals (k,) float32`` + flat
``idx (k,) int32`` into the flattened tensor — no transform domain, a
genuinely different wire format from DeMo's per-chunk DCT grids (int32
positions instead of int16 intra-chunk offsets; fp16-quantizable values).

**Data-seeded index selection.** The k kept coordinates per tensor are a
pseudo-random subset drawn from a seed derived from the *content of the
batch the peer trained on* (plus the leaf index). This makes the layout
auditable by construction: the validator's replay audit recomputes the
local step from the chain-derived assignment and lands on the SAME
coordinates as an honest peer (same batch → same seed), so the
count-sketch cosine between payload and replay stays high; a copycat's
payload carries its *victim's* coordinates, which a replay of the
copycat's own assignment never reproduces — the decoy margin collapses
exactly as it does for DeMo. Selection is a top-k over hashed per-
position priorities (one fused pass, vmappable, no host RNG), so the
whole local step stays a single jit-shareable program.
"""
from __future__ import annotations

from typing import Any, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp

# the same Murmur3-style finalizer the count-sketch hashes with: index
# selection and sketch-slot hashing must stay one construction so the
# replay audit's payload/replay cosines line up
from repro.audit.fingerprint import mix_u32 as _mix_u32
from repro.schemes import GradScheme, register_scheme


class RandKPayload(NamedTuple):
    vals: jnp.ndarray   # (k,) float32 kept entries
    idx: jnp.ndarray    # (k,) int32 flat positions into the tensor


def _is_rk(x) -> bool:
    return isinstance(x, RandKPayload)


def batch_seed(batch) -> jnp.ndarray:
    """uint32 content digest of a data-batch pytree, inside the trace.

    Deterministic in leaf order and content, so a peer and the
    validator's replay of the same assigned batch derive the same index
    seed. Not collision-resistant like the chain commitment digest (it
    does not need to be: it only decides *which* coordinates ship).
    """
    acc = jnp.uint32(0x9E3779B9)
    for leaf in jax.tree.leaves(batch):
        x = jnp.asarray(leaf)
        if jnp.issubdtype(x.dtype, jnp.floating):
            bits = jax.lax.bitcast_convert_type(
                x.astype(jnp.float32), jnp.uint32)
        else:
            bits = x.astype(jnp.uint32)
        flat = bits.reshape(-1)
        pos = jnp.arange(flat.shape[0], dtype=jnp.uint32)
        h = _mix_u32(flat * jnp.uint32(2654435761)
                     + pos * jnp.uint32(40503), jnp.uint32(0))
        acc = _mix_u32(acc ^ jnp.sum(h), jnp.uint32(0xA511E9B3))
    return acc


def _select_idx(n: int, k: int, seed, leaf_salt: int) -> jnp.ndarray:
    """k distinct pseudo-random flat positions in [0, n): top-k over
    hashed per-position priorities. ``seed`` may be traced (data-derived);
    the layout is a uniform-ish k-subset, deterministic in (seed, leaf)."""
    pos = jnp.arange(n, dtype=jnp.uint32)
    pri = _mix_u32(pos * jnp.uint32(2246822519)
                   + jnp.uint32(leaf_salt & 0xFFFFFFFF), seed)
    # drop the top bit so the priorities sort correctly as int32
    _, idx = jax.lax.top_k((pri >> 1).astype(jnp.int32), k)
    return idx.astype(jnp.int32)


@register_scheme
class RandKScheme(GradScheme):
    """Seeded random-k + sign-SGD, bound to one param tree's leaf sizes."""

    name = "randk"

    def __init__(self, hp, params):
        super().__init__(hp, params)
        self._remember_shapes(params)
        # static per-leaf k: a fraction of each tensor's elements
        self._ks: Tuple[int, ...] = tuple(
            max(1, int(round(n * hp.randk_frac)))
            for n in self._leaf_sizes())

    def cache_key(self) -> tuple:
        return (self.name, self.hp.randk_beta, self.hp.randk_frac,
                self._ks)

    # ------------------------------------------------- peer production
    def local_step(self, grads, ef, batch=None):
        seed = (batch_seed(batch) if batch is not None
                else jnp.uint32(self.hp.seed))
        flat_e, treedef = jax.tree.flatten(ef)
        flat_g = jax.tree.leaves(grads)
        payloads, new_ef = [], []
        for li, (e, g, k) in enumerate(zip(flat_e, flat_g, self._ks)):
            e32 = (self.hp.randk_beta * e.astype(jnp.float32)
                   + g.astype(jnp.float32))
            flat = e32.reshape(-1)
            idx = _select_idx(flat.shape[0], k, seed, li * 10007 + 1)
            vals = jnp.take(flat, idx)
            # error feedback: only what shipped leaves the buffer
            e_new = flat.at[idx].add(-vals).reshape(e32.shape)
            payloads.append(RandKPayload(vals=vals, idx=idx))
            new_ef.append(e_new.astype(e.dtype))
        return (jax.tree.unflatten(treedef, payloads),
                jax.tree.unflatten(treedef, new_ef))

    # -------------------------------------------- validator evaluation
    def single_peer_delta(self, payload):
        out = []
        leaves_p = jax.tree.leaves(payload, is_leaf=_is_rk)
        for p, n, shape in zip(leaves_p, self._leaf_sizes(),
                               self._leaf_shapes()):
            flat = jnp.zeros((n,), jnp.float32).at[p.idx].set(
                p.vals.astype(jnp.float32))
            out.append(jnp.sign(flat).reshape(shape))
        return jax.tree.unflatten(self._treedef(), out)

    def aggregate(self, stacked, weights=None):
        K = jax.tree.leaves(stacked)[0].shape[0]
        if weights is None:
            weights = jnp.full((K,), 1.0 / K, jnp.float32)
        # per-peer global L2 over the kept entries (norm-attack defense)
        sq = sum(jnp.sum(p.vals.astype(jnp.float32) ** 2, axis=-1)
                 for p in jax.tree.leaves(stacked, is_leaf=_is_rk))
        w = (weights * (1.0 / (jnp.sqrt(sq) + 1e-12))).astype(jnp.float32)
        out = []
        for p, n, shape in zip(jax.tree.leaves(stacked, is_leaf=_is_rk),
                               self._leaf_sizes(), self._leaf_shapes()):
            flat = jnp.zeros((n,), jnp.float32).at[p.idx.reshape(-1)].add(
                (p.vals.astype(jnp.float32) * w[:, None]).reshape(-1))
            out.append(jnp.sign(flat).reshape(shape))
        return jax.tree.unflatten(self._treedef(), out)

    # ------------------------------------------------------ wire format
    def payload_bytes(self, payload) -> int:
        # fp16-quantized values + int32 flat positions on the wire
        total = 0
        for p in jax.tree.leaves(payload, is_leaf=_is_rk):
            total += p.vals.size * 2 + p.idx.size * 4
        return total

    def estimate_payload_bytes(self) -> int:
        return sum(k * (2 + 4) for k in self._ks)

    def format_ok(self, payload) -> bool:
        try:
            flat_p = jax.tree.leaves(payload, is_leaf=_is_rk)
            sizes = self._leaf_sizes()
            if len(flat_p) != len(sizes):
                return False
            for p, n, k in zip(flat_p, sizes, self._ks):
                if not isinstance(p, RandKPayload):
                    return False
                if p.vals.shape != (k,) or p.idx.shape != (k,):
                    return False
                if p.idx.dtype != jnp.int32:
                    return False
            # value sanity fused into one jitted scalar (one sync total,
            # not 3 blocking reads per leaf — see GradScheme._values_ok)
            return self._values_ok(payload)
        except Exception:
            return False

    def _value_check(self, payload):
        flat_p = jax.tree.leaves(payload, is_leaf=_is_rk)
        ok = jnp.bool_(True)
        for p, n in zip(flat_p, self._leaf_sizes()):
            ok &= jnp.isfinite(p.vals).all()
            ok &= (p.idx >= 0).all() & (p.idx < n).all()
        return ok

    # ------------------------------------------------------------ audit
    def flatten_for_sketch(self, stacked) -> List[Tuple[Any, Any]]:
        return [(p.vals, p.idx.astype(jnp.uint32) * jnp.uint32(2654435761))
                for p in jax.tree.leaves(stacked, is_leaf=_is_rk)]

    # ----------------------------------------------------- fabrication
    def compress(self, tree, seed: int = 0):
        flat, treedef = jax.tree.flatten(tree)
        out = []
        for li, (x, k) in enumerate(zip(flat, self._ks)):
            idx = _select_idx(jnp.size(x), k, jnp.uint32(seed),
                              li * 10007 + 1)
            out.append(RandKPayload(
                vals=jnp.take(x.astype(jnp.float32).reshape(-1), idx),
                idx=idx))
        return jax.tree.unflatten(treedef, out)

    # ------------------------------------------------- shape bookkeeping
    def _remember_shapes(self, params) -> None:
        leaves, treedef = jax.tree.flatten(params)
        self._shapes = tuple(tuple(l.shape) for l in leaves)
        self._sizes = tuple(int(jnp.size(l)) for l in leaves)
        self._td = treedef

    def _leaf_shapes(self):
        return self._shapes

    def _leaf_sizes(self):
        return self._sizes

    def _treedef(self):
        return self._td

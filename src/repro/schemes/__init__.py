"""Pluggable gradient-scheme API: the seam between the Gauntlet
incentive pipeline and the synchronous distributed-training scheme.

The paper's portability claim is that the Gauntlet applies to *any*
synchronous scheme that aggregates updates or pseudo-gradients. This
package makes that true of the repo: everything the validator, the
peers, the uniqueness audit and the simulator need from the training
scheme is behind :class:`GradScheme` —

* the **payload** pytree type (whatever the scheme puts in a bucket),
  its wire size, and structural format validation;
* peer-side production: per-peer optimizer state (error feedback) and
  the fused ``local_step`` (grads → payload);
* validator-side evaluation: ``single_peer_delta`` (the dense signed
  update a LossScore evaluates) and the fused, jit-shareable
  ``aggregate_apply`` (the coordinated model update every replica runs
  bit-identically);
* host-level payload staging: ``stack/pad/take_payloads`` over the
  leading peer axis — generic pytree ops, so the static-shape padded
  round entry points work for any payload layout;
* the audit hook ``flatten_for_sketch``: (values, position-ids) pairs
  the count-sketch fingerprinter hashes, instead of assuming any
  particular payload field layout.

Schemes register by name (``@register_scheme``) and are selected via
``hp.scheme`` / ``Scenario.scheme`` through :func:`make_scheme`.
``repro.schemes.demo`` (DCT-top-k DeMo, the paper's codec) is the
default; ``repro.schemes.randk`` (seeded random-k sparsification with
sign-SGD aggregation) proves the pipeline is scheme-generic.

Every method that runs inside jit (``local_step``, ``aggregate_apply``,
``single_peer_delta``, ``flatten_for_sketch``, the payload tree ops)
must be traceable; everything else is host-side. Scheme instances hold
only *derived shape metadata* (e.g. DCT chunk layouts), never parameter
arrays — they ride inside shared jit-cache closures.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence, Tuple, Type

import jax
import jax.numpy as jnp


def tree_signature(params) -> tuple:
    """Hashable (structure, shapes, dtypes) fingerprint of a pytree —
    the jit-cache key ingredient for shape-polymorphic shared programs.
    Leaves may be arrays or ``jax.ShapeDtypeStruct`` stand-ins."""
    leaves, treedef = jax.tree.flatten(params)
    return (treedef,
            tuple((tuple(l.shape), str(jnp.dtype(l.dtype)))
                  for l in leaves))


class GradScheme:
    """Abstract base for a distributed-training update scheme.

    Subclasses implement the scheme-specific math; the generic payload
    staging below works for any payload that is a pytree of arrays with
    a leading peer axis after :meth:`stack_payloads` (NamedTuple payload
    leaves are pytree nodes, so the generic ops see their fields as
    ordinary array leaves).
    """

    name: str = "abstract"

    def __init__(self, hp, params):
        self.hp = hp

    # ---------------------------------------------------- identity
    def cache_key(self) -> tuple:
        """Hashable knob tuple: two scheme instances with equal keys (and
        equal param tree signatures) may share compiled programs."""
        raise NotImplementedError

    # ------------------------------------------------- peer production
    def init_state(self, params):
        """Fresh per-peer optimizer state (e.g. error feedback)."""
        raise NotImplementedError

    def local_step(self, grads, state, batch=None):
        """(grads, state[, the consumed batch]) -> (payload, new state).

        ``batch`` is the peer's primary (assigned) batch; schemes whose
        payload layout is data-derived (e.g. rand-k index selection
        seeded from the batch content) use it, others ignore it. It is
        always the batch the peer committed on chain, so the replay
        audit reproduces the same layout from the assignment.
        """
        raise NotImplementedError

    # -------------------------------------------- validator evaluation
    def single_peer_delta(self, payload):
        """Dense signed update Δ_p for one peer's payload (Algo 1:
        θ'_p = θ − β·Sign(Δ_p)); vmapped over the stacked peer axis by
        the batched primary eval."""
        raise NotImplementedError

    def aggregate_apply(self, params, stacked, rows, lr, weights=None):
        """One fused coordinated-update step: gather ``rows`` (peer
        indices) from the stacked payloads, aggregate and apply
        θ ← θ − α·Δ. ``weights`` (len(rows),) supports static-shape
        padding: zero-weight rows must be exact ±0.0 no-ops so padded
        calls stay bit-identical to unpadded ones."""
        raise NotImplementedError

    def shared_aggregate_apply(self, params):
        """One jitted :meth:`aggregate_apply` per (cache_key, tree
        signature): the validator and every peer replica fetch the SAME
        compiled callable, so coordinated aggregation runs one program
        fleet-wide and replicas stay bit-identical by construction."""
        key = (self.cache_key(), tree_signature(params))
        fn = _AGG_JIT_CACHE.get(key)
        if fn is None:
            fn = _AGG_JIT_CACHE[key] = jax.jit(self.aggregate_apply)
        return fn

    # ------------------------------------------------------ wire format
    def payload_bytes(self, payload) -> int:
        """Wire size of one peer's payload."""
        raise NotImplementedError

    def estimate_payload_bytes(self) -> int:
        """Wire size from shape metadata alone (no payload needed) —
        the simulator resolves round-relative link specs against it."""
        raise NotImplementedError

    def format_ok(self, payload) -> bool:
        """§3.2 check (c): structure, shapes, dtypes, value sanity."""
        raise NotImplementedError

    def _value_check(self, payload):
        """Traceable value-sanity predicate: ONE boolean scalar over the
        whole payload (finite values, in-range indices, ...). Subclasses
        implement it; :meth:`_values_ok` jits + fuses it so the host
        pays one dispatch and one device sync per payload — the naive
        per-leaf ``bool(...)`` reads were 3 blocking syncs per leaf and
        dominated fast-filter wall time at large F_t."""
        raise NotImplementedError

    def _values_ok(self, payload) -> bool:
        """Host entry for :meth:`_value_check` — cached jit per scheme
        instance (payload shapes are fixed by the instance's param tree,
        so one compiled predicate serves every peer)."""
        fn = self.__dict__.get("_value_ok_jit")
        if fn is None:
            fn = self.__dict__["_value_ok_jit"] = jax.jit(self._value_check)
        return bool(fn(payload))

    # ------------------------------------------------------------ audit
    def flatten_for_sketch(self, stacked) -> List[Tuple[Any, Any]]:
        """(values, position-ids) pairs for the count-sketch
        fingerprinter: per pair, ``values`` and ``ids`` share a shape
        with leading peer axis K, and ``ids`` (uint32) identifies each
        value's position in the underlying update so identical payloads
        sketch identically. Traceable (runs inside the fingerprint jit).
        """
        raise NotImplementedError

    # --------------------------------- generic payload staging (host +
    # trace level; any pytree-of-arrays payload gets these for free)
    def stack_payloads(self, payload_trees: Sequence[Any]):
        """List of per-peer payload pytrees -> one pytree whose array
        leaves carry a leading peer axis K (the same layout
        ``jax.lax.all_gather`` produces on a mesh path)."""
        return jax.tree.map(lambda *xs: jnp.stack(xs), *payload_trees)

    def pad_payloads(self, stacked, total: int):
        """Pad the leading peer axis to ``total`` rows with zeros — a
        zero payload must evaluate to an exactly-zero update in every
        scheme (zero coefficients at position 0 do, for both shipped
        schemes), so padded rows are maskable no-ops."""
        def pad(x):
            n = x.shape[0]
            if n >= total:
                return x
            return jnp.concatenate(
                [x, jnp.zeros((total - n,) + x.shape[1:], x.dtype)])
        return jax.tree.map(pad, stacked)

    def take_payloads(self, stacked, rows):
        """Select ``rows`` along the leading peer axis (traceable — the
        validator gathers aggregation rows inside jit)."""
        rows = jnp.asarray(rows, jnp.int32)
        return jax.tree.map(lambda x: jnp.take(x, rows, axis=0), stacked)

    def payload_rows(self, stacked) -> int:
        """Leading (peer) axis length of a stacked payload tree."""
        return jax.tree.leaves(stacked)[0].shape[0]

    # ----------------------------------------------------- fabrication
    def compress(self, tree, seed: int = 0):
        """Dense params-like pytree -> a format-valid payload (benchmark
        peers fabricate payloads without running a model)."""
        raise NotImplementedError


# one compiled aggregate program per (scheme knobs, tree signature),
# process-wide — validators and peers all fetch the same callable
_AGG_JIT_CACHE: Dict[tuple, Any] = {}


# ------------------------------------------------------------- registry

SCHEMES: Dict[str, Type[GradScheme]] = {}


def register_scheme(cls: Type[GradScheme]) -> Type[GradScheme]:
    SCHEMES[cls.name] = cls
    return cls


def get_scheme(name: str) -> Type[GradScheme]:
    if name not in SCHEMES:
        raise KeyError(
            f"unknown grad scheme {name!r}; known: {sorted(SCHEMES)}")
    return SCHEMES[name]


def make_scheme(hp, params) -> GradScheme:
    """Build the scheme named by ``hp.scheme`` for this param tree."""
    return get_scheme(getattr(hp, "scheme", "demo"))(hp, params)


# populate the registry (import order matters: the classes above must
# exist before the scheme modules import them back)
from repro.schemes import demo as _demo      # noqa: E402,F401
from repro.schemes import randk as _randk    # noqa: E402,F401

__all__ = [
    "GradScheme", "SCHEMES", "register_scheme", "get_scheme",
    "make_scheme", "tree_signature",
]

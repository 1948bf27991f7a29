"""Fine-grained Mixture-of-Experts (DeepSeekMoE, arXiv:2401.06066, as
DeepSeek-V2, arXiv:2405.04434, routes it), dropless, over the experts
this chip holds.

Router (DeepSeek's gate: softmax scoring, greedy top-k), per token x_t:

    s_t = softmax(x_t W_r)          float32 logits over all E experts
    (g_t, e_t) = top_k(s_t, k)      the gates are the probabilities as
                                    they are: no renormalisation over
                                    the k (norm_topk_prob false), and a
                                    routed scaling factor of 1

Balance loss, per sequence of S tokens over all E router outputs, then
averaged over the batch (DeepSeek's ``seq_aux``):

    f_i = E / (k S) · #{(t, j) : e_tj = i}       P_i = (1/S) Σ_t s_ti
    aux = α Σ_i f_i P_i

Expert layer. The chip holds experts [o, o + n) of the E (all by
default, ``MoEConfig.experts_held`` / ``expert_offset``) and computes
their part of the result; an assignment to an absent expert adds
nothing here:

    y_t = Σ_{j : e_tj held} g_tj · FFN_{e_tj}(x_t)  +  FFN_shared(x_t)
    FFN_e(x) = (silu(x G_e) ⊙ (x U_e)) D_e

Nothing is dropped. In each token group (``num_groups``, the data
shards inside one peer) the T·k assignments are sorted by expert, the
held ones first and grouped; the rows gather their tokens; each matrix
is one grouped product (``grouped_matmul``) over the n held experts;
the gate-weighted rows are summed back per token. T·k rows is the
static bound (every assignment on a held expert); rows no held
assignment fills lie past the last group and cost no matmul work.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro import hints
from repro.models import layers
from repro.obs import trace

HIGHEST = jax.lax.Precision.HIGHEST


def init_moe(key, cfg):
    m, d = cfg.moe, cfg.d_model
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    n, f = m.held, m.expert_d_ff
    scale = 1.0 / jnp.sqrt(d)

    def expert_bank(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "gate": (scale * jax.random.normal(k1, (n, d, f))).astype(dtype),
            "up": (scale * jax.random.normal(k2, (n, d, f))).astype(dtype),
            "down": ((1.0 / jnp.sqrt(f)) * jax.random.normal(k3, (n, f, d))).astype(dtype),
        }

    p = {"router": layers.init_linear(ks[0], d, m.num_experts, dtype,
                                      scale=0.02),
         "experts": expert_bank(ks[1])}
    if m.num_shared_experts:
        p["shared"] = layers.init_swiglu(ks[2], d,
                                         m.num_shared_experts * f, dtype)
    return p


def route(router_p, x, m) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (..., S, d) -> gates (..., S, k) float32, expert ids
    (..., S, k), the balance loss (α included)."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        router_p["w"].astype(jnp.float32), precision=HIGHEST)
    if "b" in router_p:
        logits = logits + router_p["b"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, m.top_k)
    E, S = probs.shape[-1], probs.shape[-2]
    picks = jnp.sum(jax.nn.one_hot(eidx, E, dtype=jnp.float32), axis=(-3, -2))
    f = picks * (E / (m.top_k * S))                      # (..., E)
    aux = jnp.mean(jnp.sum(f * jnp.mean(probs, axis=-2), axis=-1))
    return gates, eidx, aux * m.router_aux_coef


# Moving rows between token order and expert order. ``order`` sorts the
# T·k assignments (row r of assignment order is token r // k), ``inv``
# is its inverse: each move is a gather, and so is its transpose, which
# XLA would otherwise make a scatter-add.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _to_expert_order(x, order, inv, k):
    """(T, d) tokens -> (T·k, d) rows, in expert order."""
    return x[order // k]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _to_token_order(y, order, inv, k):
    """(T·k, d) rows in expert order -> (T, d): each token's k rows
    summed (in float32)."""
    rows = y[inv].reshape(-1, k, y.shape[-1])
    return jnp.sum(rows, axis=1, dtype=jnp.float32).astype(y.dtype)


def _to_expert_order_fwd(x, order, inv, k):
    return _to_expert_order(x, order, inv, k), (order, inv)


def _to_expert_order_bwd(k, res, g):
    order, inv = res
    return _to_token_order(g, order, inv, k), None, None


def _to_token_order_fwd(y, order, inv, k):
    return _to_token_order(y, order, inv, k), (order, inv)


def _to_token_order_bwd(k, res, g):
    order, inv = res
    return _to_expert_order(g, order, inv, k), None, None


_to_expert_order.defvjp(_to_expert_order_fwd, _to_expert_order_bwd)
_to_token_order.defvjp(_to_token_order_fwd, _to_token_order_bwd)


# The grouped product is ``lax.ragged_dot``, which XLA compiles for a
# TPU into a Mosaic kernel that skips the row tiles past the last group
# and partitions like any other op (megablox's Pallas ``gmm`` ran the
# expert FFN 1.49x faster alone, but as a Mosaic kernel it needs every
# mesh axis manual inside the model; PERF.md). That kernel leaves the
# rows past the last group unwritten, so they hold whatever the buffer
# held, NaN included, and its weight gradient may read them times a
# zero: ``_held_part`` selects zero there before any arithmetic, in the
# elementwise work around the products. ``ragged_dot`` batches only
# where every operand is batched: under a vmap (the validator's replay
# audit maps the peer's step over peers that share the parameters) each
# product runs once per batch row.

def _per_row(f):
    g = jax.custom_batching.custom_vmap(f)

    @g.def_vmap
    def rule(axis_size, in_batched, *args):
        def one(i):
            return g(*(a[i] if b else a for a, b in zip(args, in_batched)))
        return jax.lax.map(one, jnp.arange(axis_size)), True

    return g


def _ragged_vjp(x, w, sizes, g):
    return jax.vjp(lambda x, w: jax.lax.ragged_dot(x, w, sizes), x, w)[1](g)


_product = _per_row(jax.lax.ragged_dot)
_product_dx = _per_row(lambda x, w, sizes, g: _ragged_vjp(x, w, sizes, g)[0])
_product_dw = _per_row(lambda x, w, sizes, g: _ragged_vjp(x, w, sizes, g)[1])


@jax.custom_vjp
def grouped_matmul(x, w, sizes):
    """(rows, d) x (groups, d, f) -> (rows, f): row block g, of
    ``sizes[g]`` rows in order, times w[g]; rows past the last block,
    and their input gradient, hold whatever the product leaves there."""
    return _product(x, w, sizes)


def _grouped_matmul_fwd(x, w, sizes):
    return _product(x, w, sizes), (x, w, sizes)


def _grouped_matmul_bwd(res, g):
    x, w, sizes = res
    return _product_dx(x, w, sizes, g), _product_dw(x, w, sizes, g), None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


# Row blocks longer than this run as slices (PERF.md §6: on a v5e the
# step over 98,304 rows in one product read non-finite where slices of
# 49,152 did not; that was before the rows past the last group were
# zeroed, and is not settled).
ROW_SLICE = 49152


def sliced_matmul(x, w, sizes):
    """``grouped_matmul`` over slices of at most ``ROW_SLICE`` rows, each
    with the part of every group that falls in it."""
    R = x.shape[0]
    if R <= ROW_SLICE:
        return grouped_matmul(x, w, sizes)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    outs = []
    for lo in range(0, R, ROW_SLICE):
        hi = min(lo + ROW_SLICE, R)
        part = jnp.clip(ends, lo, hi) - jnp.clip(starts, lo, hi)
        outs.append(grouped_matmul(x[lo:hi], w, part))
    return jnp.concatenate(outs)


def _held_part(experts, x, gates, eidx, m):
    """One group's dispatch, products and combine. x: (..., d) tokens,
    gates and eidx: (..., k) -> (T, d) the held experts' part, for the
    T tokens in order, and the assignments on each held expert (n,)."""
    d = x.shape[-1]
    k, n = m.top_k, m.held
    Tk = eidx.size
    with jax.named_scope(trace.BLOCK_MOE_DISPATCH):
        local = eidx.reshape(Tk) - m.expert_offset
        key = jnp.where((local >= 0) & (local < n), local, n)  # n: absent
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(Tk, dtype=jnp.int32))
        sizes = jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)
        filled = (jnp.arange(Tk) < jnp.sum(sizes))[:, None]
        rows = jnp.where(filled, _to_expert_order(x.reshape(-1, d), order,
                                                  inv, k), 0)
    with jax.named_scope(trace.BLOCK_MOE_EXPERTS):
        w = jax.tree.map(lambda a: a.astype(x.dtype), experts)
        # each product zero past the filled rows, and by the selects'
        # transposes so is every gradient that reaches a product
        def product(a, name):
            return jnp.where(filled, sliced_matmul(a, w[name], sizes), 0)
        h, u = product(rows, "gate"), product(rows, "up")
        out = product(jax.nn.silu(h) * u, "down")
    with jax.named_scope(trace.BLOCK_MOE_COMBINE):
        g = gates.reshape(Tk)[order].astype(x.dtype)[:, None]
        out = out * g
        y = _to_token_order(out, order, inv, k)
    return y, sizes


def moe_ffn(p, x, cfg, num_groups: int = 1):
    """x: (B, S, d) -> (B, S, d), the balance loss, and counts (2,)
    int32: the assignments that fell on held experts, and the most
    that fell on one held expert.

    The tokens split along the batch into ``num_groups`` groups (the
    data shards inside one peer), each dispatched, multiplied and
    summed back on its own (``hints.per_group_shard``: on the device
    that holds it), so a data-sharded layer moves no token between
    chips and sorts one shard's assignments, not the batch's."""
    m = cfg.moe
    B, S, d = x.shape
    T, k, G = B * S, m.top_k, num_groups
    assert T % G == 0, (T, G)
    with jax.named_scope(trace.BLOCK_MOE_ROUTE):
        gates, eidx, aux = route(p["router"], x, m)           # (B, S, k)
    if G == 1:
        y, sizes = _held_part(p["experts"], x, gates, eidx, m)
    else:
        part = jax.vmap(functools.partial(_held_part, m=m),
                        in_axes=(None, 0, 0, 0))
        y, sizes = hints.per_group_shard(
            part, p["experts"], x.reshape(G, T // G, d),
            gates.reshape(G, T // G, k), eidx.reshape(G, T // G, k))
        sizes = jnp.sum(sizes, axis=0)
    y = y.reshape(B, S, d)
    if "shared" in p:
        with jax.named_scope(trace.BLOCK_MOE_SHARED):
            y = y + layers.swiglu(p["shared"], x)
    return y, aux, jnp.stack([jnp.sum(sizes), jnp.max(sizes)])

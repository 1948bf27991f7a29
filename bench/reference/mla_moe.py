"""Plain reference of a DeepSeek-V2 decoder (latent attention with YaRN
rope, one dense layer, then fine-grained expert layers) and of one DeMo
training step, written from the published description and importing
nothing of the program under test. The DeMo codec, the schedule and the
readings' helpers are ``dense.py``'s.

Model, per layer (pre-norm, RMSNorm with a gain):

  attention (MLA, no query low rank), H heads:
    [q_nope, q_rope] = x W_q                 per head 128 + 64 wide
    [c, k_rope] = x W_kv_a;  c <- RMSNorm(c)  512 + 64 wide
    [k_nope, v] = c W_kv_b                   per head 128 + 128 wide
    q_rope, k_rope rotated at positions 0..s-1 with YaRN's frequencies
    (k_rope is one head, shared by all);
    o = softmax(scale (q_nope.k_nope + q_rope.k_rope), causal) v;  out = o W_o
    scale = 192^-1/2 * mscale^2,  mscale = 0.1 * mscale_all_dim * ln(factor) + 1
  YaRN: f_extra = theta^(-2i/64), f_inter = f_extra / factor,
    inv_freq = f_inter (1 - m) + f_extra m with m = 1 - ramp(low, high), the
    linear ramp over pairs between the correction dims of beta_fast and
    beta_slow over the pre-training length; cos and sin are multiplied
    by mscale(mscale) / mscale(mscale_all_dim).
  layer 0: SwiGLU, down(silu(gate x) * up x), intermediate_size wide.
  expert layers: router probabilities s = softmax(x W_r) over all
    ``router_outputs`` experts, in float32; the top ``num_experts_per_tok``
    by probability, each weighted by its probability (no renormalisation,
    routed scaling 1). Each held expert (the ``n_routed_experts`` from
    ``expert_offset``) is computed densely on every token and multiplied
    by its routing weight, which is zero where the token did not pick
    it; an expert not held here adds nothing. The shared experts are one
    SwiGLU of n_shared_experts x moe_intermediate_size. Balance loss per
    sequence of S tokens, E experts, k picks: aux_loss_alpha * sum_i f_i
    P_i, f_i = E/(k S) * picks of i, P_i = mean over the sequence of s_i;
    averaged over the sequences and added to the loss.
  loss: mean next-token cross-entropy over ``logit_rows`` classes, plus
    the expert layers' balance losses.

Departures from the published model: the rope dims are rotated as two
halves, where DeepSeek rotates interleaved pairs (the same map after a
fixed permutation of the rope columns of W_q and W_kv_a); the vocabulary
and the experts are the configuration's slice.

Parameters: ``dense`` holds layer 0; ``moe_layers`` the expert layers,
each leaf with a leading layer axis, which the DeMo codec sees whole (as
a program that scans over stacked layers holds it). ``mode`` and
``flip_update`` are ``dense.py``'s. The gradient is computed in blocks
of one row, so that it fits on a chip after the program's window.
"""
from __future__ import annotations

import functools
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import dense  # noqa: E402

# ------------------------------------------------------------- model


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn(c: dict):
    """(inverse frequencies of the rope pairs, gain of cos and sin,
    softmax scale) for config file ``c``."""
    dim, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    y = c["rope_scaling"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    if y is None:
        return extra.astype(np.float32), 1.0, scale
    factor, orig = float(y["factor"]), y["original_max_position_embeddings"]

    def corr_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(y["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    inv = extra / factor * (1.0 - mask) + extra * mask
    gain = (_yarn_mscale(factor, y["mscale"])
            / _yarn_mscale(factor, y["mscale_all_dim"]))
    scale *= _yarn_mscale(factor, y["mscale_all_dim"]) ** 2
    return inv.astype(np.float32), gain, scale


def _rope(x, inv, gain):
    """x (b, s, heads, dim): rotate-half rotary embedding at 0..s-1."""
    s, half = x.shape[1], x.shape[-1] // 2
    ang = np.arange(s, dtype=np.float32)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang) * gain, jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang) * gain, jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla(c, mm, x, a):
    b, s, _ = x.shape
    H, dn, dr = (c["num_attention_heads"], c["qk_nope_head_dim"],
                 c["qk_rope_head_dim"])
    dv, r = c["v_head_dim"], c["kv_lora_rank"]
    inv, gain, scale = yarn(c)
    q = dense._linear(mm, a["wq_b"], x).reshape(b, s, H, dn + dr)
    kv = dense._linear(mm, a["wkv_a"], x)
    lat = dense._rmsnorm(kv[..., :r], a["kv_norm"]["g"], c["rms_norm_eps"])
    q_rope = _rope(q[..., dn:], inv, gain)
    k_rope = _rope(kv[..., None, r:], inv, gain)[:, :, 0]
    kvb = dense._linear(mm, a["wkv_b"], lat).reshape(b, s, H, dn + dv)
    scores = (mm("bqhd,bkhd->bhqk", q[..., :dn], kvb[..., :dn])
              + mm("bqhd,bkd->bhqk", q_rope, k_rope)) * scale
    causal = np.tril(np.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf),
                           axis=-1)
    o = mm("bhqk,bkhd->bqhd", probs, kvb[..., dn:]).reshape(b, s, H * dv)
    return dense._linear(mm, a["wo"], o)


def _swiglu(mm, m, x):
    ff = (jax.nn.silu(dense._linear(mm, m["gate"], x))
          * dense._linear(mm, m["up"], x))
    return dense._linear(mm, m["down"], ff)


def _route(c, mm, x, router):
    """(routing weight over all experts (b, s, E), the balance loss)."""
    E, k = c["router_outputs"], c["num_experts_per_tok"]
    probs = jax.nn.softmax(mm("bsd,de->bse", x, router["w"]), axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    picked = jax.nn.one_hot(idx, E, dtype=jnp.float32)       # (b, s, k, E)
    weight = jnp.sum(picked * gates[..., None], axis=2)
    f = jnp.sum(picked, axis=(1, 2)) * E / (k * x.shape[1])
    aux = jnp.mean(jnp.sum(f * jnp.mean(probs, axis=1), axis=-1))
    return weight, c["aux_loss_alpha"] * aux


def _experts(c, mm, x, p):
    """(the layer's output, its balance loss)."""
    weight, aux = _route(c, mm, x, p["router"])
    off, n = c["expert_offset"], c["n_routed_experts"]
    held = weight[..., off:off + n]                           # (b, s, n)
    e = p["experts"]
    h = (jax.nn.silu(mm("bsd,edf->bsef", x, e["gate"]))
         * mm("bsd,edf->bsef", x, e["up"]))
    y = mm("bsef,efd->bsd", h * held[..., None], e["down"])
    return y + _swiglu(mm, p["shared"], x), aux


def _block(c, mm, x, p, ffn):
    x = x + _mla(c, mm, dense._rmsnorm(x, p["norm1"]["g"],
                                       c["rms_norm_eps"]), p["attn"])
    y, extra = ffn(dense._rmsnorm(x, p["norm2"]["g"], c["rms_norm_eps"]))
    return x + y, extra


def loss(c, mm, params, tokens, labels):
    """Mean next-token cross-entropy of a block of rows, plus the expert
    layers' balance losses."""
    x = params["embed"]["w"][tokens].astype(jnp.float32)
    x = jax.checkpoint(lambda x, p: _block(
        c, mm, x, p, lambda h: (_swiglu(mm, p["mlp"], h), None))[0])(
            x, params["dense"])

    @jax.checkpoint
    def body(x, p):
        return _block(c, mm, x, p, lambda h: _experts(c, mm, h, p["moe"]))

    x, aux = jax.lax.scan(body, x, params["moe_layers"])
    x = dense._rmsnorm(x, params["final_norm"]["g"], c["rms_norm_eps"])
    logits = mm("bsd,dv->bsv", x, params["lm_head"]["w"])
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - gold) + jnp.sum(aux)


# -------------------------------------------------------------- DeMo


@functools.lru_cache(maxsize=None)
def _step_fns(c_json: str, h_json: str, mode: str, flip_update: bool):
    """``grad(params, tokens, labels)`` over blocks of one row, the loss
    and gradient the mean over the rows; ``update(params, ef, grads,
    lr)``, the DeMo step on every tensor whole."""
    c, h = json.loads(c_json), json.loads(h_json)
    mm = dense.matmul(mode)
    leaf = functools.partial(dense.demo_leaf, beta=h["demo_beta"],
                             chunk=h["demo_chunk"], topk=h["demo_topk"])
    sign = 1.0 if flip_update else -1.0
    pair = lambda o: isinstance(o, tuple)  # noqa: E731

    def grad(params, tokens, labels):
        def acc(carry, row):
            l_sum, g_sum = carry
            tok, lab = row
            l, g = jax.value_and_grad(
                lambda p: loss(c, mm, p, tok[None], lab[None]))(params)
            return (l_sum + l, jax.tree.map(jnp.add, g_sum, g)), None

        zeros = jax.tree.map(jnp.zeros_like, params)
        (l_sum, g_sum), _ = jax.lax.scan(acc, (jnp.float32(0), zeros),
                                         (tokens, labels))
        n = tokens.shape[0]
        return l_sum / n, jax.tree.map(lambda g: g / n, g_sum)

    def update(params, ef, grads, lr):
        out = jax.tree.map(leaf, ef, grads)
        new_e = jax.tree.map(lambda o: o[0], out, is_leaf=pair)
        new_p = jax.tree.map(
            lambda p, o: p * (1 - lr * h["weight_decay"]) + sign * lr * o[1],
            params, out, is_leaf=pair)
        return new_p, new_e

    return jax.jit(grad), jax.jit(update, donate_argnums=(0, 1))


@jax.jit
def _norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree)


@jax.jit
def _change(a, b):
    return _norms(jax.tree.map(jnp.subtract, a, b))


def train_readings(c: dict, h: dict, params, batches, *, mode: str,
                   initial, flip_update: bool = False, stacked: bool = True):
    """``dense.train_readings`` for this model: ``len(batches)`` DeMo
    steps from ``params`` (consumed), and what the check compares. Every
    tensor is compressed whole (``stacked`` must be true)."""
    if not stacked:
        raise ValueError("the expert layers are held stacked")
    grad, update = _step_fns(json.dumps(c, sort_keys=True),
                             json.dumps(h, sort_keys=True), mode,
                             flip_update)
    ef = jax.tree.map(jnp.zeros_like, params)
    losses, out = [], {}
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches):
            l, grads = grad(params, b["tokens"], b["labels"])
            if i == 0:
                out["grad1"] = dense.named(_norms(grads))
            params, ef = update(params, ef, grads,
                                jnp.float32(dense.lr_at(i, h)))
            del grads
            losses.append(float(l))
            if i == 0:
                out["ef1"] = dense.named(_norms(ef))
                out["ef1_vec"] = dense._host(ef, True)
        del ef
        start = initial()
        out["change"] = dense.named(_change(params, start))
        out["change_vec"] = dense._host(jax.tree.map(
            lambda a, b: np.asarray(a - b), params, start), True)
    out["losses"] = losses
    return out

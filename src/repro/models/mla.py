"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

Per token x (d) and head h, with q_lora_rank 0 (DeepSeek-V2-Lite) or a
normed low-rank query otherwise:

    [q_nope_h, q_rope_h] = (x W_q)_h                  128 + 64 wide
    [c, k_rope] = x W_kv_a;   c <- RMSNorm(c)          512 + 64 wide
    [k_nope_h, v_h] = (c W_kv_b)_h                     128 + 128 wide
    q_rope, k_rope <- rope(·, positions)               k_rope shared by
                                                       every head
    o_h = softmax(scale·(q_nope_h·k_nope_h + q_rope_h·k_rope), causal) v_h
    out = [o_1 .. o_H] W_o

scale = (nope + rope)^(-1/2), times yarn_mscale(factor,
mscale_all_dim)^2 where the config sets YaRN rope scaling (cos and sin
then take ``layers.apply_rope``'s gain, 1 for DeepSeek-V2). Departure:
DeepSeek rotates interleaved pairs of the rope dims; this rotates the
two halves, which is the same map after a fixed permutation of the rope
columns of W_q and W_kv_a (on random weights, the same model).

Train/prefill: naive (expand latent to per-head K/V).
Decode: *absorbed* form — W_uk is folded into the query and W_uv into the
output so each step attends directly over the (S, r) latent cache plus the
shared rope key. This is the TPU-native adaptation: the per-step work is a
handful of MXU matmuls against a compact latent cache instead of
re-expanding full K/V (which would cost O(S·r·H·hd) per token).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import hints
from repro.models import layers
from repro.obs import trace

NEG_INF = -1e30


class MLACache(NamedTuple):
    c_kv: jnp.ndarray       # (B, S, r) compressed latent (post-norm)
    k_rope: jnp.ndarray     # (B, S, rope_dim) shared rotated rope key
    pos: jnp.ndarray


def init_mla(key, cfg):
    m, d, H = cfg.mla, cfg.d_model, cfg.num_heads
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    p = {}
    if m.q_lora_rank:
        p["wq_a"] = layers.init_linear(ks[0], d, m.q_lora_rank, dtype)
        p["q_norm"] = layers.init_rmsnorm(m.q_lora_rank, dtype)
        q_in = m.q_lora_rank
    else:
        q_in = d
    p["wq_b"] = layers.init_linear(ks[1], q_in,
                                   H * (m.qk_nope_head_dim + m.qk_rope_head_dim),
                                   dtype)
    p["wkv_a"] = layers.init_linear(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim,
                                    dtype)
    p["kv_norm"] = layers.init_rmsnorm(m.kv_lora_rank, dtype)
    p["wkv_b"] = layers.init_linear(ks[3], m.kv_lora_rank,
                                    H * (m.qk_nope_head_dim + m.v_head_dim),
                                    dtype)
    p["wo"] = layers.init_linear(ks[4], H * m.v_head_dim, d, dtype)
    return p


def _project_q(p, x, cfg, positions):
    m, H = cfg.mla, cfg.num_heads
    if cfg.mla.q_lora_rank:
        q_in = layers.rmsnorm(p["q_norm"], layers.linear(p["wq_a"], x),
                              cfg.norm_eps)
    else:
        q_in = x
    q = layers.linear(p["wq_b"], q_in)
    q = q.reshape(*x.shape[:-1], H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = layers.apply_rope(q_rope, positions, cfg.rope_theta,
                               cfg.rope_scaling)
    return q_nope, q_rope


def _latent_kv(p, x, cfg, positions):
    m = cfg.mla
    kv = layers.linear(p["wkv_a"], x)
    c_kv, k_rope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    c_kv = layers.rmsnorm(p["kv_norm"], c_kv, cfg.norm_eps)
    # shared single-head rope key, rotated at absolute positions
    k_rope = layers.apply_rope(k_rope[..., None, :], positions,
                               cfg.rope_theta, cfg.rope_scaling)[..., 0, :]
    return c_kv, k_rope


def softmax_scale(cfg) -> float:
    """(nope + rope)^(-1/2), times YaRN's mscale(mscale_all_dim)^2."""
    m, y = cfg.mla, cfg.rope_scaling
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if y is not None and y.mscale_all_dim:
        scale *= layers.yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def attend_full(p, x, cfg, q_block: int = 512):
    """Naive expanded MLA for train/prefill, q-row-blocked (the fp32 score
    buffer is (B,H,q_block,S), jax.checkpoint'ed per block). x: (B,S,d)."""
    with jax.named_scope(trace.BLOCK_MLA):
        return _attend_full(p, x, cfg, q_block)


def _attend_full(p, x, cfg, q_block: int):
    B, S, _ = x.shape
    m, H = cfg.mla, cfg.num_heads
    positions = jnp.arange(S)[None, :]
    q_nope, q_rope = _project_q(p, x, cfg, positions)      # (B,S,H,*)
    c_kv, k_rope = _latent_kv(p, x, cfg, positions)        # (B,S,r),(B,S,rd)
    kvb = layers.linear(p["wkv_b"], c_kv)
    kvb = kvb.reshape(B, S, H, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kvb, [m.qk_nope_head_dim], axis=-1)
    q_nope, q_rope, k_nope, v = map(hints.constrain_heads,
                                    (q_nope, q_rope, k_nope, v))
    scale = softmax_scale(cfg)

    def block(qn, qr, offset):
        scores = (jnp.einsum("bqhd,bkhd->bhqk", qn, k_nope)
                  + jnp.einsum("bqhd,bkd->bhqk", qr, k_rope))
        scores = scores.astype(jnp.float32) * scale
        qpos = jnp.arange(qn.shape[1])[:, None] + offset
        mask = (jnp.arange(S)[None, :] <= qpos)[None, None]
        scores = jnp.where(mask, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    if S <= 1024 or S % q_block:
        out = block(q_nope, q_rope, 0)
    else:
        nq = S // q_block
        qn = q_nope.reshape(B, nq, q_block, H, -1).transpose(1, 0, 2, 3, 4)
        qr = q_rope.reshape(B, nq, q_block, H, -1).transpose(1, 0, 2, 3, 4)

        @jax.checkpoint
        def body(carry, inp):
            qni, qri, i = inp
            return carry, block(qni, qri, i * q_block)

        _, outs = jax.lax.scan(body, (), (qn, qr, jnp.arange(nq)))
        out = outs.transpose(1, 0, 2, 3, 4).reshape(B, S, H, m.v_head_dim)
    return layers.linear(p["wo"], out.reshape(B, S, H * m.v_head_dim))


def init_mla_cache(cfg, batch: int, seq_len: int, dtype) -> MLACache:
    m = cfg.mla
    return MLACache(
        c_kv=jnp.zeros((batch, seq_len, m.kv_lora_rank), dtype),
        k_rope=jnp.zeros((batch, seq_len, m.qk_rope_head_dim), dtype),
        pos=jnp.zeros((), jnp.int32))


def attend_decode(p, x, cache: MLACache, cfg):
    """Absorbed-matrix MLA decode. x: (B,1,d)."""
    B, S1, _ = x.shape
    m, H = cfg.mla, cfg.num_heads
    pos = cache.pos
    positions = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_rope = _project_q(p, x, cfg, positions)      # (B,1,H,*)
    c_new, kr_new = _latent_kv(p, x, cfg, positions)       # (B,1,r),(B,1,rd)
    c_kv = jax.lax.dynamic_update_slice(cache.c_kv,
                                        c_new.astype(cache.c_kv.dtype),
                                        (0, pos, 0))
    k_rope = jax.lax.dynamic_update_slice(cache.k_rope,
                                          kr_new.astype(cache.k_rope.dtype),
                                          (0, pos, 0))
    # absorb W_uk into q:  q_lat[h] = q_nope[h] @ W_uk[h]^T : (B,1,H,r)
    W = p["wkv_b"]["w"].astype(x.dtype)                    # (r, H*(nope+v))
    Wk = W.reshape(m.kv_lora_rank, H, m.qk_nope_head_dim + m.v_head_dim)
    W_uk = Wk[..., :m.qk_nope_head_dim]                    # (r,H,nope)
    W_uv = Wk[..., m.qk_nope_head_dim:]                    # (r,H,v)
    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, W_uk)
    scale = softmax_scale(cfg)
    ckv = c_kv.astype(x.dtype)
    scores = (jnp.einsum("bqhr,bkr->bhqk", q_lat, ckv)
              + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope.astype(x.dtype)))
    scores = scores.astype(jnp.float32) * scale
    valid = (jnp.arange(c_kv.shape[1]) <= pos)[None, None, None, :]
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out_lat = jnp.einsum("bhqk,bkr->bqhr", probs, ckv)     # (B,1,H,r)
    out = jnp.einsum("bqhr,rhd->bqhd", out_lat, W_uv)      # absorb W_uv
    out = layers.linear(p["wo"], out.reshape(B, S1, H * m.v_head_dim))
    return out, MLACache(c_kv=c_kv, k_rope=k_rope, pos=pos + 1)

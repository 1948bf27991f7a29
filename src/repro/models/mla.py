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

Train/prefill: naive (expand latent to per-head K/V). Compiled for a
TPU, at a length the kernel's blocks divide, the causal core is the
Pallas splash kernel: flash attention in VMEM with float32 scores and
statistics, which skips the blocks above the diagonal; elsewhere it is
an XLA softmax over 512-row query blocks of the whole square.
Decode: *absorbed* form — W_uk is folded into the query and W_uv into the
output so each step attends directly over the (S, r) latent cache plus the
shared rope key. This is the TPU-native adaptation: the per-step work is a
handful of MXU matmuls against a compact latent cache instead of
re-expanding full K/V (which would cost O(S·r·H·hd) per token).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from repro import hints
from repro.models import layers
from repro.obs import trace

NEG_INF = -1e30

# The splash kernel's blocks. One v5e, the causal core alone at the
# benchmark cell's micro-batch (4 x 4096, 16 heads, qk 192, v 128):
# forward 4.94 ms at 512 everywhere, 4.40 at these; forward and backward
# 18.64 ms at 512 with separate dq and dkv kernels, 15.00 with the fused
# backward at 1024 (the XLA core: 21.78 and 63.79 ms).
BLOCK = 1024
BLOCKS = splash.BlockSizes(
    block_q=BLOCK, block_kv=BLOCK, block_kv_compute=512,
    block_q_dkv=BLOCK, block_kv_dkv=BLOCK, block_kv_dkv_compute=BLOCK,
    use_fused_bwd_kernel=True)


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
    """Naive expanded MLA for train/prefill. x: (B,S,d). Compiled for a
    TPU with S a multiple of ``BLOCK``, the causal core is the splash
    kernel (``_attend_in_vmem``); otherwise it is q-row-blocked (the fp32
    score buffer is (B,H,q_block,S), jax.checkpoint'ed per block)."""
    with jax.named_scope(trace.BLOCK_MLA):
        return _attend_full(p, x, cfg, q_block)


def _project_heads(p, x, cfg):
    """(q_nope, q_rope, k_nope, k_rope, v) of x (B,S,d): (B,S,H,*) each,
    but the one shared rope key k_rope (B,S,rope)."""
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
    return q_nope, q_rope, k_nope, k_rope, v


def _attend_full(p, x, cfg, q_block: int):
    B, S, _ = x.shape
    heads = _project_heads(p, x, cfg)
    scale = softmax_scale(cfg)
    blocked = functools.partial(_attend_blocked, scale=scale, q_block=q_block)
    if S % BLOCK:
        out = blocked(*heads)
    else:
        out = jax.lax.platform_dependent(
            *heads, default=blocked,
            tpu=functools.partial(_attend_in_vmem, scale=scale,
                                  interpret=False))
    return layers.linear(p["wo"], out.reshape(B, S, -1))


def _attend_blocked(q_nope, q_rope, k_nope, k_rope, v, *, scale: float,
                    q_block: int):
    """The causal core in XLA: (B,S,H,v) from ``_project_heads``."""
    B, S, H, _ = q_nope.shape

    def block(qn, qr, offset):
        scores = (jnp.einsum("bqhd,bkhd->bhqk", qn, k_nope)
                  + jnp.einsum("bqhd,bkd->bhqk", qr, k_rope))
        scores = scores.astype(jnp.float32) * scale
        qpos = jnp.arange(qn.shape[1])[:, None] + offset
        mask = (jnp.arange(S)[None, :] <= qpos)[None, None]
        scores = jnp.where(mask, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    if S <= 1024 or S % q_block:
        return block(q_nope, q_rope, 0)
    nq = S // q_block
    qn = q_nope.reshape(B, nq, q_block, H, -1).transpose(1, 0, 2, 3, 4)
    qr = q_rope.reshape(B, nq, q_block, H, -1).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def body(carry, inp):
        qni, qri, i = inp
        return carry, block(qni, qri, i * q_block)

    _, outs = jax.lax.scan(body, (), (qn, qr, jnp.arange(nq)))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, S, H, v.shape[-1])


def _attend_in_vmem(q_nope, q_rope, k_nope, k_rope, v, *, scale: float,
                    interpret: bool):
    """The causal core through the splash kernel: q = scale·[q_nope,
    q_rope] (scaled in float32, rounded once), k = [k_nope, k_rope] with
    the shared rope key on every head, per example (H,S,D). The kernel
    keeps no score buffer in HBM, so nothing here is checkpointed: its
    backward saves q, k, v, the output and the log-sum-exp."""
    B, S, H, _ = q_nope.shape
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    q = (q.astype(jnp.float32) * scale).astype(q_nope.dtype)
    k_rope = jnp.broadcast_to(k_rope[:, :, None, :],
                              (B, S, H, k_rope.shape[-1]))
    k = jnp.concatenate([k_nope, k_rope], axis=-1)

    def core(q, k, v):
        # splash caches the mask's block info by mask and block shape, so
        # a second trace (the layer's remat) does not build it again
        h, s = q.shape[1:3]
        mask = splash.MultiHeadMask([splash.CausalMask((s, s))] * h)
        kernel = splash.make_splash_mha(mask, block_sizes=BLOCKS,
                                        head_shards=1, q_seq_shards=1,
                                        interpret=interpret)
        return jax.vmap(kernel)(q, k, v)

    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    return hints.per_head_shard(core, q, k, v).transpose(0, 2, 1, 3)


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

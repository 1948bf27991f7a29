"""Shared neural-net layers (pure functional JAX, no flax).

Params are plain dict pytrees. Initializers take an explicit PRNG key and
return arrays in ``cfg.param_dtype``; compute casts to ``cfg.dtype``.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# ----------------------------------------------------------------- init


def _normal(key, shape, scale, dtype):
    return (scale * jax.random.normal(key, shape, dtype=jnp.float32)).astype(dtype)


def init_linear(key, d_in, d_out, dtype, bias=False, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": _normal(key, (d_in, d_out), scale, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def init_embedding(key, vocab, d, dtype):
    return {"w": _normal(key, (vocab, d), 0.02, dtype)}


def init_rmsnorm(d, dtype):
    return {"g": jnp.ones((d,), dtype)}


# ----------------------------------------------------------------- apply


def linear(p, x):
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def embed(p, tokens, dtype):
    return p["w"].astype(dtype)[tokens]


def rmsnorm(p, x, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * p["g"].astype(jnp.float32)).astype(dt)


def swiglu(p, x):
    return linear(p["down"], jax.nn.silu(linear(p["gate"], x)) * linear(p["up"], x))


def init_swiglu(key, d, d_ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"gate": init_linear(k1, d, d_ff, dtype),
            "up": init_linear(k2, d, d_ff, dtype),
            "down": init_linear(k3, d_ff, d, dtype)}


# ----------------------------------------------------------------- rope


def rope_freqs(head_dim: int, theta: float, scaling=None) -> jnp.ndarray:
    """Rotary inverse frequencies; YaRN's where ``scaling`` (a
    ``configs.base.YarnConfig``) is given."""
    if scaling is not None:
        return jnp.asarray(yarn_inv_freq(head_dim, theta, scaling))
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction: 0.1·mscale·ln(factor) + 1 (1 where
    the positions are not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, y) -> np.ndarray:
    """YaRN's inverse frequencies (DeepSeek-V2's form), for ``dim``
    rotated dimensions:

        f_extra = θ^(−2i/dim)            the pre-trained frequencies
        f_inter = f_extra / factor       stretched over factor × positions
        inv_freq = f_inter·(1 − m) + f_extra·m,   m = 1 − ramp(low, high)

    ramp rises linearly from 0 at pair ``low`` to 1 at pair ``high``
    (clipped); [low, high] is the band of pairs whose wavelengths make
    between ``beta_fast`` and ``beta_slow`` turns over the
    ``original_max_position`` pre-trained positions:

        d(r) = dim·ln(L / (2π r)) / (2 ln θ)
        low = max(⌊d(beta_fast)⌋, 0),  high = min(⌈d(beta_slow)⌉, dim − 1)
    """
    def turns(r):
        return (dim * math.log(y.original_max_position / (r * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns(y.beta_fast)), 0)
    high = min(math.ceil(turns(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    m = 1.0 - ramp
    return (extra / y.factor * (1.0 - m) + extra * m).astype(np.float32)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               scaling=None) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq). With YaRN
    ``scaling``, cos and sin are also multiplied by
    yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta, scaling)               # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    if scaling is not None:
        gain = (yarn_mscale(scaling.factor, scaling.mscale)
                / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if gain != 1.0:
            cos, sin = cos * gain, sin * gain
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- loss


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean token cross-entropy in fp32. logits (..., V), labels (...)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def chunked_cross_entropy(x: jnp.ndarray, emb_w: jnp.ndarray,
                          labels: jnp.ndarray, mask: Optional[jnp.ndarray],
                          num_chunks: int) -> jnp.ndarray:
    """CE without materializing full (T, V) logits: scan over seq chunks.

    x: (B, S, d) final hidden states; emb_w: (V, d) output embedding.
    Cuts the logits working set by num_chunks — the beyond-paper memory
    optimization used by the perf pass for large-vocab archs.
    """
    B, S, d = x.shape
    assert S % num_chunks == 0, (S, num_chunks)
    cs = S // num_chunks
    xs = x.reshape(B, num_chunks, cs, d).swapaxes(0, 1)        # (n, B, cs, d)
    ls = labels.reshape(B, num_chunks, cs).swapaxes(0, 1)
    ms = (mask.reshape(B, num_chunks, cs).swapaxes(0, 1).astype(jnp.float32)
          if mask is not None else jnp.ones((num_chunks, B, cs), jnp.float32))

    def body(carry, inp):
        xc, lc, mc = inp
        logits = (xc @ emb_w.T.astype(xc.dtype)).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll_sum, m_sum = carry
        return (nll_sum + jnp.sum((lse - gold) * mc), m_sum + jnp.sum(mc)), None

    (nll, m), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)),
                               (xs, ls, ms))
    return nll / jnp.maximum(m, 1.0)

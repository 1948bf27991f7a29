"""Fused chunked-WKV Pallas kernel vs the model's chunked-scan oracle
(interpret mode on CPU): shape / chunk / seq-block sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _inputs(bh, t, n, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    r = jax.random.normal(ks[0], (bh, t, n))
    k = jax.random.normal(ks[1], (bh, t, n))
    v = jax.random.normal(ks[2], (bh, t, n))
    # realistic log-decays: negative, mostly close to 0
    lw = -jnp.exp(jax.random.normal(ks[3], (bh, t, n)) - 1.0)
    u = 0.5 * jax.random.normal(jax.random.fold_in(ks[0], 7), (n,))
    return r, k, v, lw, u


@pytest.mark.parametrize("bh,t,n,chunk", [
    (2, 128, 64, 64),
    (1, 256, 64, 64),
    (4, 64, 32, 32),
    (2, 128, 64, 32),   # chunk smaller than seq block
])
def test_wkv_kernel_matches_oracle(bh, t, n, chunk):
    r, k, v, lw, u = _inputs(bh, t, n, key=bh + t)
    o_k, s_k = ops.wkv_chunks(r, k, v, lw, u, chunk=chunk,
                              interpret=True)
    o_r, s_r = ref.wkv_chunks(r, k, v, lw, u, chunk=chunk)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               rtol=1e-4, atol=1e-4)


def test_wkv_kernel_seq_blocking_carries_state():
    """State must flow across seq-block grid steps (T split into 2)."""
    r, k, v, lw, u = _inputs(2, 256, 64, key=11)
    o_full, s_full = ops.wkv_chunks(r, k, v, lw, u, chunk=64,
                                    interpret=True)
    o_blk, s_blk = ops.wkv_chunks(r, k, v, lw, u, chunk=64, seq_block=128,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(o_blk), np.asarray(o_full),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_blk), np.asarray(s_full),
                               rtol=1e-4, atol=1e-4)


def test_wkv_kernel_decay_semantics():
    """Strong decay (lw << 0) must kill cross-chunk state influence."""
    bh, t, n = 1, 128, 64
    r, k, v, lw, u = _inputs(bh, t, n, key=3)
    hard = jnp.full_like(lw, -8.0)   # MIN_LOG_W: ~e^-8 per step
    o_k, s_k = ops.wkv_chunks(r, k, v, hard, u, chunk=64,
                              interpret=True)
    o_r, s_r = ref.wkv_chunks(r, k, v, hard, u, chunk=64)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=1e-4, atol=1e-4)
    # with decay e^-8 per step the state forgets almost immediately:
    # it equals the last token's kv outer product to high precision
    last_kv = k[:, -1][..., :, None] * v[:, -1][..., None, :]
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(last_kv),
                               rtol=1e-2, atol=1e-2)


def test_wkv_kernel_matches_model_time_mix_core():
    """End-to-end: kernel output == the rwkv6 model's chunked path on the
    same (B,T,H,N) tensors."""
    from repro.models.rwkv6 import _chunked_wkv
    B, T, H, N = 2, 128, 3, 64
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    r = jax.random.normal(ks[0], (B, T, H, N))
    k = jax.random.normal(ks[1], (B, T, H, N))
    v = jax.random.normal(ks[2], (B, T, H, N))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, T, H, N)))
    u = 0.3 * jnp.ones((H, N))
    o_m, s_m = _chunked_wkv(r, k, v, lw, u, 64)
    # kernel layout: (B*H, T, N)
    tohw = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, T, N)
    o_k, s_k = ops.wkv_chunks(tohw(r), tohw(k), tohw(v), tohw(lw),
                              u[0], chunk=64, interpret=True)
    # accumulation order differs between the batched-einsum model path
    # and the per-head kernel loop: agreement to ~5e-3 absolute
    np.testing.assert_allclose(
        np.asarray(o_k.reshape(B, H, T, N).transpose(0, 2, 1, 3)),
        np.asarray(o_m), rtol=2e-2, atol=5e-3)

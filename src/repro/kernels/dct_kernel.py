"""Pallas TPU kernel: batched per-chunk 2-D DCT (the DeMo compression
hot-spot).

The (NC, s, s) chunk grid is tiled into VMEM blocks of ``block_chunks``
chunks; each block runs two MXU matmuls (M @ X @ Mᵀ) with the s x s DCT
basis resident in VMEM. With s=64 a block of 64 chunks is 1 MiB in +
1 MiB out, double-buffered, plus the matmul and transpose temporaries:
inside v5e's 16 MiB scoped VMEM. 128 chunks is not — the v5e compiler
counts 17.3 MB for it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_CHUNKS = 64


def _dct_block_kernel(x_ref, m_ref, o_ref, *, inverse: bool):
    x = x_ref[...].astype(jnp.float32)          # (TB, s, s)
    m = m_ref[...].astype(jnp.float32)          # (s, s)
    if inverse:
        m = m.T
    # y = M @ x @ M^T, batched over TB. dot_general hits the MXU.
    y = jax.lax.dot_general(x, m, (((2,), (1,)), ((), ())))   # (TB,s,i) x@M^T ... see below
    # first contraction: over x's last dim with m's last dim -> x @ M^T
    # second: contract x's middle dim with m: result = M @ (x M^T)
    y = jax.lax.dot_general(y, m, (((1,), (1,)), ((), ())))   # (TB, s, s)
    # dims now (TB, k_cols, i_rows); transpose back to (TB, i, k)
    o_ref[...] = y.transpose(0, 2, 1)


def _pallas_dct(x: jnp.ndarray, m: jnp.ndarray, *, inverse: bool,
                block_chunks: int, interpret: bool) -> jnp.ndarray:
    nc, s, _ = x.shape
    tb = min(block_chunks, nc)
    # pad chunk count to a multiple of the block
    pad = (-nc) % tb
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, s, s), x.dtype)], axis=0)
    grid = (x.shape[0] // tb,)
    out = pl.pallas_call(
        functools.partial(_dct_block_kernel, inverse=inverse),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, s, s), lambda i: (i, 0, 0)),
            pl.BlockSpec((s, s), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tb, s, s), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        interpret=interpret,
    )(x, m)
    return out[:nc]


def dct2_chunks(x: jnp.ndarray, *, block_chunks: int = DEFAULT_BLOCK_CHUNKS,
                interpret: bool = True) -> jnp.ndarray:
    """Forward per-chunk 2-D DCT. x: (NC, s, s)."""
    from repro.demo.dct import dct_matrix
    m = jnp.asarray(dct_matrix(x.shape[-1]))
    return _pallas_dct(x, m, inverse=False, block_chunks=block_chunks,
                       interpret=interpret)


def idct2_chunks(c: jnp.ndarray, *, block_chunks: int = DEFAULT_BLOCK_CHUNKS,
                 interpret: bool = True) -> jnp.ndarray:
    """Inverse per-chunk 2-D DCT. c: (NC, s, s)."""
    from repro.demo.dct import dct_matrix
    m = jnp.asarray(dct_matrix(c.shape[-1]))
    return _pallas_dct(c, m, inverse=True, block_chunks=block_chunks,
                       interpret=interpret)

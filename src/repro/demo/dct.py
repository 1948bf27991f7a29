"""Chunked 2-D DCT transform for DeMo compression (arXiv:2411.19870).

Every parameter tensor is canonicalized to 2-D (dim0, prod(rest)), padded to
multiples of the chunk side ``s``, and viewed as an (R, s, C, s) grid of
s x s chunks. Encode applies an orthonormal DCT-II along both chunk axes —
a batched ``Mᵀ X M`` pair of matmuls, which is exactly what the Pallas
kernel in ``repro.kernels.dct_kernel`` runs on the MXU. These jnp
implementations are the reference oracles for those kernels.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace


@functools.lru_cache(maxsize=None)
def dct_matrix(s: int) -> np.ndarray:
    """Orthonormal DCT-II basis M (s,s): y = M @ x. M @ M.T = I."""
    k = np.arange(s)[:, None]
    n = np.arange(s)[None, :]
    m = np.cos(np.pi * (2 * n + 1) * k / (2 * s))
    m[0] *= 1.0 / math.sqrt(2)
    return (m * math.sqrt(2.0 / s)).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class ChunkMeta:
    """Static chunking layout for one tensor.

    Deliberately a plain dataclass (NOT a NamedTuple): it must be a pytree
    *leaf* so ``jax.tree.map`` over meta trees passes whole metas around.

    Canonicalization: an ndim>=2 tensor is viewed as
    (prod(shape[:-1]), shape[-1]); a 1-D tensor is wrapped to width s.
    Both are then zero-padded to multiples of s.
    """
    shape: Tuple[int, ...]   # original tensor shape
    c0: int                  # canonical 2-D rows
    c1: int                  # canonical 2-D cols
    rows: int                # R: padded c0 / s
    cols: int                # C: padded c1 / s
    s: int

    @property
    def num_chunks(self) -> int:
        return self.rows * self.cols


def chunk_meta(shape: Tuple[int, ...], s: int) -> ChunkMeta:
    if len(shape) == 1:
        c1 = min(s, shape[0])
        c0 = -(-shape[0] // c1)
    else:
        c0 = int(np.prod(shape[:-1]))
        c1 = shape[-1]
    return ChunkMeta(shape=tuple(shape), c0=c0, c1=c1,
                     rows=-(-c0 // s), cols=-(-c1 // s), s=s)


def to_chunks(x: jnp.ndarray, meta: ChunkMeta) -> jnp.ndarray:
    """(orig shape) -> (R, s, C, s) zero-padded chunk grid, fp32.

    For ndim>=2 the canonical 2-D view is a plain collapse of the leading
    dims — NO global flatten. (The flatten-then-reshape variant defeats
    GSPMD sharding propagation and made XLA replicate every params-sized
    stage of the compression pipeline; §Perf pair B.)
    """
    s = meta.s
    if x.ndim >= 2:
        x2 = x.reshape(meta.c0, meta.c1).astype(jnp.float32)
    else:
        flat = x.reshape(-1).astype(jnp.float32)
        flat = jnp.pad(flat, (0, meta.c0 * meta.c1 - flat.size))
        x2 = flat.reshape(meta.c0, meta.c1)
    x2 = jnp.pad(x2, ((0, meta.rows * s - meta.c0),
                      (0, meta.cols * s - meta.c1)))
    return x2.reshape(meta.rows, s, meta.cols, s)


def from_chunks(g: jnp.ndarray, meta: ChunkMeta) -> jnp.ndarray:
    """(R, s, C, s) -> original tensor shape (crop padding)."""
    s = meta.s
    x2 = g.reshape(meta.rows * s, meta.cols * s)[:meta.c0, :meta.c1]
    if len(meta.shape) >= 2:
        return x2.reshape(meta.shape)
    n = int(np.prod(meta.shape))
    return x2.reshape(-1)[:n].reshape(meta.shape)


def dct2(chunks: jnp.ndarray) -> jnp.ndarray:
    """(R, s, C, s) -> per-chunk 2-D DCT coefficients, same layout."""
    m = jnp.asarray(dct_matrix(chunks.shape[1]))
    return jnp.einsum("ij,rjcl,kl->rick", m, chunks.astype(jnp.float32), m)


def idct2(coeffs: jnp.ndarray) -> jnp.ndarray:
    """Inverse of dct2 (orthonormal: inverse = transpose)."""
    m = jnp.asarray(dct_matrix(coeffs.shape[1]))
    return jnp.einsum("ji,rjcl,lk->rick", m, coeffs.astype(jnp.float32), m)


def encode(x: jnp.ndarray, meta: ChunkMeta) -> jnp.ndarray:
    """Tensor -> flat per-chunk DCT coefficients (num_chunks, s*s)."""
    with jax.named_scope(trace.SCOPE_ENCODE):
        c = dct2(to_chunks(x, meta))
        # (R,s,C,s) -> (R,C,s,s) -> (RC, s*s)
        return c.transpose(0, 2, 1, 3).reshape(meta.num_chunks,
                                               meta.s * meta.s)


def decode(coeffs_flat: jnp.ndarray, meta: ChunkMeta) -> jnp.ndarray:
    """(num_chunks, s*s) coefficients -> tensor in original shape."""
    s = meta.s
    with jax.named_scope(trace.SCOPE_DECODE):
        c = coeffs_flat.reshape(meta.rows, meta.cols, s, s).transpose(
            0, 2, 1, 3)
        return from_chunks(idct2(c), meta)

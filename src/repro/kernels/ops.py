"""Jit'd public wrappers over the Pallas kernels.

``interpret`` is the caller's choice: True runs the kernel through the
Pallas interpreter on any backend (how the tests validate the kernels
against ``repro.kernels.ref`` on CPU), False compiles it for the TPU.

``demo_encode`` bound to an ``interpret`` value (``functools.partial``)
is a drop-in for ``repro.demo.dct.encode`` so the DeMo optimizer can run
its whole compression pipeline through the kernels via ``encode_fn=``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.demo import dct as dct_ref
from repro.kernels import (dct_kernel, ef_update_kernel, topk_kernel,
                           wkv_kernel)


@functools.partial(jax.jit, static_argnames=("interpret", "block_chunks"))
def dct2_chunks(x, *, interpret: bool,
                block_chunks: int = dct_kernel.DEFAULT_BLOCK_CHUNKS):
    return dct_kernel.dct2_chunks(x, block_chunks=block_chunks,
                                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "block_chunks"))
def idct2_chunks(c, *, interpret: bool,
                 block_chunks: int = dct_kernel.DEFAULT_BLOCK_CHUNKS):
    return dct_kernel.idct2_chunks(c, block_chunks=block_chunks,
                                   interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("k", "interpret", "block_rows"))
def topk_chunks(x, k: int, *, interpret: bool,
                block_rows: int = topk_kernel.DEFAULT_BLOCK_ROWS):
    return topk_kernel.topk_chunks(x, k, block_rows=block_rows,
                                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("beta", "interpret"))
def ef_update(e, g, beta: float, *, interpret: bool):
    return ef_update_kernel.ef_update(e, g, beta, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "chunk", "seq_block"))
def wkv_chunks(r, k, v, lw, u, *, interpret: bool, chunk: int = 64,
               seq_block: int = 0):
    return wkv_kernel.wkv_chunks(r, k, v, lw, u, chunk=chunk,
                                 seq_block=seq_block, interpret=interpret)


def demo_encode(x: jnp.ndarray, meta: dct_ref.ChunkMeta, *,
                interpret: bool) -> jnp.ndarray:
    """Kernel-backed replacement for ``repro.demo.dct.encode``."""
    chunks = dct_ref.to_chunks(x, meta)                       # (R,s,C,s)
    flat = chunks.transpose(0, 2, 1, 3).reshape(meta.num_chunks, meta.s,
                                                meta.s)
    coeffs = dct2_chunks(flat, interpret=interpret)           # (NC,s,s)
    return coeffs.reshape(meta.num_chunks, meta.s * meta.s)


def demo_decode(coeffs_flat: jnp.ndarray, meta: dct_ref.ChunkMeta, *,
                interpret: bool):
    """Kernel-backed replacement for ``repro.demo.dct.decode``."""
    c = idct2_chunks(coeffs_flat.reshape(meta.num_chunks, meta.s, meta.s),
                     interpret=interpret)
    c = c.reshape(meta.rows, meta.cols, meta.s, meta.s).transpose(0, 2, 1, 3)
    return dct_ref.from_chunks(c, meta)

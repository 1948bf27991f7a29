"""Pallas TPU kernel: fused chunked-WKV for RWKV-6 time-mix.

§Perf pair C showed rwkv6 training is memory-roofline-bound and that the
dominant traffic is the (L, L, N) intra-chunk decay tensor the jnp path
materializes in HBM for every chunk. This kernel keeps the ENTIRE chunk
recurrence in VMEM: one grid program per (batch, head, seq block) loads
that head's r/k/v/log-decay strips, loops the chunks sequentially
(carrying the (N, N) state in registers/VMEM), and builds the decay
tensor per chunk *inside* VMEM — it never touches HBM.

The chunk loop is unrolled, so T is tiled into ``seq_block`` tokens per
grid step (default ``DEFAULT_SEQ_BLOCK``); the state flows across grid
steps through the carry ref (the T axis is the innermost sequential grid
dim). At N=64, L=64 a 256-token block compiles for v5e in about 3 s; a
4096-token block unrolls 64 chunks, compiles for minutes and overflows
v5e's 16 MiB scoped VMEM by 64 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

MIN_LOG_W = -8.0
DEFAULT_SEQ_BLOCK = 256


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_ref,
                *, chunk: int, seq_block: int):
    """One (b, h) pair, one seq block of ``seq_block`` tokens."""
    t_idx = pl.program_id(1)

    @pl.when(t_idx == 0)
    def _init():
        s_ref[...] = jnp.zeros(s_ref.shape, s_ref.dtype)

    r = r_ref[0].astype(jnp.float32)             # (TB, N)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = jnp.maximum(lw_ref[0].astype(jnp.float32), MIN_LOG_W)
    u = u_ref[0].astype(jnp.float32)             # (N,)
    TB, N = r.shape
    nc = TB // chunk
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    mask = (rows > cols).astype(jnp.float32)
    # inclusive prefix sums as a matmul (Mosaic has no cumsum)
    incl = (rows >= cols).astype(jnp.float32)

    S = s_ref[...].astype(jnp.float32)           # (N, N) carried state
    for c in range(nc):                          # static unroll
        sl = slice(c * chunk, (c + 1) * chunk)
        rc, kc, vc, lwc = r[sl], k[sl], v[sl], lw[sl]     # (L, N)
        la = jnp.dot(incl, lwc,                   # inclusive log-decay
                     precision=jax.lax.Precision.HIGHEST)
        lap = la - lwc                           # exclusive
        lend = la[-1:]                           # (1, N)
        # intra-chunk decay tensor — VMEM-resident, never written out
        dec = jnp.exp(jnp.minimum(
            lap[:, None, :] - la[None, :, :], 0.0))        # (L, L, N)
        # three-operand contraction as multiply + lane reduction (Mosaic
        # lowers no dot with a batch dim in the middle)
        scores = jnp.sum(rc[:, None, :] * kc[None, :, :] * dec, axis=-1)
        scores = scores * mask
        bonus = jnp.sum(rc * u[None, :] * kc, axis=-1, keepdims=True)
        o = scores @ vc + bonus * vc
        o = o + (rc * jnp.exp(lap)) @ S                    # inter-chunk
        kdec = kc * jnp.exp(lend - la)                     # (L, N)
        S = jnp.exp(lend[0])[:, None] * S + kdec.T @ vc
        o_ref[0, sl, :] = o
    s_ref[...] = S


def wkv_chunks(r, k, v, lw, u, *, chunk: int = 64,
               seq_block: int = 0, interpret: bool = True):
    """Fused chunked-WKV. r/k/v/lw: (BH, T, N) fp32; u: (N,).

    Returns (o (BH, T, N) fp32, final state (BH, N, N) fp32). Exact same
    math as ``repro.models.rwkv6._chunked_wkv`` (the oracle is
    ``repro.kernels.ref.wkv_chunks_ref``).
    """
    BH, T, N = r.shape
    assert T % chunk == 0, (T, chunk)
    tb = seq_block or min(T, DEFAULT_SEQ_BLOCK)
    tb = max(chunk, (tb // chunk) * chunk)
    assert T % tb == 0, (T, tb)
    grid = (BH, T // tb)
    strip = pl.BlockSpec((1, tb, N), lambda b, t: (b, t, 0))
    out, state = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk, seq_block=tb),
        grid=grid,
        in_specs=[strip, strip, strip,
                  strip,
                  pl.BlockSpec((1, N), lambda b, t: (0, 0))],
        out_specs=[strip,
                   pl.BlockSpec((N, N), lambda b, t: (b, 0))],
        out_shape=[jax.ShapeDtypeStruct((BH, T, N), jnp.float32),
                   jax.ShapeDtypeStruct((BH * N, N), jnp.float32)],
        interpret=interpret,
    )(r, k, v, lw, u.reshape(1, N))
    return out, state.reshape(BH, N, N)

"""Pallas TPU kernel: per-chunk top-k magnitude selection.

TPU has no warp-shuffle top-k; the TPU-idiomatic equivalent is a k-step
iterative argmax over a VMEM-resident block (k is small — DeMo keeps 32 of
4096 coefficients). Each grid step loads (block_rows, E) coefficients into
VMEM and runs ``k`` vectorized argmax+mask iterations entirely on-chip.
At E=4096, 64 rows keep the block, its magnitudes and the column iota
inside v5e's 16 MiB scoped VMEM; 256 rows do not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 64


def _topk_kernel(x_ref, vals_ref, idx_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)                    # (R, E)
    rows, E = x.shape
    mag = jnp.abs(x)
    cols = jax.lax.broadcasted_iota(jnp.int32, (rows, E), 1)
    slots = jax.lax.broadcasted_iota(jnp.int32, (rows, k), 1)

    def body(i, carry):
        mag_c, vals, idx = carry
        j = jnp.argmax(mag_c, axis=-1).astype(jnp.int32)  # (R,)
        onehot = cols == j[:, None]
        v = jnp.sum(jnp.where(onehot, x, 0.0), axis=-1)   # signed value
        # results collect in registers: the TPU cannot store one column
        # at a lane offset it cannot prove 128-aligned
        vals = jnp.where(slots == i, v[:, None], vals)
        idx = jnp.where(slots == i, j[:, None], idx)
        mag_c = jnp.where(onehot, -1.0, mag_c)            # knock out
        return mag_c, vals, idx

    _, vals, idx = jax.lax.fori_loop(
        0, k, body, (mag, jnp.zeros((rows, k), jnp.float32),
                     jnp.zeros((rows, k), jnp.int32)))
    vals_ref[...] = vals
    idx_ref[...] = idx


def topk_chunks(x: jnp.ndarray, k: int, *,
                block_rows: int = DEFAULT_BLOCK_ROWS,
                interpret: bool = True):
    """x: (NC, E) -> (vals (NC,k), idx (NC,k) int32), top-k by |value|.

    Ties broken by lower index (matches jax.lax.top_k for distinct mags).
    """
    nc, E = x.shape
    br = min(block_rows, nc)
    pad = (-nc) % br
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, E), x.dtype)], axis=0)
    grid = (x.shape[0] // br,)
    vals, idx = pl.pallas_call(
        functools.partial(_topk_kernel, k=k),
        grid=grid,
        in_specs=[pl.BlockSpec((br, E), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, k), lambda i: (i, 0)),
                   pl.BlockSpec((br, k), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((x.shape[0], k), jnp.float32),
                   jax.ShapeDtypeStruct((x.shape[0], k), jnp.int32)],
        interpret=interpret,
    )(x)
    return vals[:nc], idx[:nc]

"""Fused RMSNorm Pallas TPU kernel — the Reconstructing-BatchNorm analogue.

Paper §6.4 splits/fuses normalization with neighbouring kernels to halve the
normalized tensor's HBM reads.  The LM-era equivalent is a fused RMSNorm:
one pass reads x, computes the f32 mean-square across the feature dim, and
writes the scaled output — instead of the unfused square / mean / rsqrt /
mul / mul chain (5 reads + 4 writes -> 1 read + 1 write).

Layout: x (rows, D) with D a multiple of 128 (ops wrapper pads); one
row-block per grid step, weight broadcast to every block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 8


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float, d_real: int):
    x = x_ref[...].astype(jnp.float32)                 # (blk, D)
    D = x.shape[-1]
    if d_real != D:                                    # padded tail is zero
        denom = float(d_real)
    else:
        denom = float(D)
    ms = jnp.sum(x * x, axis=-1, keepdims=True) / denom
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_2d(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
               d_real: int = 0, interpret: bool) -> jax.Array:
    rows, D = x.shape
    blk = min(BLOCK_ROWS, rows)
    grid = (rows // blk,)
    kern = functools.partial(_rmsnorm_kernel, eps=eps, d_real=d_real or D)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((blk, D), lambda i: (i, 0)),
                  pl.BlockSpec((1, D), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((blk, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, D), x.dtype),
        interpret=interpret,
    )(x, w.reshape(1, D))

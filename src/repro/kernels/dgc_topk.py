"""DGC gradient-sparsification Pallas TPU kernel (threshold selection stage).

Deep Gradient Compression (paper §5.2 / Algorithm 12) transmits only the
largest-magnitude gradient entries.  Exact global top-k is a poor fit for the
VPU; the TPU-native formulation (as in production DGC implementations) is
*threshold sparsification*: estimate the k-th magnitude from a sample on the
host/XLA side, then run one vectorized pass that zeroes everything below the
threshold and counts survivors.  This kernel is that pass; ``ops.dgc_mask``
wraps it, and ``ref.dgc_topk_ref`` is the exact top-k oracle the tests
compare against (using the oracle's own k-th value as the threshold).

Layout: (rows, LANE) f32 blocks like fused_adam.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 1024
BLOCK_ROWS = 8


def _dgc_kernel(g_ref, thr_ref, o_ref, cnt_ref):
    g = g_ref[...].astype(jnp.float32)
    thr = thr_ref[0]
    keep = jnp.abs(g) >= thr
    o_ref[...] = jnp.where(keep, g, 0.0).astype(o_ref.dtype)
    cnt_ref[...] = jnp.sum(keep.astype(jnp.int32), axis=-1, keepdims=True)


def dgc_threshold_2d(g: jax.Array, thr: jax.Array, *,
                     interpret: bool):
    """g: (rows, LANE) f32; thr: (1,) f32 -> (sparse g, per-row keep counts)."""
    rows = g.shape[0]
    blk = min(BLOCK_ROWS, rows)
    grid = (rows // blk,)
    return pl.pallas_call(
        _dgc_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((blk, LANE), lambda i: (i, 0)),
                  pl.BlockSpec((1,), lambda i: (0,))],
        out_specs=[pl.BlockSpec((blk, LANE), lambda i: (i, 0)),
                   pl.BlockSpec((blk, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, LANE), g.dtype),
                   jax.ShapeDtypeStruct((rows, 1), jnp.int32)],
        interpret=interpret,
    )(g, thr)

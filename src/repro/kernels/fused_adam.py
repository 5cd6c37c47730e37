"""FusedAdam Pallas TPU kernel (paper §6.3).

One VMEM-tiled pass over contiguous (param, grad, m, v) vectors producing the
updated triple — the TPU analogue of Apex FusedAdam: the paper's win was
eliminating thousands of CUDA launches; the TPU win is eliminating per-op
dispatch/fusion overhead and re-reading the same vectors across the ~10
element-wise stages of an unfused Adam chain (read p,g,m,v once, write p,m,v
once: 7 vector transfers instead of ~20).

Layout: the ops wrapper flattens/pads to (rows, LANE) with LANE=1024 (8x128
VPU tiles); the kernel runs one row-block per grid step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 1024
BLOCK_ROWS = 8


def _adam_kernel(p_ref, g_ref, m_ref, v_ref, lr_ref, c1_ref, c2_ref,
                 po_ref, mo_ref, vo_ref, *, b1: float, b2: float,
                 eps: float, wd: float):
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...]
    v = v_ref[...]
    lr = lr_ref[0]
    c1 = c1_ref[0]
    c2 = c2_ref[0]
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    step = (m_new / c1) / (jnp.sqrt(v_new / c2) + eps) + wd * p
    po_ref[...] = (p - lr * step).astype(po_ref.dtype)
    mo_ref[...] = m_new
    vo_ref[...] = v_new


def fused_adam_2d(p: jax.Array, g: jax.Array, m: jax.Array, v: jax.Array,
                  lr: jax.Array, c1: jax.Array, c2: jax.Array, *,
                  b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
                  wd: float = 0.1, interpret: bool):
    """All arrays (rows, LANE) f32; lr/c1/c2 shape-(1,) f32 scalars."""
    rows = p.shape[0]
    blk = min(BLOCK_ROWS, rows)
    grid = (rows // blk,)
    kern = functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps, wd=wd)
    vec = pl.BlockSpec((blk, LANE), lambda i: (i, 0))
    scal = pl.BlockSpec((1,), lambda i: (0,))
    out = jax.ShapeDtypeStruct((rows, LANE), jnp.float32)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[vec, vec, vec, vec, scal, scal, scal],
        out_specs=[vec, vec, vec],
        out_shape=[out, out, out],
        interpret=interpret,
    )(p, g, m, v, lr, c1, c2)

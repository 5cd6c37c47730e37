"""Mamba-2's short causal depthwise conv with bias and SiLU, in Pallas.

y_s = SiLU(bias + Σ_k w_k · x_{s−K+1+k}) per channel, x zero before the
sequence; K = 4 and the channels are x, B and C side by side (E + 2N).  XLA
runs the four shifted reads, the f32 sums and the SiLU, and their backward,
as several f32 passes over (B, S, channels); here each direction is one
pass over the grid (batch, row block), rows of ``block_rows(S)`` with all
channels in the lanes.

Forward: the row blocks run in order and the last 8 input rows of a block
are kept in VMEM scratch for the next, so the K − 1 rows each block reaches
back for are never read twice from HBM.  Backward: the row blocks run last
to first; each recomputes the pre-activation u from x (its 8-row look-back
read as one more small block), takes dz = dy · SiLU'(u), and keeps its
first 8 rows of dz in scratch for the block before it, which reaches
forward for them: dx_s = Σ_k w_k · dz_{s+K−1−k}.  The weight and bias
gradients are summed over the rows in VMEM and written once per batch row;
XLA sums them over the batch.

Numerics as the XLA path: bf16 in, sums, SiLU and its derivative in f32,
y and dx stored in x's dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
HALO = 8                            # rows kept across blocks (K − 1 ≤ 8)
VMEM_LIMIT = 64 * 1024 * 1024


def block_rows(S: int) -> int:
    """Rows a grid step takes: the largest of 128, 64, …, 8 dividing S (a
    multiple of 8)."""
    return next(r for r in (128, 64, 32, 16, 8) if S % r == 0)


def _sigmoid(u):
    return 1.0 / (1.0 + jnp.exp(-u))


def _pre(full, w, b, rows):
    """u = b + Σ_k w_k · x_{s−K+1+k} of the ``rows`` rows after the
    ``HALO`` rows of ``full``."""
    K = w.shape[0]
    u = b + full[HALO:HALO + rows] * w[K - 1:K]
    for i in range(1, K):
        u = u + full[HALO - i:HALO - i + rows] * w[K - 1 - i:K - i]
    return u


def _fwd_kernel(x_ref, w_ref, b_ref, y_ref, prev_scr):
    rows = x_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        prev_scr[...] = jnp.zeros_like(prev_scr)

    x = x_ref[0].astype(F32)
    u = _pre(jnp.concatenate([prev_scr[...], x], axis=0),
             w_ref[...].astype(F32), b_ref[...].astype(F32), rows)
    y_ref[0] = (u * _sigmoid(u)).astype(y_ref.dtype)
    prev_scr[...] = x[rows - HALO:]


def _bwd_kernel(x_ref, xp_ref, dy_ref, w_ref, b_ref, dx_ref, dw_ref, db_ref,
                next_scr, dw_scr, db_scr):
    """Grid (batch, row block), the row blocks last to first."""
    rows = x_ref.shape[1]
    K = w_ref.shape[0]
    j = pl.program_id(1)
    first_block = j == pl.num_programs(1) - 1

    @pl.when(j == 0)
    def _init():
        next_scr[...] = jnp.zeros_like(next_scr)
        dw_scr[...] = jnp.zeros_like(dw_scr)
        db_scr[...] = jnp.zeros_like(db_scr)

    w = w_ref[...].astype(F32)
    x = x_ref[0].astype(F32)
    xp = jnp.where(first_block, 0.0, xp_ref[0].astype(F32))
    full = jnp.concatenate([xp, x], axis=0)
    u = _pre(full, w, b_ref[...].astype(F32), rows)
    sg = _sigmoid(u)
    dz = dy_ref[0].astype(F32) * sg * (1.0 + u * (1.0 - sg))
    dz_full = jnp.concatenate([dz, next_scr[...]], axis=0)
    dx = dz * w[K - 1:K]
    for i in range(1, K):
        dx = dx + dz_full[i:i + rows] * w[K - 1 - i:K - i]
    dx_ref[0] = dx.astype(dx_ref.dtype)
    dw_scr[...] += jnp.concatenate(
        [jnp.sum(full[HALO - K + 1 + k:HALO - K + 1 + k + rows] * dz,
                 axis=0, keepdims=True) for k in range(K)], axis=0)
    db_scr[...] += jnp.sum(dz, axis=0, keepdims=True)
    next_scr[...] = dz[:HALO]

    @pl.when(first_block)
    def _finish():
        dw_ref[0] = dw_scr[...]
        db_ref[0] = db_scr[...]


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                vmem_limit_bytes=VMEM_LIMIT)


@functools.partial(jax.jit, static_argnums=(3,))
def _forward(x, w, b, interpret):
    B, S, C = x.shape
    rows = block_rows(S)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(B, S // rows),
        in_specs=[pl.BlockSpec((1, rows, C), lambda i, j: (i, j, 0)),
                  pl.BlockSpec(w.shape, lambda i, j: (0, 0)),
                  pl.BlockSpec((1, C), lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((1, rows, C), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((HALO, C), F32)],
        compiler_params=_params(),
        interpret=interpret,
    )(x, w, b[None])


def _backward(x, w, b, dy, interpret):
    B, S, C = x.shape
    K = w.shape[0]
    rows = block_rows(S)
    n = S // rows

    def blk(i, j):                      # the row blocks last to first
        return (i, n - 1 - j, 0)

    def look_back(i, j):                # the 8 rows before the block
        return (i, jnp.maximum((n - 1 - j) * (rows // HALO) - 1, 0), 0)

    row_spec = pl.BlockSpec((1, rows, C), blk)
    dx, dw, db = pl.pallas_call(
        _bwd_kernel,
        grid=(B, n),
        in_specs=[row_spec, pl.BlockSpec((1, HALO, C), look_back), row_spec,
                  pl.BlockSpec(w.shape, lambda i, j: (0, 0)),
                  pl.BlockSpec((1, C), lambda i, j: (0, 0))],
        out_specs=[row_spec,
                   pl.BlockSpec((1, K, C), lambda i, j: (i, 0, 0)),
                   pl.BlockSpec((1, 1, C), lambda i, j: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((B, K, C), F32),
                   jax.ShapeDtypeStruct((B, 1, C), F32)],
        scratch_shapes=[pltpu.VMEM((HALO, C), F32), pltpu.VMEM((K, C), F32),
                        pltpu.VMEM((1, C), F32)],
        compiler_params=_params(),
        interpret=interpret,
    )(x, x, dy, w, b[None])
    return (dx, dw.sum(0).astype(w.dtype),
            db.sum((0, 1)).astype(b.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv_silu(x, w, b, interpret):
    return _forward(x, w, b, interpret)


def _conv_silu_fwd(x, w, b, interpret):
    return _forward(x, w, b, interpret), (x, w, b)


def _conv_silu_bwd(interpret, res, dy):
    return _backward(*res, dy, interpret)


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def conv_silu(x: jax.Array, w: jax.Array, b: jax.Array, *,
              interpret: bool) -> jax.Array:
    """x: (B, S, C), S a multiple of 8; w: (K, C), its last row on the
    current position, K ≤ 8; b: (C,) -> SiLU(causal conv + b) (B, S, C) in
    x's dtype, differentiable in x, w and b.  ``interpret=True`` executes
    the kernels in Python on the CPU."""
    if x.shape[1] % HALO or w.shape[0] > HALO:
        raise ValueError(f"no tiling for S={x.shape[1]}, K={w.shape[0]}")
    return _conv_silu(x, w, b, interpret)

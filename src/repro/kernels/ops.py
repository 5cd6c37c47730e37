"""Jit'd public wrappers for the Pallas kernels: padding, reshaping, dtype
management.  Each wrapper runs its kernel through the Pallas interpreter on
the CPU backend and compiles it for the device on every other backend; the
choice is made from ``jax.default_backend()`` when the wrapper is traced.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from . import causal_conv as _cc
from . import flash_attention as _fa
from . import ssd_chunk as _sc
from . import fused_adam as _ad
from . import rmsnorm as _rn
from . import dgc_topk as _dg


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(x: jax.Array, axis: int, mult: int) -> Tuple[jax.Array, int]:
    n = x.shape[axis]
    target = -(-n // mult) * mult
    if target == n:
        return x, n
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - n)
    return jnp.pad(x, pad), n


# ------------------------------------------------------------------ flash
@functools.partial(jax.jit, static_argnames=("causal",))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True) -> jax.Array:
    """q: (B, H, S, D); k/v: (B, KH, S, D).  Pads S to the kernel's block
    multiple (padded keys are masked); differentiable in q, k and v."""
    S = q.shape[2]
    Sp = _fa.padded_len(S)
    qp, kp, vp = (_pad_to(x, 2, Sp)[0] for x in (q, k, v))
    out = _fa.flash_attention(qp, kp, vp, causal=causal, kv_len=S,
                              interpret=_interpret())
    return out[:, :, :S]


# ------------------------------------------------------------ chunked ssd
@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd(x: jax.Array, b: jax.Array, c: jax.Array, dt: jax.Array,
        cum: jax.Array, d: jax.Array, *, chunk: int):
    """The chunked SSD (``ssd_chunk.ssd``): x (B, S, E), b/c (B, S, N),
    dt/cum (B, H, S) f32, d (H,) -> y (B, S, E), final state (B, N, E)."""
    return _sc.ssd(x, b, c, dt, cum, d, chunk=chunk, interpret=_interpret())


# ------------------------------------------------------- causal conv silu
@jax.jit
def conv_silu(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """SiLU(causal depthwise conv of x + b) (``causal_conv.conv_silu``): x
    (B, S, C) with S a multiple of 8, w (K, C), b (C,)."""
    return _cc.conv_silu(x, w, b, interpret=_interpret())


# ------------------------------------------------------------- fused adam
@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd"))
def fused_adam(p: jax.Array, g: jax.Array, m: jax.Array, v: jax.Array, *,
               lr, b1: float, b2: float, eps: float, wd: float, c1, c2):
    """Flat f32 vectors (N,) -> updated (p, m, v)."""
    N = p.shape[0]
    lane = _ad.LANE

    def to2d(x):
        xp, _ = _pad_to(x.astype(jnp.float32), 0, lane)
        return xp.reshape(-1, lane)

    p2, g2, m2, v2 = map(to2d, (p, g, m, v))
    rows = p2.shape[0]
    blk = min(_ad.BLOCK_ROWS, rows)
    if rows % blk:
        extra = blk - rows % blk
        z = jnp.zeros((extra, lane), jnp.float32)
        p2, g2, m2, v2 = (jnp.concatenate([a, z]) for a in (p2, g2, m2, v2))
    po, mo, vo = _ad.fused_adam_2d(
        p2, g2, m2, v2,
        jnp.asarray(lr, jnp.float32).reshape(1),
        jnp.asarray(c1, jnp.float32).reshape(1),
        jnp.asarray(c2, jnp.float32).reshape(1),
        b1=b1, b2=b2, eps=eps, wd=wd, interpret=_interpret())
    return (po.reshape(-1)[:N], mo.reshape(-1)[:N], vo.reshape(-1)[:N])


# ---------------------------------------------------------------- rmsnorm
@jax.jit
def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    """x: (..., D), w: (D,) -> fused RMSNorm over the last dim."""
    shape = x.shape
    D = shape[-1]
    x2 = x.reshape(-1, D)
    x2p, _ = _pad_to(x2, 1, 128)
    wp, _ = _pad_to(w, 0, 128)
    rows = x2p.shape[0]
    blk = min(_rn.BLOCK_ROWS, rows)
    padr = 0
    if rows % blk:
        padr = blk - rows % blk
        x2p = jnp.concatenate(
            [x2p, jnp.zeros((padr, x2p.shape[1]), x2p.dtype)])
    out = _rn.rmsnorm_2d(x2p, wp, eps=eps, d_real=D, interpret=_interpret())
    if padr:
        out = out[:-padr]
    return out[:, :D].reshape(shape)


# --------------------------------------------------------------- dgc mask
@jax.jit
def dgc_mask(g: jax.Array, threshold: jax.Array):
    """Zero entries with |g| < threshold.  Returns (sparse g, kept count)."""
    shape = g.shape
    flat = g.reshape(-1).astype(jnp.float32)
    N = flat.shape[0]
    lane = _dg.LANE
    fp, _ = _pad_to(flat, 0, lane)
    g2 = fp.reshape(-1, lane)
    rows = g2.shape[0]
    blk = min(_dg.BLOCK_ROWS, rows)
    padr = 0
    if rows % blk:
        padr = blk - rows % blk
        g2 = jnp.concatenate([g2, jnp.zeros((padr, lane), jnp.float32)])
    out, cnt = _dg.dgc_threshold_2d(
        g2, jnp.asarray(threshold, jnp.float32).reshape(1),
        interpret=_interpret())
    if padr:
        out, cnt = out[:-padr], cnt[:-padr]
    sparse = out.reshape(-1)[:N].reshape(shape).astype(g.dtype)
    # padded zeros never pass |0| >= thr for thr > 0
    return sparse, jnp.sum(cnt)

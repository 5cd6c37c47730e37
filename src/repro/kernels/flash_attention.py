"""Flash attention Pallas TPU kernel (blockwise causal attention).

TPU adaptation of the paper-era GPU flash algorithm (DESIGN.md §2): instead of
warp-level softmax reductions, the kernel tiles (q_block x k_block) score
tiles through VMEM with MXU-aligned 128x128 blocks; running max / denominator
/ accumulator live in VMEM scratch across the innermost k-grid dimension.
Scores never touch HBM — this removes the O(S^2) HBM traffic that makes the
pure-XLA chunked attention memory-bound (EXPERIMENTS.md §Perf).

Layouts: q (B, H, S, D), k/v (B, KH, S, D); GQA handled by mapping each q
head h to kv head h // (H // KH) in the BlockSpec index maps.  D padded to a
multiple of 128 by the ops wrapper.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  seq_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    run = True
    if causal:
        # whole k block strictly after the last q row -> nothing to do
        run = k_start <= q_start + block_q - 1

    @pl.when(jnp.asarray(run))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
        mask = k_pos < seq_len
        if causal:
            mask &= q_pos >= k_pos
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                             # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                          # (bq, bk)
        corr = jnp.exp(m_prev - m_new)                  # (bq, 1)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    sm_scale: float = 0.0,
                    kv_len: int = 0,
                    interpret: bool) -> jax.Array:
    """q: (B, H, S, D); k/v: (B, KH, S, D) -> (B, H, S, D).

    D must be a multiple of 128 and S a multiple of the block sizes (the ops
    wrapper pads; ``sm_scale``/``kv_len`` carry the pre-padding softmax scale
    and valid key count).  ``interpret=True`` executes the kernel body in
    Python on CPU; ``False`` compiles it for the TPU.
    """
    B, H, S, D = q.shape
    KH = k.shape[1]
    G = H // KH
    scale = sm_scale or 1.0 / math.sqrt(D)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    nq = S // block_q
    nk = S // block_k
    grid = (B, H, nq, nk)

    kern = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_len=kv_len or S)

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)

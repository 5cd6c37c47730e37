"""Flash attention Pallas TPU kernel, forward and backward.

Score tiles (``block_q`` x ``block_k``) live in VMEM only.  The forward
keeps a running max, denominator and f32 accumulator per query row across
the innermost k-grid dimension and saves the output and the row
log-sum-exp (``lse``), never the probabilities.  The backward is one pass
over the query blocks of each key block: it recomputes each tile's
probabilities from q, k and ``lse``, takes ``delta = rowsum(dO * O)``
(computed by XLA), and accumulates dK and dV of the key block over the G
query heads of its kv head, and dQ of all G heads' rows, in VMEM; dQ
leaves once per (batch, kv head).  Five matmuls a tile: S, dP, dV, dK, dQ.

The MXU is fed the operands' own dtype (bf16 in training) with f32
accumulation; scores, max, sum and accumulators are f32, and P and dS go to
their matmuls in the operands' dtype.  Under ``causal`` a tile wholly above
the diagonal is skipped, and its index map is clamped to the last tile that
is computed so the skipped grid steps fetch nothing new; only tiles that
cross the diagonal (or the padded key tail) build a mask.

Layouts: q (B, H, S, D), k/v (B, KH, S, D); GQA maps q head h to kv head
h // (H // KH) in the index maps, so K/V are never repeated in HBM.  S must
be ``padded_len(S)`` (the ops wrapper pads; ``kv_len`` is the number of
real keys).  D may be any width the block holds whole.

The forward names the output and ``lse`` it keeps for the backward
(``SAVED``, with ``checkpoint_name``), so a remat policy that saves those
names keeps them and the backward does not run the forward kernel again.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30
NT = (((1,), (1,)), ((), ()))        # contract the last dims: a @ b.T
TN = (((0,), (0,)), ((), ()))        # contract the first dims: a.T @ b
VMEM_LIMIT = 64 * 1024 * 1024        # of the v5e's 128 MiB
SAVED = ("flash_o", "flash_lse")     # the forward's o and lse, by name


def padded_len(S: int) -> int:
    """The sequence length the kernels run ``S`` at: one whole block up to
    1024 (a multiple of 8), else a multiple of 128."""
    mult = 8 if S <= 1024 else 128
    return -(-S // mult) * mult


def block_size(S: int) -> int:
    """The square tile for a padded sequence of ``S``: ``S`` itself up to
    1024, else the largest of 1024, 512, 256, 128 that divides it.  On a
    v5e at S 4096, D 64 the 1024 tile was the fastest of 512-2048 for the
    forward and the backward (PERF.md §6): larger tiles cost more
    masked work on the diagonal, smaller ones more grid steps."""
    if S <= 1024:
        return S
    for b in (1024, 512, 256, 128):
        if S % b == 0:
            return b
    raise ValueError(f"S={S} is not a multiple of 128; pad it to "
                     f"padded_len(S)")


def backward_fits(G: int, S: int, D: int) -> bool:
    """Whether the backward's VMEM-resident dQ of G heads x S rows (an f32
    accumulator and a double-buffered output block, 8 bytes an element)
    leaves half of ``VMEM_LIMIT`` to its tiles."""
    return 8 * G * padded_len(S) * D <= VMEM_LIMIT // 2


def _last_k(qi, block_q: int, block_k: int, kv_len: int, causal: bool):
    """Index of the last key block query block ``qi`` needs."""
    last = (kv_len - 1) // block_k
    if causal:
        last = jnp.minimum(last, (qi * block_q + block_q - 1) // block_k)
    return last


def _first_q(ki, block_q: int, block_k: int, causal: bool):
    """Index of the first query block that key block ``ki`` is seen by."""
    return (ki * block_k) // block_q if causal else 0


def _tile(qi, ki, block_q: int, block_k: int, kv_len: int, causal: bool):
    """(run, edge): whether the (qi, ki) tile has any unmasked score, and
    whether some of its scores are masked."""
    q_start, k_start = qi * block_q, ki * block_k
    k_end = k_start + block_k - 1
    run = k_start < kv_len
    edge = k_end >= kv_len
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
        edge = jnp.logical_or(edge, k_end > q_start)
    return run, edge


def _mask(s, q_start, k_start, kv_len: int, causal: bool, k_major: bool):
    """``s`` with masked scores set to NEG_INF; rows are queries, or keys
    when ``k_major``."""
    qa, ka = (1, 0) if k_major else (0, 1)
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, qa)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, ka)
    keep = k_pos < kv_len
    if causal:
        keep = jnp.logical_and(keep, q_pos >= k_pos)
    return jnp.where(keep, s, NEG_INF)


def _scaled(ref, scale: float):
    """The (1, 1, rows, D) block's tile times the softmax scale, in its own
    dtype: one multiply per element of q in place of one per score."""
    x = ref[0, 0]
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _both(run, edge, body):
    """Run ``body(masked)`` on tiles that need a mask and on those that
    do not, each traced once."""
    pl.when(jnp.logical_and(run, edge))(lambda: body(True))
    pl.when(jnp.logical_and(run, jnp.logical_not(edge)))(lambda: body(False))


# ------------------------------------------------------------------ forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k, kv_len):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(masked):
        v = v_ref[0, 0]
        s = jax.lax.dot_general(_scaled(q_ref, scale), k_ref[0, 0], NT,
                                preferred_element_type=jnp.float32)
        if masked:
            s = _mask(s, qi * block_q, ki * block_k, kv_len, causal, False)
        m_prev = m_scr[...]                              # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                           # (bq, bk)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    _both(*_tile(qi, ki, block_q, block_k, kv_len, causal), body)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l)).reshape(1, block_q)


def _forward(q, k, v, *, scale, causal, kv_len, interpret):
    B, H, S, D = q.shape
    KH = k.shape[1]
    G = H // KH
    bq = bk = block_size(S)

    def kv_map(b, h, qi, ki):
        return (b, h // G, jnp.minimum(
            ki, _last_k(qi, bq, bk, kv_len, causal)), 0)

    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             block_q=bq, block_k=bk, kv_len=kv_len)
    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0))
    return pl.pallas_call(
        kern,
        grid=(B, H, S // bq, S // bk),
        in_specs=[q_spec,
                  pl.BlockSpec((1, 1, bk, D), kv_map),
                  pl.BlockSpec((1, 1, bk, D), kv_map)],
        out_specs=[q_spec,
                   pl.BlockSpec((1, 1, 1, bq),
                                lambda b, h, qi, ki: (b, h, 0, qi))],
        out_shape=[jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(q, k, v)


# ----------------------------------------------------------------- backward
def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dq_ref, dk_scr, dv_scr, dq_scr,
                *, scale, causal, block_q, block_k, kv_len):
    """Grid (B, KH, nk, G, nq), key-major (tiles are (bk, bq), so lse and
    delta broadcast along lanes): key block ki of kv head kh accumulates
    dK and dV over the G query heads and the query blocks; dQ of the G
    heads accumulates over the key blocks in VMEM and is written at the
    last step of (b, kh)."""
    ki, g, qi = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    first = jnp.logical_and(g == 0, qi == 0)

    @pl.when(jnp.logical_and(ki == 0, first))
    def _init_dq():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(first)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def body(masked):
        q, do, k = _scaled(q_ref, scale), do_ref[0, 0], k_ref[0, 0]
        s = jax.lax.dot_general(k, q, NT, preferred_element_type=jnp.float32)
        if masked:
            s = _mask(s, qi * block_q, ki * block_k, kv_len, causal, True)
        p = jnp.exp(s - lse_ref[0, 0])                   # (bk, bq)
        dv_scr[...] += jnp.dot(p.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[0, 0], do, NT,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, 0])).astype(q.dtype)
        dk_scr[...] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
        dq_scr[g, rows, :] += jax.lax.dot_general(
            ds, k, TN, preferred_element_type=jnp.float32)

    _both(*_tile(qi, ki, block_q, block_k, kv_len, causal), body)

    last = jnp.logical_and(g == pl.num_programs(3) - 1,
                           qi == pl.num_programs(4) - 1)

    @pl.when(last)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(ki == pl.num_programs(2) - 1, last))
    def _finish_dq():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _backward(q, k, v, o, lse, do, *, scale, causal, kv_len, interpret):
    B, H, S, D = q.shape
    KH = k.shape[1]
    G = H // KH
    bq = bk = block_size(S)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, :, None, :]              # (B, H, 1, S)

    def q_map(b, kh, ki, g, qi):
        return (b, kh * G + g, jnp.maximum(qi, _first_q(ki, bq, bk, causal)),
                0)

    def row_map(b, kh, ki, g, qi):
        return (b, kh * G + g, 0,
                jnp.maximum(qi, _first_q(ki, bq, bk, causal)))

    kv_spec = pl.BlockSpec((1, 1, bk, D), lambda b, kh, ki, g, qi:
                           (b, kh, ki, 0))
    q_spec = pl.BlockSpec((1, 1, bq, D), q_map)
    row_spec = pl.BlockSpec((1, 1, 1, bq), row_map)
    dq_spec = pl.BlockSpec((1, G, S, D), lambda b, kh, ki, g, qi:
                           (b, kh, 0, 0))
    dk, dv, dq = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, kv_len=kv_len),
        grid=(B, KH, S // bk, G, S // bq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec, dq_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(q.shape, q.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((G, S, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# -------------------------------------------------------------- custom vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, kv_len, interpret):
    return _forward(q, k, v, scale=scale, causal=causal, kv_len=kv_len,
                    interpret=interpret)[0]


def _flash_fwd(q, k, v, scale, causal, kv_len, interpret):
    o, lse = _forward(q, k, v, scale=scale, causal=causal, kv_len=kv_len,
                      interpret=interpret)
    o, lse = checkpoint_name(o, SAVED[0]), checkpoint_name(lse, SAVED[1])
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, causal, kv_len, interpret, res, do):
    q, k, v, o, lse = res
    return _backward(q, k, v, o, lse, do, scale=scale, causal=causal,
                     kv_len=kv_len, interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, sm_scale: float = 0.0,
                    kv_len: int = 0, interpret: bool) -> jax.Array:
    """q: (B, H, S, D); k/v: (B, KH, S, D) -> (B, H, S, D), differentiable
    in q, k and v.

    S must be ``padded_len(S)``, and G = H // KH, S and D must pass
    ``backward_fits`` for the backward (the ops wrapper pads;
    ``sm_scale``/``kv_len`` carry the pre-padding softmax scale and valid
    key count).  ``interpret=True`` executes the kernels in Python on CPU;
    ``False`` compiles them for the TPU.
    """
    D = q.shape[-1]
    scale = sm_scale or 1.0 / math.sqrt(D)
    return _flash(q, k, v, scale, causal, kv_len or q.shape[2], interpret)

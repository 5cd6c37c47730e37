"""Chunked SSD (Mamba-2's state-space scan) with its (Q, Q) work in Pallas.

Mamba-2's chunked SSD splits the sequence into chunks of Q positions.  Per
head, with ``cum`` the in-chunk cumulative sum of the log-decays dt·A (f32,
non-increasing) and h the state entering the chunk,

    y_i = sum_{j <= i} C_i·B_j · exp(cum_i - cum_j) · dt_j · x_j     (diagonal)
        + exp(cum_i) · C_i·h  +  D · x_i                            (read-out)
    h'  = exp(cum_last) · h + sum_j B_j ⊗ exp(cum_last - cum_j) · dt_j · x_j

``ssd`` computes y and the final state in three ``pallas_call``s:
``_per_chunk`` takes each chunk's own state (B_jᵀ times the decayed,
dt-scaled x_j), ``_recur`` the state entering each chunk (grid (batch,
head block, chunk), the f32 state held in VMEM across the chunks), and
``_fwd_kernel`` the diagonal block, the read-out and D·x over the grid
(batch, chunk, head block).  The backward mirrors them: ``_per_chunk``
takes the read-out's gradient of each entering state (C_iᵀ times
exp(cum_i)·dy_i), ``_recur_bwd`` runs the recurrence backwards, and
``_bwd_kernel`` recomputes the tiles and takes every other gradient.  No
(Q, Q) tile, and no f32 array of the shape of x, is written to HBM; XLA
keeps only the (B, H, S) decays and a few sums over heads.  The
``mamba_ssm`` Triton kernels split the work the same way
(``_chunk_state_fwd``, ``_state_passing_fwd``, ``_chunk_scan_fwd`` and
their backwards, with ``_chunk_scan_bwd_ddAcs_stable``).

Blocks.  x and y (B, S, E), with E = H·64 heads of 64 side by side in the
lanes, are read in blocks of ``head_block(H)`` heads, two heads to each 128
lanes; B and C (B, S, N) in whole-chunk blocks; the states entering each
chunk as (chunks, B, N, E); dt and cum as (B, H, S) rows (one head a
sublane, positions in the lanes) and once more as (B, H/hb, S, hb)
columns, since a tile needs cum_i down its rows and cum_j along its
columns.  All are read in place through the index maps: there is no
chunk-major copy.  C·Bᵀ is shared by every head (one group): it is computed
once per (batch, chunk), at the first head block, and kept in VMEM scratch
across the head-block axis.  A chunk is tiled in (128, 128) sub-blocks; the
sub-blocks above the diagonal are wholly masked and skipped, and only the
diagonal ones build a mask.  A grid step of the diagonal block loops over
its pairs of heads (``fori_loop``, the pair's 128 lanes sliced at run
time, each head's dt and cum rows and columns picked out by a masked
sum), so its forward and backward bodies are traced and lowered once and
not once a pair; ``_states_kernel``, one small matmul a pair, stays
unrolled.  Each head multiplies its tile by the 128 lanes of its pair (a
64-wide product takes the MXU as long) and keeps its own 64 lanes.

Numerics, as the published kernels: segment sums, decays and every ``exp``
are f32, the sums above the diagonal are set to −inf before ``exp`` (never
after: the sums there are positive and overflow); the tile
M = C·Bᵀ ∘ exp(seg) ∘ dt_j, the entering state and each chunk's scaled
inputs are cast to x's dtype for their matmuls, which accumulate in f32;
the states and their recurrence are f32; y is summed in f32 and stored in
x's dtype.

Backward of the tiles: with G = C·Bᵀ ∘ exp(seg), M = G ∘ dt_j, dM = dy·xᵀ
and T = dM ∘ G: dx = Mᵀ·dy + D·dy + (B·dh)·w, d(dt_j) = Σ_i T_ij (+ the
chunk state's term), dseg = T ∘ dt_j, d(cum_i) += Σ_j dseg_ij and
d(cum_j) −= Σ_i dseg_ij (the row and column sums taken in the kernel),
with the read-out's and the chunk state's terms of d(cum); and
d(C·Bᵀ) = Σ_h dM ∘ dt_j ∘ exp(seg), summed over the head blocks in VMEM
scratch with the read-out's dC and the chunk state's dB, so that at the
last head block dC = d(C·Bᵀ)·B + … and dB = d(C·Bᵀ)ᵀ·C + … are written
once.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NT = (((1,), (1,)), ((), ()))        # contract the last dims: a @ b.T
TN = (((0,), (0,)), ((), ()))        # contract the first dims: a.T @ b
BLK = 128                            # sub-block of a chunk
HEAD = 64                            # SSD head dim the lane pairing assumes
PAIR = 2 * HEAD                      # lanes of one pair of heads
CHUNKS = (128, 256)                  # chunk lengths the kernels tile
VMEM_LIMIT = 64 * 1024 * 1024        # of the v5e's 128 MiB
F32 = jnp.float32


def head_block(H: int, P: int) -> Optional[int]:
    """Heads a grid step takes, or ``None`` where the widths cannot be
    tiled: heads of 64 in pairs, and a (hb, Q) row block whose hb is a
    multiple of 8 or all H.  16 heads (1024 lanes) a step, not 8: half the
    grid steps, and the build that took 16 ran faster on a v5e at 80
    heads (PERF.md §6, PR 17)."""
    if P != HEAD or H % 2:
        return None
    return next((hb for hb in (16, 8) if H % hb == 0), H)


def _sl(i: int, n: int = BLK) -> slice:
    return slice(i * n, (i + 1) * n)


def _pair_lanes(pr):
    """The 128 lanes of the ``pr``-th pair of heads, ``pr`` a loop index."""
    return pl.ds(pl.multiple_of(pr * PAIR, PAIR), PAIR)


def _pick(v, h, axis: int):
    """Head ``h``'s (a loop index's) row (axis 0) or column (axis 1) of a
    block of all the step's heads: a masked sum, as a row or column cannot
    be sliced at a lane or sublane the kernel only knows at run time."""
    at = jax.lax.broadcasted_iota(jnp.int32, v.shape, axis) == h
    return jnp.sum(jnp.where(at, v, 0.0), axis=axis, keepdims=True)


def _head(dt_ref, cum_ref, cumc_ref, h, nb):
    """Head h's dt and cum rows (1, BLK) and cum columns (BLK, 1), one of
    each a sub-block."""
    return ([_pick(dt_ref[0, :, _sl(c)], h, 0) for c in range(nb)],
            [_pick(cum_ref[0, :, _sl(c)], h, 0) for c in range(nb)],
            [_pick(cumc_ref[0, 0, _sl(r), :], h, 1) for r in range(nb)])


def _seg(cum_i, cum_j, diag: bool):
    """(BLK, BLK) f32 log-decay from column j to row i; above the diagonal
    of a diagonal sub-block, −inf."""
    s = cum_i - cum_j
    if diag:
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(row >= col, s, -jnp.inf)
    return s


def _even():
    """(BLK, PAIR) mask of the first head's lanes of a pair."""
    return jax.lax.broadcasted_iota(jnp.int32, (BLK, PAIR), 1) < HEAD


def _head_sum(v, even, first: bool):
    """(BLK, 1) sum of ``v`` over one head's 64 lanes of its pair."""
    return jnp.sum(jnp.where(even if first else jnp.logical_not(even),
                             v, 0.0), axis=1, keepdims=True)


def _last(row):
    """(1, 1): the last lane of a (1, n) row."""
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == row.shape[1] - 1, row, 0.0), axis=1,
                   keepdims=True)


def _diag_tile(cb_scr, head, r, c):
    """(G, exp(seg), dt_j) of a head's (r, c) sub-block."""
    dt, cum, cumc = head
    decay = jnp.exp(_seg(cumc[r], cum[c], c == r))
    return cb_scr[_sl(r), _sl(c)] * decay, decay, dt[c]


# ------------------------------------------------------------------ forward
def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, cumc_ref, hin_ref,
                d_ref, y_ref, cb_scr):
    nb = cb_scr.shape[0] // BLK
    hb = dt_ref.shape[1]
    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _cb():
        cb_scr[...] = jax.lax.dot_general(c_ref[0], b_ref[0], NT,
                                          preferred_element_type=F32)

    even = _even()

    def pair(pr, carry):
        lanes = _pair_lanes(pr)
        heads = [_head(dt_ref, cum_ref, cumc_ref, 2 * pr + k, nb)
                 for k in (0, 1)]
        hin = hin_ref[0, 0, :, lanes].astype(dtype)      # (N, PAIR)
        dl = d_ref[:, lanes]                             # (1, PAIR)
        for r in range(nb):
            ys = []
            for head in heads:
                acc = jnp.zeros((BLK, PAIR), F32)
                for c in range(r + 1):
                    g, _, dt_j = _diag_tile(cb_scr, head, r, c)
                    acc += jnp.dot((g * dt_j).astype(dtype),
                                   x_ref[0, _sl(c), lanes],
                                   preferred_element_type=F32)
                ys.append(acc)
            e = jnp.where(even, jnp.exp(heads[0][2][r]),
                          jnp.exp(heads[1][2][r]))
            off = jnp.dot(c_ref[0, _sl(r)], hin, preferred_element_type=F32)
            y = (jnp.where(even, ys[0], ys[1]) + off * e
                 + x_ref[0, _sl(r), lanes].astype(F32) * dl)
            y_ref[0, _sl(r), lanes] = y.astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, hb // 2, pair, 0)


def _specs(Q, hb, N):
    """BlockSpecs on the grid (batch, chunk, head block): x-like (B, S, E);
    B and C (B, S, N); rows (B, H, S); columns (B, H/hb, S, hb); states
    (chunks, B, N, E); D as lanes (1, E); per-chunk lane sums (B, chunks,
    1, E)."""
    E_blk = hb * HEAD
    return dict(
        x=pl.BlockSpec((1, Q, E_blk), lambda b, c, g: (b, c, g)),
        bc=pl.BlockSpec((1, Q, N), lambda b, c, g: (b, c, 0)),
        row=pl.BlockSpec((1, hb, Q), lambda b, c, g: (b, g, c)),
        col=pl.BlockSpec((1, 1, Q, hb), lambda b, c, g: (b, g, c, 0)),
        state=pl.BlockSpec((1, 1, N, E_blk), lambda b, c, g: (c, b, 0, g)),
        lanes=pl.BlockSpec((1, E_blk), lambda b, c, g: (0, g)),
        lane_sum=pl.BlockSpec((1, 1, 1, E_blk),
                              lambda b, c, g: (b, c, 0, g)))


def _columns(t, hb):
    """(B, H, S) -> (B, H/hb, S, hb): each head block's values as columns."""
    B, H, S = t.shape
    return t.reshape(B, H // hb, hb, S).transpose(0, 1, 3, 2)


def _rows(t):
    """The inverse of ``_columns``."""
    B, G, S, hb = t.shape
    return t.transpose(0, 1, 3, 2).reshape(B, G * hb, S)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _lanes(t, E):
    """(..., H) -> (..., E): each head's value on its 64 lanes."""
    return jnp.repeat(t, E // t.shape[-1], axis=-1)


def _states_kernel(v_ref, m_ref, l_ref, out_ref):
    """out (N, hb·64) = mᵀ · (v ∘ l) of one chunk, l a weight per head and
    position."""
    hb = l_ref.shape[-1]
    even = jax.lax.broadcasted_iota(jnp.int32, (l_ref.shape[2], PAIR), 1
                                    ) < HEAD
    l = l_ref[0, 0]                                      # (Q, hb)
    m = m_ref[0]                                         # (Q, N)
    # unrolled, unlike the other kernels: one small matmul a pair, which a
    # loop made 1.7x slower on a v5e (PERF.md §6, PR 17)
    for pr in range(hb // 2):
        lanes = _sl(pr, PAIR)
        lv = jnp.where(even, l[:, 2 * pr:2 * pr + 1],
                       l[:, 2 * pr + 1:2 * pr + 2])
        vs = (v_ref[0, :, lanes].astype(F32) * lv).astype(m.dtype)
        out_ref[0, 0, :, lanes] = jax.lax.dot_general(
            m, vs, TN, preferred_element_type=F32)


def _per_chunk(v, m, l, *, chunk, interpret):
    """Σ_i m_iᵀ ⊗ l_i·v_i over each chunk, (chunks, B, N, E) f32: v (B, S,
    E), m (B, S, N), l (B, H, S) f32.  Each chunk's own state (v = x,
    m = B, l = exp(cum_last − cum)·dt), and the read-out's gradient of the
    state entering it (v = dy, m = C, l = exp(cum))."""
    B, S, E = v.shape
    H, N = l.shape[1], m.shape[-1]
    hb = head_block(H, E // H)
    sp = _specs(chunk, hb, N)
    return pl.pallas_call(
        _states_kernel,
        grid=(B, S // chunk, H // hb),
        in_specs=[sp["x"], sp["bc"], sp["col"]],
        out_specs=sp["state"],
        out_shape=jax.ShapeDtypeStruct((S // chunk, B, N, E), F32),
        compiler_params=_params(),
        interpret=interpret,
    )(v, m, _columns(l, hb))


def _decays(dt, cum, E, chunk):
    """w = exp(cum_last − cum)·dt (B, H, S), each input's decay to its
    chunk's end times dt, and exp(cum_last), the decay of the entering
    state across each chunk, on the lanes (chunks, B, 1, E)."""
    B, H, S = dt.shape
    cum = cum.reshape(B, H, S // chunk, chunk)
    w = jnp.exp(cum[..., -1:] - cum).reshape(B, H, S) * dt
    return w, _lanes(jnp.exp(cum[..., -1]).transpose(2, 0, 1), E)[:, :, None]


def _recur_kernel(s_ref, a_ref, hin_ref, final_ref, h_scr):
    """Grid (batch, head block, chunk): the state entering chunk c, then
    h ← a_c·h + s_c, kept in VMEM across the chunks."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    h = h_scr[...]
    hin_ref[0, 0] = h
    h_scr[...] = h * a_ref[0, 0] + s_ref[0, 0]

    @pl.when(c == pl.num_programs(2) - 1)
    def _finish():
        final_ref[0] = h_scr[...]


def _recur_bwd_kernel(dh_ref, h_ref, a_ref, dfinal_ref, ds_ref, da_ref,
                      g_scr):
    """The recurrence backwards, chunk c = chunks − 1 − the grid's: g, the
    gradient of the state after c, is that of c's own state; Σ_n g·h_c that
    of its decay; g ← a_c·g + the read-out's gradient of h_c."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        g_scr[...] = dfinal_ref[0]

    g = g_scr[...]
    ds_ref[0, 0] = g
    da_ref[0, 0] = jnp.sum(g * h_ref[0, 0], axis=0, keepdims=True)
    g_scr[...] = g * a_ref[0, 0] + dh_ref[0, 0]


def _recur_specs(nc, N, E_blk, reverse: bool):
    """(per-chunk state, per-chunk lanes, final state) BlockSpecs on the
    grid (batch, head block, chunk), the chunks taken last to first when
    ``reverse``."""
    def at(c):
        return nc - 1 - c if reverse else c
    return (pl.BlockSpec((1, 1, N, E_blk), lambda b, g, c: (at(c), b, 0, g)),
            pl.BlockSpec((1, 1, 1, E_blk), lambda b, g, c: (at(c), b, 0, g)),
            pl.BlockSpec((1, N, E_blk), lambda b, g, c: (b, 0, g)))


def _recur(states, decay, hb, *, interpret):
    """The state entering each chunk (chunks, B, N, E) and the final one
    (B, N, E), all f32."""
    nc, B, N, E = states.shape
    st, ln, fin = _recur_specs(nc, N, hb * HEAD, reverse=False)
    h_in, final = pl.pallas_call(
        _recur_kernel,
        grid=(B, E // (hb * HEAD), nc),
        in_specs=[st, ln],
        out_specs=[st, fin],
        out_shape=[jax.ShapeDtypeStruct(states.shape, F32),
                   jax.ShapeDtypeStruct((B, N, E), F32)],
        scratch_shapes=[pltpu.VMEM((N, hb * HEAD), F32)],
        compiler_params=_params(),
        interpret=interpret,
    )(states, decay)
    return h_in, final


def _recur_bwd(dh, h_in, decay, dfinal, hb, *, interpret):
    """The gradients of each chunk's own state (chunks, B, N, E) and of its
    decay on the lanes (chunks, B, 1, E)."""
    nc, B, N, E = dh.shape
    st, ln, fin = _recur_specs(nc, N, hb * HEAD, reverse=True)
    return pl.pallas_call(
        _recur_bwd_kernel,
        grid=(B, E // (hb * HEAD), nc),
        in_specs=[st, st, ln, fin],
        out_specs=[st, ln],
        out_shape=[jax.ShapeDtypeStruct(dh.shape, F32),
                   jax.ShapeDtypeStruct(decay.shape, F32)],
        scratch_shapes=[pltpu.VMEM((N, hb * HEAD), F32)],
        compiler_params=_params(),
        interpret=interpret,
    )(dh, h_in, decay, dfinal)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _forward(x, b, c, dt, cum, d, *, chunk, interpret):
    B, S, E = x.shape
    H, N = dt.shape[1], b.shape[-1]
    hb = head_block(H, E // H)
    w, decay = _decays(dt, cum, E, chunk)
    h_in, final = _recur(_per_chunk(x, b, w, chunk=chunk,
                                    interpret=interpret), decay, hb,
                         interpret=interpret)
    sp = _specs(chunk, hb, N)
    y = pl.pallas_call(
        _fwd_kernel,
        grid=(B, S // chunk, H // hb),
        in_specs=[sp["x"], sp["bc"], sp["bc"], sp["row"], sp["row"],
                  sp["col"], sp["state"], sp["lanes"]],
        out_specs=sp["x"],
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((chunk, chunk), F32)],
        compiler_params=_params(),
        interpret=interpret,
    )(x, b, c, dt, cum, _columns(cum, hb), h_in,
      _lanes(d, E)[None])
    return y, final, h_in


# ----------------------------------------------------------------- backward
def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, cumc_ref, dtc_ref,
                dy_ref, hin_ref, dst_ref, d_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dcum_ref, ddtc_ref,
                dcumc_ref, dd_ref, cb_scr, dcb_scr, db_scr, dc_scr):
    nb = cb_scr.shape[0] // BLK
    hb = dt_ref.shape[1]
    g = pl.program_id(2)
    dtype = x_ref.dtype

    @pl.when(g == 0)
    def _init():
        cb_scr[...] = jax.lax.dot_general(c_ref[0], b_ref[0], NT,
                                          preferred_element_type=F32)
        dcb_scr[...] = jnp.zeros_like(dcb_scr)
        db_scr[...] = jnp.zeros_like(db_scr)
        dc_scr[...] = jnp.zeros_like(dc_scr)

    even = _even()
    mine = (even, jnp.logical_not(even))
    col_of = jax.lax.broadcasted_iota(jnp.int32, (BLK, hb), 1)
    last_row = jax.lax.broadcasted_iota(jnp.int32, (BLK, hb), 0) == BLK - 1
    row_of = jax.lax.broadcasted_iota(jnp.int32, (hb, BLK), 0)

    def to_col(h, v):                                    # (BLK,1) -> (BLK,hb)
        return jnp.where(col_of == h, v, 0.0)

    def pair(pr, carry):
        # ddtc, dcumc: (BLK, hb) columns a sub-block; ddt, dcum rows:
        # (hb, BLK) a sub-block, each head's row set once
        ddtc, dcumc, ddt_rows, dcum_rows = (list(t) for t in carry)
        lanes = _pair_lanes(pr)
        hs = (2 * pr, 2 * pr + 1)
        heads = [_head(dt_ref, cum_ref, cumc_ref, h, nb) for h in hs]
        dtc = [[_pick(dtc_ref[0, 0, _sl(r), :], h, 1) for r in range(nb)]
               for h in hs]
        # the read-out, D·x and the chunk's own state, both heads at once;
        # each head's d(cum_i) terms are kept as a (BLK, PAIR) tile to be
        # summed over its lanes with the diagonal block's row sums
        hin = hin_ref[0, 0, :, lanes].astype(dtype)      # (N, PAIR)
        dst = dst_ref[0, 0, :, lanes].astype(dtype)      # (N, PAIR)
        dl = d_ref[:, lanes]                             # (1, PAIR)
        last = [_last(head[1][nb - 1]) for head in heads]
        dd = jnp.zeros((1, PAIR), F32)
        dlast = [jnp.zeros((1, 1), F32) for _ in hs]
        dx = ([], [])
        dcum_i = ([], [])
        for r in range(nb):
            x = x_ref[0, _sl(r), lanes]
            xf = x.astype(F32)
            dy = dy_ref[0, _sl(r), lanes].astype(F32)
            cum_i = [head[2][r] for head in heads]
            e = jnp.where(even, jnp.exp(cum_i[0]), jnp.exp(cum_i[1]))
            off = jnp.dot(c_ref[0, _sl(r)], hin, preferred_element_type=F32)
            dc_scr[_sl(r), :] += jax.lax.dot_general(
                (dy * e).astype(dtype), hin, NT, preferred_element_type=F32)
            dd += jnp.sum(dy * xf, axis=0, keepdims=True)
            to_end = [jnp.exp(last[k] - cum_i[k]) for k in (0, 1)]
            w = [to_end[k] * dtc[k][r] for k in (0, 1)]
            wl = jnp.where(even, w[0], w[1])
            dxs = jnp.dot(b_ref[0, _sl(r)], dst, preferred_element_type=F32)
            db_scr[_sl(r), :] += jax.lax.dot_general(
                (xf * wl).astype(dtype), dst, NT, preferred_element_type=F32)
            dx_extra = dy * dl + dxs * wl
            dw_x = dxs * xf
            q = dy * off * e - dw_x * wl
            for k in (0, 1):
                dx[k].append(dx_extra)
                dcum_i[k].append(jnp.where(mine[k], q, 0.0))
                dw = _head_sum(dw_x, even, k == 0)
                ddtc[r] += to_col(hs[k], dw * to_end[k])
                dlast[k] += jnp.sum(dw * w[k], axis=0, keepdims=True)
        for k in (0, 1):
            dcumc[nb - 1] += jnp.where(
                jnp.logical_and(last_row, col_of == hs[k]), dlast[k], 0.0)
        dd_ref[0, 0, :, lanes] = dd
        # the diagonal block, head by head
        for k in (0, 1):
            ddt = [jnp.zeros((1, BLK), F32) for _ in range(nb)]
            dcum = [jnp.zeros((1, BLK), F32) for _ in range(nb)]
            for r in range(nb):
                dy = dy_ref[0, _sl(r), lanes]
                dy_h = jnp.where(mine[k], dy, jnp.zeros_like(dy))
                rows = dcum_i[k][r]              # (BLK, BLK): PAIR == BLK
                for c in range(r + 1):
                    gt, decay, dt_j = _diag_tile(cb_scr, heads[k], r, c)
                    dm = jax.lax.dot_general(dy_h, x_ref[0, _sl(c), lanes],
                                             NT, preferred_element_type=F32)
                    dx[k][c] += jax.lax.dot_general(
                        (gt * dt_j).astype(dtype), dy, TN,
                        preferred_element_type=F32)
                    t = dm * gt
                    ddt[c] += jnp.sum(t, axis=0, keepdims=True)
                    dseg = t * dt_j
                    dcum[c] -= jnp.sum(dseg, axis=0, keepdims=True)
                    rows += dseg
                    dcb_scr[_sl(r), _sl(c)] += dm * dt_j * decay
                dcumc[r] += to_col(hs[k], jnp.sum(rows, axis=1,
                                                  keepdims=True))
            for c in range(nb):
                ddt_rows[c] = jnp.where(row_of == hs[k], ddt[c], ddt_rows[c])
                dcum_rows[c] = jnp.where(row_of == hs[k], dcum[c],
                                         dcum_rows[c])
        for c in range(nb):
            dx_ref[0, _sl(c), lanes] = jnp.where(
                even, dx[0][c], dx[1][c]).astype(dx_ref.dtype)
        return tuple(ddtc), tuple(dcumc), tuple(ddt_rows), tuple(dcum_rows)

    cols = tuple(jnp.zeros((BLK, hb), F32) for _ in range(nb))
    rows = tuple(jnp.zeros((hb, BLK), F32) for _ in range(nb))
    ddtc, dcumc, ddt_rows, dcum_rows = jax.lax.fori_loop(
        0, hb // 2, pair, (cols, cols, rows, rows))
    for c in range(nb):
        ddtc_ref[0, 0, _sl(c), :] = ddtc[c]
        dcumc_ref[0, 0, _sl(c), :] = dcumc[c]
        ddt_ref[0, :, _sl(c)] = ddt_rows[c]
        dcum_ref[0, :, _sl(c)] = dcum_rows[c]

    @pl.when(g == pl.num_programs(2) - 1)
    def _finish():
        dcb = dcb_scr[...].astype(dtype)
        dc_ref[0] = (jnp.dot(dcb, b_ref[0], preferred_element_type=F32)
                     + dc_scr[...]).astype(dc_ref.dtype)
        db_ref[0] = (jax.lax.dot_general(dcb, c_ref[0], TN,
                                         preferred_element_type=F32)
                     + db_scr[...]).astype(db_ref.dtype)


def _backward(x, b, c, dt, cum, d, h_in, dy, dfinal, *, chunk, interpret):
    B, S, E = x.shape
    H, N, nc = dt.shape[1], b.shape[-1], S // chunk
    hb = head_block(H, E // H)
    # the read-out's gradient of each entering state, then the recurrence
    # backwards: d(state after c) carries into c's own state and, decayed,
    # into the state entering c
    dh = _per_chunk(dy, c, jnp.exp(cum), chunk=chunk, interpret=interpret)
    _, decay = _decays(dt, cum, E, chunk)
    dstates, da = _recur_bwd(dh, h_in, decay, dfinal, hb,
                             interpret=interpret)
    sp = _specs(chunk, hb, N)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=F32)
    dx, db, dc, ddt, dcum, ddtc, dcumc, dd = pl.pallas_call(
        _bwd_kernel,
        grid=(B, nc, H // hb),
        in_specs=[sp["x"], sp["bc"], sp["bc"], sp["row"], sp["row"],
                  sp["col"], sp["col"], sp["x"], sp["state"], sp["state"],
                  sp["lanes"]],
        out_specs=[sp["x"], sp["bc"], sp["bc"], sp["row"], sp["row"],
                   sp["col"], sp["col"], sp["lane_sum"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype),
                   f32(dt.shape), f32(cum.shape),
                   f32((B, H // hb, S, hb)), f32((B, H // hb, S, hb)),
                   f32((B, nc, 1, E))],
        scratch_shapes=[pltpu.VMEM((chunk, chunk), F32),
                        pltpu.VMEM((chunk, chunk), F32),
                        pltpu.VMEM((chunk, N), F32),
                        pltpu.VMEM((chunk, N), F32)],
        compiler_params=_params(),
        interpret=interpret,
    )(x, b, c, dt, cum, _columns(cum, hb), _columns(dt, hb), dy,
      h_in, dstates, _lanes(d, E)[None])
    # the decay across each chunk is exp(cum at its last position)
    a = decay[:, :, 0, ::E // H]                            # (nc, B, H)
    dlast = (da[:, :, 0].reshape(nc, B, H, E // H).sum(-1) * a)
    dcum = (dcum + _rows(dcumc)).reshape(B, H, nc, chunk)
    dcum = dcum.at[..., -1].add(dlast.transpose(1, 2, 0))
    dd = dd.sum((0, 1, 2)).reshape(H, E // H).sum(-1)
    return (dx, db, dc, ddt + _rows(ddtc), dcum.reshape(B, H, S), dd)


# -------------------------------------------------------------- custom vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd(x, b, c, dt, cum, d, chunk, interpret):
    y, final, _ = _forward(x, b, c, dt, cum, d, chunk=chunk,
                           interpret=interpret)
    return y, final


def _ssd_fwd(x, b, c, dt, cum, d, chunk, interpret):
    y, final, h_in = _forward(x, b, c, dt, cum, d, chunk=chunk,
                              interpret=interpret)
    return (y, final), (x, b, c, dt, cum, d, h_in)


def _ssd_bwd(chunk, interpret, res, cts):
    dy, dfinal = cts
    return _backward(*res, dy, dfinal, chunk=chunk, interpret=interpret)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x: jax.Array, b: jax.Array, c: jax.Array, dt: jax.Array,
        cum: jax.Array, d: jax.Array, *, chunk: int, interpret: bool):
    """The chunked SSD from a zero state, differentiable in every operand.

    x: (B, S, E) with E = H·64; b, c: (B, S, N) in x's dtype; dt and cum:
    (B, H, S) f32, cum the cumulative sum of dt·A restarting at each chunk;
    d: (H,) f32.  S must be a multiple of ``chunk``, ``chunk`` one of
    ``CHUNKS`` and ``head_block(H, 64)`` not ``None``.  Returns y (B, S, E)
    in x's dtype and the final state (B, N, E) f32.  ``interpret=True``
    executes the kernels in Python on the CPU.
    """
    B, S, E = x.shape
    H = dt.shape[1]
    if chunk not in CHUNKS or S % chunk or head_block(H, E // H) is None:
        raise ValueError(f"no tiling for S={S}, chunk={chunk}, H={H}, "
                         f"E={E}")
    return _ssd(x, b, c, dt, cum, d, chunk, interpret)

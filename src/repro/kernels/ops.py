"""Where, and how, the models' Pallas kernels run.

This module makes the one choice of backend for the whole program
(``on_tpu``), and for each kernel a model calls it gives

  * ``<kernel>_reason(...)``: why the kernel cannot run here (the backend,
    the kernel's own limits, or a mesh that splits the heads it holds
    together), or ``None`` where it runs;
  * the kernel entry itself, which takes the model's own layout, brings
    it to the kernel's, and runs per shard under ``shard_map`` on a mesh
    of more than one device;
  * ``REMAT_SAVED``: the names of the kernels' outputs that the layers'
    remat keeps for the backward.

The models keep their XLA paths and ask for the reason first.  The kernels
run through the Pallas interpreter on the CPU backend (``_interpret``) and
compiled on every other, so a test can take the backend for a TPU
(``on_tpu``) and still run them.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.sharding import kernel_spec
from . import causal_conv as _cc
from . import flash_attention as _fa
from . import ssd_chunk as _sc


# the flash forward's o and lse: kept, the backward does not run it again
REMAT_SAVED = _fa.SAVED


def on_tpu() -> bool:
    """Whether the kernels' dispatch takes the TPU paths."""
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _per_shard(run, args, in_specs, out_specs):
    """``run(*args)`` per shard under ``shard_map``, or as it is where
    ``out_specs`` is ``None`` (no mesh of more than one device)."""
    if out_specs is None:
        return run(*args)
    return jax.shard_map(run, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)(*args)


def _rows(shape) -> Optional[PartitionSpec]:
    """The batch split of a (B, S, E) operand on a mesh of more than one
    device; ``None`` without one."""
    spec = kernel_spec(shape, "batch", None, "heads")
    return None if spec is None else PartitionSpec(spec[0])


def _heads_split(shape) -> bool:
    """Whether the mesh splits the heads (last) axis of a (B, S, E) x."""
    spec = kernel_spec(shape, "batch", None, "heads")
    return spec is not None and spec[2] is not None


# ------------------------------------------------------------------ flash
def _flash_specs(q, k):
    """(q spec, k spec) of (B, S, H, hd) operands on a mesh of more than
    one device; ``(None, None)`` without one."""
    return tuple(kernel_spec(x.shape, "batch", None, "heads", None)
                 for x in (q, k))


def flash_attention_reason(q, k, v, *, window: Optional[int] = None,
                           q_offset: int = 0) -> Optional[str]:
    """Why ``flash_attention`` cannot take q (B, Sq, H, hd) and k, v
    (B, Sk, KH, hd), or ``None`` where it runs."""
    if not on_tpu():
        return "backend"
    if window is not None:
        return "window"
    if q_offset:
        return "q_offset"
    if q.shape[1] != k.shape[1]:
        return "kv_length"
    if not q.shape[3] == k.shape[3] == v.shape[3]:
        return "head_dim"
    if not _fa.backward_fits(q.shape[2] // k.shape[2], q.shape[1],
                             q.shape[3]):
        return "vmem"
    qs, ks = _flash_specs(q, k)
    if qs is not None and qs[2] != ks[2]:
        return "heads_sharding"     # the GQA map would cross shards
    return None


@functools.partial(jax.jit, static_argnames=("causal",))
def _flash(q: jax.Array, k: jax.Array, v: jax.Array, *,
           causal: bool) -> jax.Array:
    """q: (B, H, S, D); k/v: (B, KH, S, D).  Pads S to the kernel's block
    multiple (padded keys are masked)."""
    S = q.shape[2]
    Sp = _fa.padded_len(S)
    if Sp != S:
        pad = ((0, 0), (0, 0), (0, Sp - S), (0, 0))
        q, k, v = (jnp.pad(x, pad) for x in (q, k, v))
    out = _fa.flash_attention(q, k, v, causal=causal, kv_len=S,
                              interpret=_interpret())
    return out[:, :, :S]


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True) -> jax.Array:
    """The flash kernel on q (B, S, H, hd) and k, v (B, S, KH, hd) ->
    (B, S, H, hd), differentiable in q, k and v."""
    def run(q, k, v):
        o = _flash(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                   v.transpose(0, 2, 1, 3), causal=causal)
        return o.transpose(0, 2, 1, 3)

    qs, ks = _flash_specs(q, k)
    return _per_shard(run, (q, k, v), (qs, ks, ks), qs)


# ------------------------------------------------------------ chunked ssd
def ssd_reason(shape, heads: int, chunk: int) -> Optional[str]:
    """Why ``ssd`` cannot take x of ``shape`` (B, S, E) in ``heads`` heads
    at ``chunk``, or ``None`` where it runs."""
    if not on_tpu():
        return "backend"
    if chunk not in _sc.CHUNKS:
        return "chunk"
    if _sc.head_block(heads, shape[2] // heads) is None:
        return "width"
    if _heads_split(shape):
        return "heads_sharding"    # a head block would cross shards
    return None


@functools.partial(jax.jit, static_argnames=("chunk",))
def _ssd(x, b, c, dt, cum, d, *, chunk: int):
    return _sc.ssd(x, b, c, dt, cum, d, chunk=chunk, interpret=_interpret())


def ssd(D: jax.Array, X, Bm, Cm, dt, dA, chunk: int):
    """The chunked SSD through the kernels of ``ssd_chunk.py``, in the
    model's layout: X (B, S, H, P), Bm/Cm (B, S, N), dt and the log-decays
    dA (B, S, H) f32, D (H,), S a multiple of ``chunk``.  Returns the final
    f32 state (B, H, P, N) and y (B, S, H·P) in X's dtype."""
    B_, S, H, P = X.shape
    N = Bm.shape[-1]
    dt, dA = dt.transpose(0, 2, 1), dA.transpose(0, 2, 1)   # (B,H,S)
    cum = jnp.cumsum(dA.reshape(B_, H, S // chunk, chunk), axis=-1)
    x = X.reshape(B_, S, H * P)
    rows = _rows(x.shape)
    y, state = _per_shard(functools.partial(_ssd, chunk=chunk),
                          (x, Bm, Cm, dt, cum.reshape(B_, H, S), D),
                          (rows,) * 5 + (PartitionSpec(),), rows)
    return state.reshape(B_, N, H, P).transpose(0, 2, 3, 1), y


# ------------------------------------------------------- causal conv silu
def conv_silu_reason(shape) -> Optional[str]:
    """Why ``conv_silu`` cannot run beside an SSD over x of ``shape``
    (B, S, E), or ``None`` where it runs: it takes any length (its rows
    are padded to 8) and width."""
    if not on_tpu():
        return "backend"
    if _heads_split(shape):
        return "heads_sharding"    # the kernel's rows hold every channel
    return None


@jax.jit
def _conv_silu(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    return _cc.conv_silu(x, w, b, interpret=_interpret())


def conv_silu(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """SiLU(causal depthwise conv of x + b) through ``causal_conv.py``: x
    (B, S, C), w (K, C), b (C,) -> (B, S, C) in x's dtype."""
    S = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (0, -S % 8), (0, 0)))
    rows = _rows(xp.shape)
    y = _per_shard(lambda x, wb: _conv_silu(x, *wb), (xp, (w, b)),
                   (rows, PartitionSpec()), rows)
    return y[:, :S]

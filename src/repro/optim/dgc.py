"""Deep Gradient Compression (Lin et al., paper §5.2 / Algorithm 12).

Top-k gradient sparsification with local error feedback: each step transmits
only the largest-magnitude ``ratio`` fraction of gradient entries; the residual
accumulates locally and is added back next step.  The Daydream what-if
(``core/whatif.py::what_if_dgc``) predicts its efficacy; this module is the
runnable implementation the prediction can be validated against.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class DGCState:
    residual: Any      # error-feedback accumulator (same tree as grads)


def dgc_init(grads_like) -> DGCState:
    return DGCState(residual=jax.tree.map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads_like))


def dgc_compress(g: jax.Array, ratio: float
                 ) -> Tuple[jax.Array, jax.Array]:
    """Dense top-|k| selection on one leaf: returns (values, int32 indices).

    k = max(1, round(ratio * size)).  Ties resolve arbitrarily (jax.lax.top_k).
    """
    flat = g.reshape(-1).astype(jnp.float32)
    k = max(1, int(round(ratio * flat.size)))
    vals, idx = jax.lax.top_k(jnp.abs(flat), k)
    return flat[idx], idx.astype(jnp.int32)


def dgc_decompress(values: jax.Array, idx: jax.Array, shape) -> jax.Array:
    size = 1
    for d in shape:
        size *= d
    out = jnp.zeros((size,), jnp.float32).at[idx].set(values)
    return out.reshape(shape)


def dgc_step(grads, state: DGCState, ratio: float = 0.01
             ) -> Tuple[Any, DGCState]:
    """One DGC round on a gradient tree: returns (sparse-equivalent dense
    gradients as transmitted, new state with residuals)."""
    def leaf(g, r):
        acc = g.astype(jnp.float32) + r
        vals, idx = dgc_compress(acc, ratio)
        sent = dgc_decompress(vals, idx, acc.shape)
        return sent.astype(g.dtype), acc - sent

    out = jax.tree.map(leaf, grads, state.residual)
    sent = jax.tree.map(lambda t: t[0], out,
                        is_leaf=lambda x: isinstance(x, tuple))
    resid = jax.tree.map(lambda t: t[1], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    return sent, DGCState(residual=resid)

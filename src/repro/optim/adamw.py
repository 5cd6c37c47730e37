"""AdamW with f32 moments over (possibly bf16) params — pure pytree functions.

Spec-mode aware: ``init`` over a SpecLeaf tree yields SpecLeaf moments with the
same logical sharding, so the dry-run can lower ``train_step`` with the full
(params, opt_state) structure and zero allocation.

``apply`` updates leaf by leaf (``tree_map``), which XLA fuses.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple, Union

import jax
import jax.numpy as jnp

from repro.models.paramdecl import SpecLeaf

Schedule = Union[float, Callable[[jax.Array], jax.Array]]


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(l.astype(jnp.float32)))
              for l in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves)) if leaves else jnp.zeros((), jnp.float32)


def _f32_like(tree):
    def leaf(l):
        if isinstance(l, SpecLeaf):
            return SpecLeaf(l.shape, jnp.dtype(jnp.float32), l.logical)
        return jnp.zeros(l.shape, jnp.float32)
    return jax.tree.map(leaf, tree, is_leaf=lambda x: isinstance(x, SpecLeaf))


@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: Schedule = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0

    # ------------------------------------------------------------------ init
    def init(self, params) -> Dict[str, Any]:
        leaves = jax.tree.leaves(params,
                                 is_leaf=lambda x: isinstance(x, SpecLeaf))
        spec_mode = leaves and isinstance(leaves[0], SpecLeaf)
        scalar = (lambda: SpecLeaf((), jnp.dtype(jnp.float32), ())) if \
            spec_mode else (lambda: jnp.zeros((), jnp.float32))
        count = (SpecLeaf((), jnp.dtype(jnp.int32), ()) if spec_mode
                 else jnp.zeros((), jnp.int32))
        return {"m": _f32_like(params), "v": _f32_like(params),
                "count": count, "gnorm": scalar()}

    # ---------------------------------------------------------------- update
    def _lr(self, count: jax.Array) -> jax.Array:
        if callable(self.lr):
            return jnp.asarray(self.lr(count), jnp.float32)
        return jnp.asarray(self.lr, jnp.float32)

    def apply(self, grads, state, params) -> Tuple[Any, Dict[str, Any]]:
        count = state["count"] + 1
        gnorm = global_norm(grads)
        scale = jnp.where(
            gnorm > self.grad_clip, self.grad_clip / jnp.maximum(gnorm, 1e-12),
            1.0) if self.grad_clip else jnp.ones((), jnp.float32)
        lr = self._lr(count)
        c1 = 1.0 - self.b1 ** count.astype(jnp.float32)
        c2 = 1.0 - self.b2 ** count.astype(jnp.float32)

        def upd(p, g, m, v):
            g = g.astype(jnp.float32) * scale
            m = self.b1 * m + (1 - self.b1) * g
            v = self.b2 * v + (1 - self.b2) * jnp.square(g)
            step = (m / c1) / (jnp.sqrt(v / c2) + self.eps)
            step = step + self.weight_decay * p.astype(jnp.float32)
            newp = (p.astype(jnp.float32) - lr * step).astype(p.dtype)
            return newp, m, v

        out = jax.tree.map(upd, params, grads, state["m"], state["v"])
        # unzip the (p, m, v) leaf tuples
        newp = jax.tree.map(lambda t: t[0], out,
                            is_leaf=lambda x: isinstance(x, tuple))
        newm = jax.tree.map(lambda t: t[1], out,
                            is_leaf=lambda x: isinstance(x, tuple))
        newv = jax.tree.map(lambda t: t[2], out,
                            is_leaf=lambda x: isinstance(x, tuple))
        return newp, {"m": newm, "v": newv, "count": count, "gnorm": gnorm}

    @staticmethod
    def last_grad_norm(state) -> jax.Array:
        return state["gnorm"]


def adamw(lr: Schedule = 3e-4, **kw) -> AdamW:
    return AdamW(lr=lr, **kw)

"""Pure-jnp oracle of the flash kernel (the allclose ground truth)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True) -> jax.Array:
    """q: (B, H, S, D); k/v: (B, KH, S, D) — naive full-score attention."""
    B, H, S, D = q.shape
    KH = k.shape[1]
    G = H // KH
    kf = jnp.repeat(k, G, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, G, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kf)
    s = s / math.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vf).astype(q.dtype)

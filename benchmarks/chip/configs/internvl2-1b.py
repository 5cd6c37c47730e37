"""Plain reference of the ``internvl2-1b`` language model as the benchmark
trains it: the Qwen2 decoder (pre-norm RMSNorm, grouped-query attention
with rotary positions, SwiGLU MLP) behind 256 projected patch embeddings,
untied unembedding, next-token cross entropy over the text positions.

It follows the published description in f32 and writes every step out as
the formula reads: full causal softmax, one query-head group at a time so
that it fits, no chunked streaming.  ``model`` is the configuration
file's ``model`` block.
"""

import math

import jax
import jax.numpy as jnp

from lib.plain import ce_mean, rmsnorm

F32 = jnp.float32


def _rope(x, theta):
    """x: (B, S, H, hd); rotate the two halves of each head."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(num, q, k, v):
    """Causal softmax attention; q (B, S, G, hd) share one k, v (B, S, hd)."""
    S, hd = q.shape[1], q.shape[-1]
    s = num.einsum("bqgk,bsk->bgqs", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return num.einsum("bgqs,bsk->bqgk", p, v)


def loss(num, p, batch, model):
    n_heads, n_kv = model["n_heads"], model["n_kv_heads"]
    group = n_heads // n_kv
    prefix = num.einsum("bpd,de->bpe", batch["patch_embeds"], p["connector"])
    text = jnp.take(p["embed"]["table"].astype(F32), batch["tokens"], axis=0)
    x = jnp.concatenate([prefix, text], axis=1)

    @jax.checkpoint
    def layer(x, lp):
        h = rmsnorm(x, lp["ln1"]["scale"])
        a = lp["attn"]
        q = _rope(num.einsum("bsd,dhk->bshk", h, a["wq"]), model["rope_theta"])
        k = _rope(num.einsum("bsd,dhk->bshk", h, a["wk"]), model["rope_theta"])
        v = num.einsum("bsd,dhk->bshk", h, a["wv"])
        B, S = q.shape[:2]
        qg = q.reshape(B, S, n_kv, group, -1)
        heads = jax.lax.map(
            jax.checkpoint(lambda i: _attention(num, qg[:, :, i], k[:, :, i],
                                                v[:, :, i])),
            jnp.arange(n_kv))                       # (n_kv, B, S, G, hd)
        o = heads.transpose(1, 2, 0, 3, 4).reshape(B, S, n_heads, -1)
        x = x + num.einsum("bshk,hkd->bsd", o, a["wo"])
        h = rmsnorm(x, lp["ln2"]["scale"])
        m = lp["mlp"]
        up = num.einsum("bsd,df->bsf", h, m["w_up"])
        gate = jax.nn.silu(num.einsum("bsd,df->bsf", h, m["w_gate"]))
        return x + num.einsum("bsf,fd->bsd", gate * up, m["w_down"]), None

    x, _ = jax.lax.scan(layer, x, p["blocks"])
    h = rmsnorm(x, p["final_norm"]["scale"])[:, model["n_patches"]:]
    return ce_mean(num, h, p["unembed"]["table"], batch["labels"])

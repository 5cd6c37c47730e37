"""Attention blocks: GQA, MLA (DeepSeek-V2), local-window, and decode paths.

Training/prefill attention goes through ``attend``, which asks
``repro.kernels.ops`` whether the Pallas flash kernel can run
(``ops.flash_attention_reason``): on a TPU, causal or full self-attention
with one head dim whose backward fits VMEM runs it (``ops.flash_attention``,
forward and backward, score tiles in VMEM, per shard under a mesh);
everything else (the CPU, local windows, MLA's 192/128 heads,
cross-attention, prefill continuation) runs ``chunked_attention``, a
``lax.scan`` over KV blocks with streaming softmax (same math, same
oracle).  Each dispatch writes one ``attn.dispatch`` record to
``repro.obs`` as it is traced.

Decode attention reads the KV cache (one new token per step).  MLA decode uses
the *absorbed* formulation: queries are projected into the compressed KV space
so the cache stays (S, kv_lora + rope_dim) per token — the whole point of MLA.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ops
from repro.sharding import shard, use_weight
from .paramdecl import normal_param, zeros_param, ones_param, split_keys

Params = Dict[str, Any]

NEG_INF = -2.0 ** 30   # mask value safe in bf16 accumulation


# --------------------------------------------------------------------- rope
def rope_angles(positions: jax.Array, head_dim: int, theta: float = 10000.0
                ) -> Tuple[jax.Array, jax.Array]:
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs     # (..., half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, hd); cos/sin: (S, hd/2) or (B, S, hd/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        c = cos[None, :, None, :].astype(x.dtype)
        s = sin[None, :, None, :].astype(x.dtype)
    else:
        c = cos[:, :, None, :].astype(x.dtype)
        s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


# ------------------------------------------------- flash-style core (train)
def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      *, causal: bool = True, window: Optional[int] = None,
                      chunk: int = 1024, q_offset: int = 0) -> jax.Array:
    """Streaming-softmax attention over KV chunks.

    q: (B, Sq, H, hd);  k, v: (B, Sk, K, hd) with H % K == 0 (GQA).
    ``window`` limits attention to the last ``window`` keys (local attention).
    ``q_offset`` is the absolute position of q[0] (for prefill continuation).
    Returns (B, Sq, H, hd).
    """
    B, Sq, H, hd = q.shape
    Sk, K, hd_v = k.shape[1], k.shape[2], v.shape[3]
    G = H // K                                     # queries per kv head
    scale = 1.0 / math.sqrt(hd)
    chunk = min(chunk, Sk)
    nchunk = (Sk + chunk - 1) // chunk
    pad = nchunk * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, nchunk, chunk, K, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nchunk, chunk, K, hd_v).transpose(1, 0, 2, 3, 4)

    qg = q.reshape(B, Sq, K, G, hd)
    q_pos = q_offset + jnp.arange(Sq)

    def body(carry, inp):
        acc, m, denom = carry                      # (B,Sq,K,G,hd), (B,Sq,K,G), _
        kb, vb, cidx = inp                         # (B,chunk,K,hd) x2, scalar
        k_pos = cidx * chunk + jnp.arange(chunk)
        # scores stay in the compute dtype (bf16 on TPU): halves the dominant
        # HBM traffic of the score chain; the running max / denominator
        # statistics stay f32 (flash-kernel numerics; Perf iteration 3)
        s = jnp.einsum("bqkgd,bckd->bqkgc", qg, kb) * jnp.asarray(
            scale, q.dtype)
        mask = jnp.ones((Sq, chunk), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        mask &= k_pos[None, :] < Sk                # padding
        if window is not None:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        s = jnp.where(mask[None, :, None, None, :], s,
                      jnp.asarray(NEG_INF, s.dtype))
        m_new = jnp.maximum(m, jnp.max(s, axis=-1).astype(jnp.float32))
        p = jnp.exp(s - m_new[..., None].astype(s.dtype))
        corr = jnp.exp(m - m_new)
        denom = denom * corr + jnp.sum(p.astype(jnp.float32), axis=-1)
        pv = jnp.einsum("bqkgc,bckd->bqkgd", p.astype(v.dtype), vb)
        acc = acc * corr[..., None].astype(acc.dtype) + pv
        return (acc, m_new, denom), None

    acc0 = jnp.zeros((B, Sq, K, G, hd_v), v.dtype)
    m0 = jnp.full((B, Sq, K, G), NEG_INF, jnp.float32)
    d0 = jnp.zeros((B, Sq, K, G), jnp.float32)
    (acc, m, denom), _ = jax.lax.scan(
        body, (acc0, m0, d0), (kc, vc, jnp.arange(nchunk)))
    denom = jnp.maximum(denom, 1e-20)
    out = acc / denom[..., None].astype(acc.dtype)
    return out.reshape(B, Sq, H, hd_v)


def attend(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
           window: Optional[int] = None, chunk: int = 1024,
           q_offset: int = 0) -> jax.Array:
    """Training/prefill attention, q: (B, Sq, H, hd), k/v: (B, Sk, K, hd):
    the Pallas flash kernel where the backend and shapes allow it, else
    ``chunked_attention``.  The choice is made, and recorded as
    ``attn.dispatch``, when the caller is traced; on the kernel's path the
    record's ``saved`` lists the names the kernel gives its o and lse for
    the remat to keep (``ops.REMAT_SAVED``)."""
    reason = ops.flash_attention_reason(q, k, v, window=window,
                                        q_offset=q_offset)
    attrs = {"path": "scan" if reason else "pallas",
             "q": list(q.shape), "k": list(k.shape), "v": list(v.shape),
             "causal": causal}
    if reason:
        attrs["reason"] = reason
    else:
        attrs["saved"] = list(ops.REMAT_SAVED)
    with obs.span("attn.dispatch", **attrs):
        pass
    if reason:
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 chunk=chunk, q_offset=q_offset)
    return ops.flash_attention(q, k, v, causal=causal)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     length: jax.Array, *, window: Optional[int] = None
                     ) -> jax.Array:
    """One-token attention against a cache.

    q: (B, 1, H, hd); caches: (B, S, K, hd); ``length``: scalar or (B,) count of
    valid cache entries *including* the current token.
    """
    B, _, H, hd = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, K, G, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache).astype(jnp.float32) * scale
    pos = jnp.arange(S)
    ln = jnp.asarray(length)
    ln = ln[:, None] if ln.ndim == 1 else ln[None, None]
    valid = pos[None, :] < ln                       # (B or 1, S)
    if window is not None:
        valid &= pos[None, :] >= ln - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache)
    return out.reshape(B, 1, H, hd)


# ----------------------------------------------------------------- GQA block
def gqa_init(key, d: int, n_heads: int, n_kv: int, head_dim: int, dtype,
             *, bias: bool = False) -> Params:
    kq, kk, kv, ko = split_keys(key, 4)
    p: Params = {
        "wq": normal_param(kq, (d, n_heads, head_dim), dtype,
                           "fsdp", "heads", "out_fsdp"),
        "wk": normal_param(kk, (d, n_kv, head_dim), dtype, "fsdp", "heads",
                           "out_fsdp"),
        "wv": normal_param(kv, (d, n_kv, head_dim), dtype, "fsdp", "heads",
                           "out_fsdp"),
        "wo": normal_param(ko, (n_heads, head_dim, d), dtype,
                           "heads", None, "out_fsdp"),
    }
    if bias:
        p["bq"] = zeros_param(None if key is None else kq,
                              (n_heads, head_dim), dtype, "heads", None)
    return p


def gqa_qkv(p: Params, x: jax.Array, cos, sin) -> Tuple[jax.Array, jax.Array, jax.Array]:
    q = jnp.einsum("bsd,dhk->bshk", x, use_weight(p["wq"], None, "heads", None))
    k = jnp.einsum("bsd,dhk->bshk", x, use_weight(p["wk"], None, "heads", None))
    v = jnp.einsum("bsd,dhk->bshk", x, use_weight(p["wv"], None, "heads", None))
    if "bq" in p:
        q = q + p["bq"]
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "heads", None)
    v = shard(v, "batch", None, "heads", None)
    return q, k, v


def gqa_attend(p: Params, x: jax.Array, cos, sin, *, causal: bool = True,
               window: Optional[int] = None, chunk: int = 1024,
               return_cache: bool = False):
    with jax.named_scope("attn"):
        q, k, v = gqa_qkv(p, x, cos, sin)
        o = attend(q, k, v, causal=causal, window=window, chunk=chunk)
        out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        out = shard(out, "batch", None, None)
        if not return_cache:
            return out
        if window is not None and k.shape[1] >= window:
            S = k.shape[1]
            k = jnp.roll(k[:, S - window:], S % window, axis=1)
            v = jnp.roll(v[:, S - window:], S % window, axis=1)
        return out, {"k": k, "v": v}


def gqa_decode(p: Params, x: jax.Array, cache: Params, pos: jax.Array,
               theta: float, *, window: Optional[int] = None
               ) -> Tuple[jax.Array, Params]:
    """x: (B, 1, d); cache {"k","v"}: (B, S, K, hd); pos: scalar index."""
    with jax.named_scope("attn"):
        positions = jnp.asarray(pos)[None]                      # (1,)
        cos, sin = rope_angles(positions, p["wq"].shape[-1], theta)
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        if "bq" in p:
            q = q + p["bq"]
        q = apply_rope(q, cos[None], sin[None])
        k = apply_rope(k, cos[None], sin[None])
        if window is None:
            kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, pos, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, pos, axis=1)
            length = pos + 1
            o = decode_attention(q, kc, vc, length)
        else:
            # ring-buffer window cache (long-context decode)
            slot = jnp.mod(pos, cache["k"].shape[1])
            kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
            length = jnp.minimum(pos + 1, cache["k"].shape[1])
            o = decode_attention(q, kc, vc, length)   # ring: all valid entries
        out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        return out, {"k": kc, "v": vc}


def gqa_cache_spec(batch: int, seq: int, n_kv: int, head_dim: int, dtype,
                   window: Optional[int] = None) -> Params:
    from .paramdecl import SpecLeaf
    S = min(seq, window) if window else seq
    shape = (batch, S, n_kv, head_dim)
    logical = ("batch", None, "heads", None)
    return {"k": SpecLeaf(shape, jnp.dtype(dtype), logical),
            "v": SpecLeaf(shape, jnp.dtype(dtype), logical)}


# ----------------------------------------------------------------- MLA block
def mla_init(key, d: int, n_heads: int, dtype, *, q_lora: int = 1536,
             kv_lora: int = 512, qk_nope: int = 128, qk_rope: int = 64,
             v_dim: int = 128) -> Params:
    k1, k2, k3, k4, k5, k6 = split_keys(key, 6)
    return {
        "wq_a": normal_param(k1, (d, q_lora), dtype, "fsdp", "out_fsdp"),
        "q_norm": ones_param(None if key is None else k1, (q_lora,), dtype, None),
        "wq_b": normal_param(k2, (q_lora, n_heads, qk_nope + qk_rope), dtype,
                             "fsdp", "heads", "out_fsdp"),
        "wkv_a": normal_param(k3, (d, kv_lora + qk_rope), dtype, "fsdp",
                              "out_fsdp"),
        "kv_norm": ones_param(None if key is None else k3, (kv_lora,), dtype, None),
        "wk_b": normal_param(k4, (kv_lora, n_heads, qk_nope), dtype,
                             "fsdp", "heads", "out_fsdp"),
        "wv_b": normal_param(k5, (kv_lora, n_heads, v_dim), dtype,
                             "fsdp", "heads", "out_fsdp"),
        "wo": normal_param(k6, (n_heads, v_dim, d), dtype, "heads", None,
                           "out_fsdp"),
    }


def _rms(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def mla_attend(p: Params, x: jax.Array, positions: jax.Array, theta: float,
               *, chunk: int = 1024, return_cache: bool = False):
    """Training/prefill MLA: expand compressed KV, run chunked attention."""
    with jax.named_scope("attn"):
        B, S, _ = x.shape
        qk_rope = p["wq_b"].shape[-1] - p["wk_b"].shape[-1]
        kv_lora = p["wk_b"].shape[0]
        q = jnp.einsum("bsd,dl->bsl", x, p["wq_a"])
        q = _rms(q, p["q_norm"])
        q = jnp.einsum("bsl,lhk->bshk", q, p["wq_b"])
        q_nope, q_rope = q[..., :-qk_rope], q[..., -qk_rope:]
        kv = jnp.einsum("bsd,dl->bsl", x, p["wkv_a"])
        c_kv, k_rope = kv[..., :kv_lora], kv[..., kv_lora:]
        c_kv = _rms(c_kv, p["kv_norm"])
        cos, sin = rope_angles(positions, qk_rope, theta)
        q_rope = apply_rope(q_rope, cos, sin)
        k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)  # (B,S,1,rope)
        k_nope = jnp.einsum("bsl,lhk->bshk", c_kv, p["wk_b"])
        v = jnp.einsum("bsl,lhk->bshk", c_kv, p["wv_b"])
        H = k_nope.shape[2]
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope, (B, S, H, qk_rope))], axis=-1)
        qfull = jnp.concatenate([q_nope, q_rope], axis=-1)
        qfull = shard(qfull, "batch", None, "heads", None)
        k = shard(k, "batch", None, "heads", None)
        v = shard(v, "batch", None, "heads", None)
        o = attend(qfull, k, v, causal=True, chunk=chunk)
        out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        out = shard(out, "batch", None, None)
        if not return_cache:
            return out
        return out, {"c_kv": c_kv, "k_rope": k_rope[:, :, 0, :]}


def mla_decode(p: Params, x: jax.Array, cache: Params, pos: jax.Array,
               theta: float) -> Tuple[jax.Array, Params]:
    """Absorbed MLA decode: cache stores (c_kv, k_rope) only.

    score_h = q_nope_h^T Wk_b_h c_kv  +  q_rope_h^T k_rope
    out_h   = (attn @ c_kv) Wv_b_h
    """
    with jax.named_scope("attn"):
        B = x.shape[0]
        qk_rope = p["wq_b"].shape[-1] - p["wk_b"].shape[-1]
        kv_lora = p["wk_b"].shape[0]
        q = _rms(jnp.einsum("bsd,dl->bsl", x, p["wq_a"]), p["q_norm"])
        q = jnp.einsum("bsl,lhk->bshk", q, p["wq_b"])         # (B,1,H,nope+rope)
        q_nope, q_rope = q[..., :-qk_rope], q[..., -qk_rope:]
        kv = jnp.einsum("bsd,dl->bsl", x, p["wkv_a"])          # (B,1,lora+rope)
        c_new, kr_new = kv[..., :kv_lora], kv[..., kv_lora:]
        c_new = _rms(c_new, p["kv_norm"])
        positions = jnp.asarray(pos)[None]
        cos, sin = rope_angles(positions, qk_rope, theta)
        q_rope = apply_rope(q_rope, cos[None], sin[None])
        kr_new = apply_rope(kr_new[:, :, None, :], cos[None], sin[None])[:, :, 0]
        ckv = jax.lax.dynamic_update_slice_in_dim(cache["c_kv"], c_new, pos, axis=1)
        krc = jax.lax.dynamic_update_slice_in_dim(cache["k_rope"], kr_new, pos,
                                                  axis=1)
        # absorb q into compressed space: (B,H,lora)
        q_abs = jnp.einsum("bshk,lhk->bhl", q_nope, p["wk_b"])
        scores = (jnp.einsum("bhl,bsl->bhs", q_abs, ckv)
                  + jnp.einsum("bhk,bsk->bhs", q_rope[:, 0], krc)
                  ).astype(jnp.float32)
        scale = 1.0 / math.sqrt(p["wq_b"].shape[-1])
        S = ckv.shape[1]
        valid = jnp.arange(S)[None, :] < (pos + 1)
        scores = jnp.where(valid[:, None, :], scores * scale, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1).astype(ckv.dtype)
        o_c = jnp.einsum("bhs,bsl->bhl", w, ckv)               # (B,H,lora)
        o = jnp.einsum("bhl,lhk->bhk", o_c, p["wv_b"])         # (B,H,v)
        out = jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None, :]
        return out, {"c_kv": ckv, "k_rope": krc}


def mla_cache_spec(batch: int, seq: int, kv_lora: int, qk_rope: int, dtype
                   ) -> Params:
    from .paramdecl import SpecLeaf
    return {
        "c_kv": SpecLeaf((batch, seq, kv_lora), jnp.dtype(dtype),
                         ("batch", None, None)),
        "k_rope": SpecLeaf((batch, seq, qk_rope), jnp.dtype(dtype),
                           ("batch", None, None)),
    }

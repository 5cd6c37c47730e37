"""Per-family transformer blocks and the scan-over-layers assembly.

Each family provides (init, train-apply, decode-apply, cache-spec) with a
uniform signature so ``model.py`` can assemble any of the ten assigned
architectures.  Layers are stacked on a leading axis and driven by
``lax.scan`` (bounded HLO size at 126-layer scale); ``jax.checkpoint`` wraps
the scan body per the config's remat policy.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.sharding import shard, mesh_axis_sizes
from .paramdecl import SpecLeaf, split_keys, stacked_init
from .layers import (rmsnorm_init, rmsnorm, layernorm_init, layernorm,
                     mlp_init, mlp)
from .attention import (gqa_init, gqa_attend, gqa_decode, gqa_cache_spec,
                        mla_init, mla_attend, mla_decode, mla_cache_spec,
                        rope_angles, chunked_attention)
from .moe import moe_init, moe_ffn
from .ssm import (mamba2_init, mamba2_forward, mamba2_decode,
                  mamba2_cache_spec)
from .rglru import rglru_init, rglru_forward, rglru_decode, rglru_cache_spec

Params = Dict[str, Any]


def _norm_fns(cfg):
    if cfg.norm == "layernorm":
        return layernorm_init, layernorm
    return rmsnorm_init, rmsnorm


def _head_dim(cfg) -> int:
    return cfg.head_dim or cfg.d_model // cfg.n_heads


def kv_cache_logical(n_kv: int) -> Tuple[Optional[str], Optional[str]]:
    """Pick (seq_axis, head_axis) logical tags for a KV cache: shard kv heads
    over `model` when divisible, otherwise shard the sequence dimension."""
    sizes = mesh_axis_sizes()
    m = sizes.get("model", 1)
    if m > 1 and n_kv % m == 0:
        return None, "heads"
    return "kvseq", None


def _retag_cache(spec_tree: Params, n_kv: int) -> Params:
    seq_ax, head_ax = kv_cache_logical(n_kv)

    def leaf(l: SpecLeaf) -> SpecLeaf:
        if len(l.shape) == 4:   # (B, S, K, hd)
            return SpecLeaf(l.shape, l.dtype, ("batch", seq_ax, head_ax, None))
        return l
    return jax.tree.map(leaf, spec_tree,
                        is_leaf=lambda x: isinstance(x, SpecLeaf))


# ---------------------------------------------------------------- dense/GQA
def dense_block_init(cfg, key) -> Params:
    k1, k2, k3, k4 = split_keys(key, 4)
    ninit, _ = _norm_fns(cfg)
    return {
        "ln1": ninit(k1, cfg.d_model, cfg.dtype),
        "attn": gqa_init(k2, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                         _head_dim(cfg), cfg.dtype, bias=cfg.attn_bias),
        "ln2": ninit(k3, cfg.d_model, cfg.dtype),
        "mlp": mlp_init(k4, cfg.d_model, cfg.d_ff, cfg.dtype,
                        gated=cfg.gated_mlp),
    }


def dense_block_apply(cfg, p, x, cos, sin) -> Tuple[jax.Array, jax.Array]:
    _, nf = _norm_fns(cfg)
    x = x + gqa_attend(p["attn"], nf(p["ln1"], x), cos, sin,
                       causal=True, window=cfg.window or None,
                       chunk=cfg.attn_chunk)
    x = x + mlp(p["mlp"], nf(p["ln2"], x), activation=cfg.activation)
    return x, jnp.zeros((), jnp.float32)


def dense_block_decode(cfg, p, x, cache, pos) -> Tuple[jax.Array, Params]:
    _, nf = _norm_fns(cfg)
    a, cache = gqa_decode(p["attn"], nf(p["ln1"], x), cache, pos,
                          cfg.rope_theta, window=cfg.window or None)
    x = x + a
    x = x + mlp(p["mlp"], nf(p["ln2"], x), activation=cfg.activation)
    return x, cache


def dense_cache_spec(cfg, batch: int, seq: int) -> Params:
    spec = gqa_cache_spec(batch, seq, cfg.n_kv_heads, _head_dim(cfg),
                          cfg.dtype, window=cfg.window or None)
    return _retag_cache(spec, cfg.n_kv_heads)


# --------------------------------------------------------------------- MoE
def moe_block_init(cfg, key) -> Params:
    k1, k2, k3, k4 = split_keys(key, 4)
    ninit, _ = _norm_fns(cfg)
    return {
        "ln1": ninit(k1, cfg.d_model, cfg.dtype),
        "attn": gqa_init(k2, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                         _head_dim(cfg), cfg.dtype),
        "ln2": ninit(k3, cfg.d_model, cfg.dtype),
        "moe": moe_init(k4, cfg.d_model, cfg.d_ff_expert, cfg.n_experts,
                        cfg.top_k, cfg.n_shared_experts, cfg.dtype),
    }


def moe_block_apply(cfg, p, x, cos, sin) -> Tuple[jax.Array, jax.Array]:
    _, nf = _norm_fns(cfg)
    x = x + gqa_attend(p["attn"], nf(p["ln1"], x), cos, sin,
                       chunk=cfg.attn_chunk)
    h, aux = moe_ffn(p["moe"], nf(p["ln2"], x), top_k=cfg.top_k,
                     capacity_factor=cfg.capacity_factor,
                     activation=cfg.activation)
    return x + h, aux


def moe_block_decode(cfg, p, x, cache, pos) -> Tuple[jax.Array, Params]:
    _, nf = _norm_fns(cfg)
    a, cache = gqa_decode(p["attn"], nf(p["ln1"], x), cache, pos,
                          cfg.rope_theta)
    x = x + a
    h, _ = moe_ffn(p["moe"], nf(p["ln2"], x), top_k=cfg.top_k,
                   capacity_factor=cfg.capacity_factor,
                   activation=cfg.activation)
    return x + h, cache


# ----------------------------------------------------------------- MLA+MoE
def mla_block_init(cfg, key) -> Params:
    k1, k2, k3, k4 = split_keys(key, 4)
    ninit, _ = _norm_fns(cfg)
    return {
        "ln1": ninit(k1, cfg.d_model, cfg.dtype),
        "attn": mla_init(k2, cfg.d_model, cfg.n_heads, cfg.dtype,
                         q_lora=cfg.q_lora, kv_lora=cfg.kv_lora,
                         qk_nope=cfg.qk_nope, qk_rope=cfg.qk_rope,
                         v_dim=cfg.v_head_dim),
        "ln2": ninit(k3, cfg.d_model, cfg.dtype),
        "moe": moe_init(k4, cfg.d_model, cfg.d_ff_expert, cfg.n_experts,
                        cfg.top_k, cfg.n_shared_experts, cfg.dtype),
    }


def mla_block_apply(cfg, p, x, cos, sin) -> Tuple[jax.Array, jax.Array]:
    _, nf = _norm_fns(cfg)
    S = x.shape[1]
    positions = jnp.arange(S)
    x = x + mla_attend(p["attn"], nf(p["ln1"], x), positions, cfg.rope_theta,
                       chunk=cfg.attn_chunk)
    h, aux = moe_ffn(p["moe"], nf(p["ln2"], x), top_k=cfg.top_k,
                     capacity_factor=cfg.capacity_factor,
                     activation=cfg.activation)
    return x + h, aux


def mla_block_decode(cfg, p, x, cache, pos) -> Tuple[jax.Array, Params]:
    _, nf = _norm_fns(cfg)
    a, cache = mla_decode(p["attn"], nf(p["ln1"], x), cache, pos,
                          cfg.rope_theta)
    x = x + a
    h, _ = moe_ffn(p["moe"], nf(p["ln2"], x), top_k=cfg.top_k,
                   capacity_factor=cfg.capacity_factor,
                   activation=cfg.activation)
    return x + h, cache


def mla_cache_tree(cfg, batch: int, seq: int) -> Params:
    return mla_cache_spec(batch, seq, cfg.kv_lora, cfg.qk_rope, cfg.dtype)


# --------------------------------------------------------------------- SSM
def ssm_block_init(cfg, key) -> Params:
    k1, k2 = split_keys(key, 2)
    ninit, _ = _norm_fns(cfg)
    return {
        "ln": ninit(k1, cfg.d_model, cfg.dtype),
        "ssm": mamba2_init(k2, cfg.d_model, cfg.ssm_state, cfg.dtype,
                           expand=cfg.ssm_expand),
    }


def ssm_block_apply(cfg, p, x, cos, sin) -> Tuple[jax.Array, jax.Array]:
    _, nf = _norm_fns(cfg)
    x = x + mamba2_forward(p["ssm"], nf(p["ln"], x), chunk=cfg.ssm_chunk)
    return x, jnp.zeros((), jnp.float32)


def ssm_block_decode(cfg, p, x, cache, pos) -> Tuple[jax.Array, Params]:
    _, nf = _norm_fns(cfg)
    h, cache = mamba2_decode(p["ssm"], nf(p["ln"], x), cache)
    return x + h, cache


def ssm_cache_spec(cfg, batch: int, seq: int) -> Params:
    return mamba2_cache_spec(batch, cfg.d_model, cfg.ssm_state, cfg.dtype,
                             expand=cfg.ssm_expand)


# ------------------------------------------------------------ hybrid group
# RecurrentGemma pattern: (recurrent, recurrent, local-attn) repeating; each
# sub-block pairs with its own MLP.
def _rec_sub_init(cfg, key) -> Params:
    k1, k2, k3, k4 = split_keys(key, 4)
    ninit, _ = _norm_fns(cfg)
    return {
        "ln1": ninit(k1, cfg.d_model, cfg.dtype),
        "rnn": rglru_init(k2, cfg.d_model, cfg.d_rnn, cfg.dtype),
        "ln2": ninit(k3, cfg.d_model, cfg.dtype),
        "mlp": mlp_init(k4, cfg.d_model, cfg.d_ff, cfg.dtype, gated=True),
    }


def _attn_sub_init(cfg, key) -> Params:
    k1, k2, k3, k4 = split_keys(key, 4)
    ninit, _ = _norm_fns(cfg)
    return {
        "ln1": ninit(k1, cfg.d_model, cfg.dtype),
        "attn": gqa_init(k2, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                         _head_dim(cfg), cfg.dtype),
        "ln2": ninit(k3, cfg.d_model, cfg.dtype),
        "mlp": mlp_init(k4, cfg.d_model, cfg.d_ff, cfg.dtype, gated=True),
    }


def hybrid_group_init(cfg, key) -> Params:
    k1, k2, k3 = split_keys(key, 3)
    return {"rec1": _rec_sub_init(cfg, k1), "rec2": _rec_sub_init(cfg, k2),
            "attn": _attn_sub_init(cfg, k3)}


def _rec_sub_apply(cfg, p, x):
    _, nf = _norm_fns(cfg)
    x = x + rglru_forward(p["rnn"], nf(p["ln1"], x))
    return x + mlp(p["mlp"], nf(p["ln2"], x), activation=cfg.activation)


def _attn_sub_apply(cfg, p, x, cos, sin):
    _, nf = _norm_fns(cfg)
    x = x + gqa_attend(p["attn"], nf(p["ln1"], x), cos, sin, causal=True,
                       window=cfg.window, chunk=cfg.attn_chunk)
    return x + mlp(p["mlp"], nf(p["ln2"], x), activation=cfg.activation)


def hybrid_group_apply(cfg, p, x, cos, sin) -> Tuple[jax.Array, jax.Array]:
    x = _rec_sub_apply(cfg, p["rec1"], x)
    x = _rec_sub_apply(cfg, p["rec2"], x)
    x = _attn_sub_apply(cfg, p["attn"], x, cos, sin)
    return x, jnp.zeros((), jnp.float32)


def hybrid_group_decode(cfg, p, x, cache, pos) -> Tuple[jax.Array, Params]:
    _, nf = _norm_fns(cfg)
    new_cache = {}
    for name in ("rec1", "rec2"):
        sp = p[name]
        h, new_cache[name] = rglru_decode(sp["rnn"], nf(sp["ln1"], x),
                                          cache[name])
        x = x + h
        x = x + mlp(sp["mlp"], nf(sp["ln2"], x), activation=cfg.activation)
    sp = p["attn"]
    a, new_cache["attn"] = gqa_decode(sp["attn"], nf(sp["ln1"], x),
                                      cache["attn"], pos, cfg.rope_theta,
                                      window=cfg.window)
    x = x + a
    x = x + mlp(sp["mlp"], nf(sp["ln2"], x), activation=cfg.activation)
    return x, new_cache


def hybrid_cache_spec(cfg, batch: int, seq: int) -> Params:
    attn_spec = gqa_cache_spec(batch, seq, cfg.n_kv_heads, _head_dim(cfg),
                               cfg.dtype, window=cfg.window)
    return {
        "rec1": rglru_cache_spec(batch, cfg.d_rnn, cfg.dtype),
        "rec2": rglru_cache_spec(batch, cfg.d_rnn, cfg.dtype),
        "attn": _retag_cache(attn_spec, cfg.n_kv_heads),
    }


# ------------------------------------------------------------------ encdec
def enc_block_init(cfg, key) -> Params:
    p = dense_block_init(cfg, key)
    return p


def enc_block_apply(cfg, p, x, cos, sin) -> Tuple[jax.Array, jax.Array]:
    _, nf = _norm_fns(cfg)
    x = x + gqa_attend(p["attn"], nf(p["ln1"], x), cos, sin, causal=False,
                       chunk=cfg.attn_chunk)
    x = x + mlp(p["mlp"], nf(p["ln2"], x), activation=cfg.activation)
    return x, jnp.zeros((), jnp.float32)


def dec_block_init(cfg, key) -> Params:
    k1, k2, k3 = split_keys(key, 3)
    ninit, _ = _norm_fns(cfg)
    p = dense_block_init(cfg, k1)
    p["ln_x"] = ninit(k2, cfg.d_model, cfg.dtype)
    p["xattn"] = gqa_init(k3, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                          _head_dim(cfg), cfg.dtype)
    return p


def _cross_attend(cfg, p, x, enc_k, enc_v):
    with jax.named_scope("xattn"):
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        q = shard(q, "batch", None, "heads", None)
        o = chunked_attention(q, enc_k, enc_v, causal=False,
                              chunk=cfg.attn_chunk)
        return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def enc_kv(p_xattn, enc_out):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p_xattn["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p_xattn["wv"])
    return shard(k, "batch", None, "heads", None), \
        shard(v, "batch", None, "heads", None)


def dec_block_apply(cfg, p, x, cos, sin, enc_out) -> Tuple[jax.Array, jax.Array]:
    _, nf = _norm_fns(cfg)
    x = x + gqa_attend(p["attn"], nf(p["ln1"], x), cos, sin, causal=True,
                       chunk=cfg.attn_chunk)
    k, v = enc_kv(p["xattn"], enc_out)
    x = x + _cross_attend(cfg, p["xattn"], nf(p["ln_x"], x), k, v)
    x = x + mlp(p["mlp"], nf(p["ln2"], x), activation=cfg.activation)
    return x, jnp.zeros((), jnp.float32)


def dec_block_decode(cfg, p, x, cache, pos) -> Tuple[jax.Array, Params]:
    """cache: {"k","v" (self), "xk","xv" (cross, precomputed at prefill)}."""
    _, nf = _norm_fns(cfg)
    a, self_cache = gqa_decode(p["attn"], nf(p["ln1"], x),
                               {"k": cache["k"], "v": cache["v"]}, pos,
                               cfg.rope_theta)
    x = x + a
    from .attention import decode_attention
    with jax.named_scope("xattn"):
        q = jnp.einsum("bsd,dhk->bshk", nf(p["ln_x"], x), p["xattn"]["wq"])
        o = decode_attention(q, cache["xk"], cache["xv"],
                             jnp.asarray(cache["xk"].shape[1]))
        x = x + jnp.einsum("bshk,hkd->bsd", o, p["xattn"]["wo"])
    x = x + mlp(p["mlp"], nf(p["ln2"], x), activation=cfg.activation)
    return x, {**self_cache, "xk": cache["xk"], "xv": cache["xv"]}


def encdec_cache_spec(cfg, batch: int, seq: int) -> Params:
    self_spec = _retag_cache(
        gqa_cache_spec(batch, seq, cfg.n_kv_heads, _head_dim(cfg), cfg.dtype),
        cfg.n_kv_heads)
    cross_spec = _retag_cache(
        gqa_cache_spec(batch, cfg.cross_len or seq, cfg.n_kv_heads,
                       _head_dim(cfg), cfg.dtype), cfg.n_kv_heads)
    return {"k": self_spec["k"], "v": self_spec["v"],
            "xk": cross_spec["k"], "xv": cross_spec["v"]}


# ----------------------------------------------------------------- prefill
def dense_block_prefill(cfg, p, x, cos, sin) -> Tuple[jax.Array, Params]:
    _, nf = _norm_fns(cfg)
    a, cache = gqa_attend(p["attn"], nf(p["ln1"], x), cos, sin, causal=True,
                          window=cfg.window or None, chunk=cfg.attn_chunk,
                          return_cache=True)
    x = x + a
    x = x + mlp(p["mlp"], nf(p["ln2"], x), activation=cfg.activation)
    return x, cache


def moe_block_prefill(cfg, p, x, cos, sin) -> Tuple[jax.Array, Params]:
    _, nf = _norm_fns(cfg)
    a, cache = gqa_attend(p["attn"], nf(p["ln1"], x), cos, sin,
                          chunk=cfg.attn_chunk, return_cache=True)
    x = x + a
    h, _ = moe_ffn(p["moe"], nf(p["ln2"], x), top_k=cfg.top_k,
                   capacity_factor=cfg.capacity_factor,
                   activation=cfg.activation)
    return x + h, cache


def mla_block_prefill(cfg, p, x, cos, sin) -> Tuple[jax.Array, Params]:
    _, nf = _norm_fns(cfg)
    S = x.shape[1]
    positions = jnp.arange(S)
    a, cache = mla_attend(p["attn"], nf(p["ln1"], x), positions,
                          cfg.rope_theta, chunk=cfg.attn_chunk,
                          return_cache=True)
    x = x + a
    h, _ = moe_ffn(p["moe"], nf(p["ln2"], x), top_k=cfg.top_k,
                   capacity_factor=cfg.capacity_factor,
                   activation=cfg.activation)
    return x + h, cache


def ssm_block_prefill(cfg, p, x, cos, sin) -> Tuple[jax.Array, Params]:
    _, nf = _norm_fns(cfg)
    h, cache = mamba2_forward(p["ssm"], nf(p["ln"], x), chunk=cfg.ssm_chunk,
                              return_state=True)
    return x + h, cache


def hybrid_group_prefill(cfg, p, x, cos, sin) -> Tuple[jax.Array, Params]:
    _, nf = _norm_fns(cfg)
    cache = {}
    for name in ("rec1", "rec2"):
        sp = p[name]
        h, cache[name] = rglru_forward(sp["rnn"], nf(sp["ln1"], x),
                                       return_state=True)
        x = x + h
        x = x + mlp(sp["mlp"], nf(sp["ln2"], x), activation=cfg.activation)
    sp = p["attn"]
    a, cache["attn"] = gqa_attend(sp["attn"], nf(sp["ln1"], x), cos, sin,
                                  causal=True, window=cfg.window,
                                  chunk=cfg.attn_chunk, return_cache=True)
    x = x + a
    x = x + mlp(sp["mlp"], nf(sp["ln2"], x), activation=cfg.activation)
    return x, cache


def dec_block_prefill(cfg, p, x, cos, sin, enc_out) -> Tuple[jax.Array, Params]:
    _, nf = _norm_fns(cfg)
    a, cache = gqa_attend(p["attn"], nf(p["ln1"], x), cos, sin, causal=True,
                          chunk=cfg.attn_chunk, return_cache=True)
    x = x + a
    xk, xv = enc_kv(p["xattn"], enc_out)
    x = x + _cross_attend(cfg, p["xattn"], nf(p["ln_x"], x), xk, xv)
    x = x + mlp(p["mlp"], nf(p["ln2"], x), activation=cfg.activation)
    return x, {**cache, "xk": xk, "xv": xv}


def run_stack_prefill(cfg, stacked: Params, x: jax.Array, prefill_fn,
                      cos, sin, *extra) -> Tuple[jax.Array, Params]:
    """scan layers, emitting each layer's cache as a stacked ys tree."""
    def body(h, lp):
        h, cache = prefill_fn(cfg, lp, h, cos, sin, *extra)
        return h, cache

    x, caches = jax.lax.scan(body, x, stacked)
    return x, caches


# ------------------------------------------------------------ scan drivers
def _remat_wrap(cfg, fn):
    """``fn`` under the config's remat: ``"none"`` keeps every activation;
    ``"full"`` keeps the layer's input and the outputs named in
    ``ops.REMAT_SAVED`` and recomputes the rest in the backward.  Those
    names exist only where attention runs the flash kernel (a TPU): there
    a layer keeps its o (B, H, S, hd) in the compute dtype and lse
    (B, H, S) in f32, about one more residual-stream activation (two at
    hd 64, whose lanes the TPU pads to 128), so the backward does not run
    the forward kernel again.  Elsewhere nothing is named and ``"full"``
    keeps the layer's input alone."""
    if cfg.remat == "none":
        return fn
    if cfg.remat != "full":
        raise ValueError(f"remat={cfg.remat!r}: 'none' or 'full'")
    return jax.checkpoint(fn, policy=jax.checkpoint_policies
                          .save_only_these_names(*ops.REMAT_SAVED))


def run_stack(cfg, stacked: Params, x: jax.Array, apply_fn,
              cos, sin, *extra) -> Tuple[jax.Array, jax.Array]:
    """scan(stacked layer params) with remat; returns (x, summed aux)."""
    def body(carry, lp):
        h, aux = carry
        h, a = apply_fn(cfg, lp, h, cos, sin, *extra)
        return (h, aux + a), None

    if cfg.scan_layers:
        body_w = _remat_wrap(cfg, body)
        (x, aux), _ = jax.lax.scan(body_w, (x, jnp.zeros((), jnp.float32)),
                                   stacked)
        return x, aux
    n = jax.tree.leaves(stacked)[0].shape[0]
    aux = jnp.zeros((), jnp.float32)
    fn = _remat_wrap(cfg, lambda c, lp: body(c, lp)[0])
    carry = (x, aux)
    for i in range(n):
        lp = jax.tree.map(lambda t: t[i], stacked)
        with jax.named_scope(f"layer{i}"):    # per-layer task->layer mapping
            carry = fn(carry, lp)
    return carry


def run_stack_decode(cfg, stacked: Params, caches: Params, x: jax.Array,
                     decode_fn, pos) -> Tuple[jax.Array, Params]:
    """scan over (layer params, layer cache); returns (x, new caches)."""
    def body(h, inp):
        lp, cache = inp
        h, new_cache = decode_fn(cfg, lp, h, cache, pos)
        return h, new_cache

    x, new_caches = jax.lax.scan(body, x, (stacked, caches))
    return x, new_caches

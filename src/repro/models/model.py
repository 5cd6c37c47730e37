"""ModelConfig + build_model: the public model API for all ten architectures.

``build_model(cfg)`` returns a :class:`Model` whose functions are pure and
jit/pjit-able:

    model.init(key)                      -> params    (key=None -> SpecLeaf tree)
    model.loss(params, batch)            -> scalar loss          (train)
    model.prefill(params, batch)         -> (logits, cache)      (inference)
    model.decode(params, cache, tok, pos)-> (logits, cache)      (one token)
    model.cache_spec(batch, seq)         -> SpecLeaf cache tree

Plus step factories (``make_train_step`` / ``make_serve_step`` /
``make_prefill_step``) and ``input_specs`` which produce the ShapeDtypeStruct
stand-ins + NamedShardings the multi-pod dry-run lowers with (no allocation).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sharding import (ShardingRules, DEFAULT_RULES, shard,
                            set_active_layout)
from .paramdecl import (SpecLeaf, split_keys, stacked_init, specs_of,
                        shapes_of, sharded_shapes_of, count_params as
                        _count_params, normal_param)
from .layers import (embedding_init, embed, rmsnorm_init, rmsnorm,
                     softmax_cross_entropy_chunked, mlp_init, mlp)
from .attention import rope_angles
from . import transformer as T

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | mla_moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0             # 0 -> d_model // n_heads
    dtype: str = "bfloat16"
    rope_theta: float = 10000.0
    activation: str = "silu"
    gated_mlp: bool = True
    norm: str = "rmsnorm"
    attn_bias: bool = False
    tie_embeddings: bool = False
    # --- MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    d_ff_expert: int = 0
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    # --- MLA (DeepSeek-V2)
    q_lora: int = 0
    kv_lora: int = 0
    qk_nope: int = 128
    qk_rope: int = 64
    v_head_dim: int = 128
    # --- SSM (mamba2)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_chunk: int = 128
    # --- hybrid (recurrentgemma)
    window: int = 0               # local-attention window (0 = full attention)
    d_rnn: int = 0
    # --- encdec (seamless)
    enc_layers: int = 0
    dec_layers: int = 0
    cross_len: int = 0            # encoder length for decode cache (0 = seq)
    # --- vlm (internvl)
    n_patches: int = 0
    # --- compilation / perf knobs (§Perf hillclimb surface)
    layout: str = "v2"            # train sharding layout: baseline | v2 | dp
    serve_layout: str = "v2"      # decode/prefill layout (weight-stationary TP)
    serve_fsdp: bool = True       # False: replicate weights over data when
                                  # they fit (kills per-token FSDP gathers)
    remat: str = "full"           # none | full (keeps the layer input and
                                  # the flash kernel's o, lse; transformer
                                  # ._remat_wrap)
    scan_layers: bool = True
    attn_chunk: int = 1024
    loss_chunk: int = 2048
    grad_accum: int = 1
    # --- applicability flags
    sub_quadratic: bool = False   # may run long_500k
    decode_supported: bool = True

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# family -> (block_init, block_apply, block_decode, block_prefill, cache_spec)
_FAMILY = {
    "dense": (T.dense_block_init, T.dense_block_apply, T.dense_block_decode,
              T.dense_block_prefill, T.dense_cache_spec),
    "vlm": (T.dense_block_init, T.dense_block_apply, T.dense_block_decode,
            T.dense_block_prefill, T.dense_cache_spec),
    "moe": (T.moe_block_init, T.moe_block_apply, T.moe_block_decode,
            T.moe_block_prefill, T.dense_cache_spec),
    "mla_moe": (T.mla_block_init, T.mla_block_apply, T.mla_block_decode,
                T.mla_block_prefill,
                lambda cfg, b, s: T.mla_cache_tree(cfg, b, s)),
    "ssm": (T.ssm_block_init, T.ssm_block_apply, T.ssm_block_decode,
            T.ssm_block_prefill, T.ssm_cache_spec),
    "hybrid": (T.hybrid_group_init, T.hybrid_group_apply,
               T.hybrid_group_decode, T.hybrid_group_prefill,
               T.hybrid_cache_spec),
}


def _n_blocks(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        return cfg.n_layers // 3
    if cfg.family == "encdec":
        return cfg.dec_layers
    return cfg.n_layers


def _n_tail(cfg: ModelConfig) -> int:
    return cfg.n_layers % 3 if cfg.family == "hybrid" else 0


# -------------------------------------------------------------------- init
def init_params(cfg: ModelConfig, key) -> Params:
    keys = split_keys(key, 8)
    p: Params = {"embed": embedding_init(keys[0], cfg.vocab, cfg.d_model,
                                         cfg.dtype),
                 "final_norm": rmsnorm_init(keys[1], cfg.d_model, cfg.dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = {"table": normal_param(
            keys[2], (cfg.vocab, cfg.d_model), cfg.dtype, "vocab_mega",
            "fsdp", scale=0.02)}
    if cfg.family == "encdec":
        binit = T.dense_block_init
        p["src_proj"] = normal_param(keys[3], (cfg.d_model, cfg.d_model),
                                     cfg.dtype, "fsdp", "out_fsdp")
        p["encoder"] = stacked_init(cfg.enc_layers,
                                    lambda k: T.enc_block_init(cfg, k), keys[4])
        p["decoder"] = stacked_init(cfg.dec_layers,
                                    lambda k: T.dec_block_init(cfg, k), keys[5])
        p["enc_norm"] = rmsnorm_init(keys[6], cfg.d_model, cfg.dtype)
        return p
    if cfg.family == "vlm":
        p["connector"] = normal_param(keys[3], (cfg.d_model, cfg.d_model),
                                      cfg.dtype, "fsdp", "out_fsdp")
    binit = _FAMILY[cfg.family][0]
    p["blocks"] = stacked_init(_n_blocks(cfg), lambda k: binit(cfg, k), keys[7])
    if _n_tail(cfg):
        p["tail"] = stacked_init(_n_tail(cfg),
                                 lambda k: T._rec_sub_init(cfg, k), keys[6])
    return p


def param_specs(cfg: ModelConfig, rules: Optional[ShardingRules] = None):
    return specs_of(init_params(cfg, None),
                    rules or ShardingRules(layout=cfg.layout))


# ----------------------------------------------------------------- forward
def _rope(cfg: ModelConfig, S: int):
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    if cfg.family == "ssm":
        return None, None
    return rope_angles(jnp.arange(S), hd, cfg.rope_theta)


def _tail_apply(cfg, p, x):
    def body(carry, lp):
        h, aux = carry
        return (T._rec_sub_apply(cfg, lp, h), aux), None
    (x, _), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), p["tail"])
    return x


def _backbone(cfg: ModelConfig, p: Params, x: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """Embedded input -> final-normed hidden states (+ MoE aux loss)."""
    _, bapply, _, _, _ = _FAMILY[cfg.family]
    cos, sin = _rope(cfg, x.shape[1])
    x, aux = T.run_stack(cfg, p["blocks"], x, bapply, cos, sin)
    if _n_tail(cfg):
        x = _tail_apply(cfg, p, x)
    return rmsnorm(p["final_norm"], x), aux


def _unembed_params(cfg: ModelConfig, p: Params) -> Params:
    return p["embed"] if cfg.tie_embeddings else p["unembed"]


def _last_logits(cfg: ModelConfig, p: Params, h_last: jax.Array) -> jax.Array:
    """h_last: (B, d) -> (B, vocab)."""
    with jax.named_scope("unembed"):
        table = _unembed_params(cfg, p)["table"]
        logits = jnp.einsum("bd,vd->bv", h_last, table)
        return shard(logits, "batch", "vocab")


def loss_fn(cfg: ModelConfig, p: Params, batch: Dict[str, jax.Array]
            ) -> jax.Array:
    mask = batch.get("mask")
    if cfg.family == "encdec":
        with jax.named_scope("frontend"):
            src = jnp.einsum("bsd,de->bse", batch["src_embeds"], p["src_proj"])
        cos, sin = _rope(cfg, src.shape[1])
        enc, _ = T.run_stack(cfg, p["encoder"], src, T.enc_block_apply,
                             cos, sin)
        enc = rmsnorm(p["enc_norm"], enc)
        x = embed(p["embed"], batch["tokens"])
        cos, sin = _rope(cfg, x.shape[1])
        x, aux = T.run_stack(cfg, p["decoder"], x, T.dec_block_apply,
                             cos, sin, enc)
        h = rmsnorm(p["final_norm"], x)
    elif cfg.family == "vlm":
        with jax.named_scope("frontend"):
            prefix = jnp.einsum("bpd,de->bpe", batch["patch_embeds"],
                                p["connector"])
        x = jnp.concatenate([prefix, embed(p["embed"], batch["tokens"])],
                            axis=1)
        h, aux = _backbone(cfg, p, x)
        h = h[:, cfg.n_patches:]
    else:
        x = embed(p["embed"], batch["tokens"])
        h, aux = _backbone(cfg, p, x)
    loss = softmax_cross_entropy_chunked(_unembed_params(cfg, p), h,
                                         batch["labels"], mask,
                                         chunk=cfg.loss_chunk)
    if cfg.n_experts:
        loss = loss + cfg.aux_loss_coef * aux / max(_n_blocks(cfg), 1)
    return loss


# ----------------------------------------------------------------- prefill
def prefill_fn(cfg: ModelConfig, p: Params, batch: Dict[str, jax.Array]
               ) -> Tuple[jax.Array, Params]:
    _, _, _, bprefill, _ = _FAMILY.get(cfg.family, (None,) * 5)
    if cfg.family == "encdec":
        with jax.named_scope("frontend"):
            src = jnp.einsum("bsd,de->bse", batch["src_embeds"], p["src_proj"])
        cos, sin = _rope(cfg, src.shape[1])
        enc, _ = T.run_stack(cfg, p["encoder"], src, T.enc_block_apply,
                             cos, sin)
        enc = rmsnorm(p["enc_norm"], enc)
        x = embed(p["embed"], batch["tokens"])
        cos, sin = _rope(cfg, x.shape[1])
        x, caches = T.run_stack_prefill(cfg, p["decoder"], x,
                                        T.dec_block_prefill, cos, sin, enc)
        h = rmsnorm(p["final_norm"], x)
        return _last_logits(cfg, p, h[:, -1]), caches
    if cfg.family == "vlm":
        with jax.named_scope("frontend"):
            prefix = jnp.einsum("bpd,de->bpe", batch["patch_embeds"],
                                p["connector"])
        x = jnp.concatenate([prefix, embed(p["embed"], batch["tokens"])],
                            axis=1)
    else:
        x = embed(p["embed"], batch["tokens"])
    cos, sin = _rope(cfg, x.shape[1])
    x, caches = T.run_stack_prefill(cfg, p["blocks"], x, bprefill, cos, sin)
    if _n_tail(cfg):
        # tail recurrent layers: prefill via forward-with-state
        def body(h, lp):
            o, c = T.rglru_forward(lp["rnn"],
                                   rmsnorm(lp["ln1"], h), return_state=True)
            h = h + o
            h = h + mlp(lp["mlp"], rmsnorm(lp["ln2"], h),
                        activation=cfg.activation)
            return h, c
        x, tail_caches = jax.lax.scan(body, x, p["tail"])
        caches = {"groups": caches, "tail": tail_caches}
    h = rmsnorm(p["final_norm"], x)
    return _last_logits(cfg, p, h[:, -1]), caches


# ------------------------------------------------------------------ decode
def decode_fn(cfg: ModelConfig, p: Params, cache: Params,
              tokens: jax.Array, pos: jax.Array
              ) -> Tuple[jax.Array, Params]:
    _, _, bdecode, _, _ = _FAMILY.get(cfg.family, (None,) * 5)
    x = embed(p["embed"], tokens)
    if cfg.family == "encdec":
        x, new_caches = T.run_stack_decode(cfg, p["decoder"], cache, x,
                                           T.dec_block_decode, pos)
    elif _n_tail(cfg):
        x, new_groups = T.run_stack_decode(cfg, p["blocks"], cache["groups"],
                                           x, bdecode, pos)
        def body(h, inp):
            lp, c = inp
            o, c = T.rglru_decode(lp["rnn"], rmsnorm(lp["ln1"], h), c)
            h = h + o
            h = h + mlp(lp["mlp"], rmsnorm(lp["ln2"], h),
                        activation=cfg.activation)
            return h, c
        x, new_tail = jax.lax.scan(body, x, (p["tail"], cache["tail"]))
        new_caches = {"groups": new_groups, "tail": new_tail}
    else:
        x, new_caches = T.run_stack_decode(cfg, p["blocks"], cache, x,
                                           bdecode, pos)
    h = rmsnorm(p["final_norm"], x)
    return _last_logits(cfg, p, h[:, -1]), new_caches


# -------------------------------------------------------------- cache spec
def _stack_spec(tree, n: int):
    return jax.tree.map(
        lambda l: SpecLeaf((n,) + l.shape, l.dtype, (None,) + l.logical),
        tree, is_leaf=lambda x: isinstance(x, SpecLeaf))


def cache_spec(cfg: ModelConfig, batch: int, seq: int) -> Params:
    if cfg.family == "encdec":
        per = T.encdec_cache_spec(cfg, batch, seq)
        return _stack_spec(per, cfg.dec_layers)
    _, _, _, _, cspec = _FAMILY[cfg.family]
    per = cspec(cfg, batch, seq)
    stacked = _stack_spec(per, _n_blocks(cfg))
    if _n_tail(cfg):
        from .rglru import rglru_cache_spec
        tail = _stack_spec(rglru_cache_spec(batch, cfg.d_rnn, cfg.dtype),
                           _n_tail(cfg))
        return {"groups": stacked, "tail": tail}
    return stacked


# ------------------------------------------------------------------- Model
@dataclasses.dataclass
class Model:
    cfg: ModelConfig

    def init(self, key) -> Params:
        return init_params(self.cfg, key)

    def loss(self, params, batch):
        return loss_fn(self.cfg, params, batch)

    def prefill(self, params, batch):
        return prefill_fn(self.cfg, params, batch)

    def decode(self, params, cache, tokens, pos):
        return decode_fn(self.cfg, params, cache, tokens, pos)

    def cache_spec(self, batch: int, seq: int):
        return cache_spec(self.cfg, batch, seq)

    def param_specs(self, rules: ShardingRules = DEFAULT_RULES):
        return param_specs(self.cfg, rules)


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family not in set(_FAMILY) | {"encdec"}:
        raise ValueError(f"unknown family {cfg.family!r}")
    return Model(cfg)


# ----------------------------------------------------------------- steps
def make_train_step(cfg: ModelConfig, optimizer) -> Callable:
    """(state, batch) -> (state, metrics).  state = {params, opt, step}."""
    model = build_model(cfg)

    def train_step(state, batch):
        set_active_layout(cfg.layout)
        params = state["params"]
        accum = cfg.grad_accum

        def lf(p, mb):
            return model.loss(p, mb)

        if accum > 1:
            def resh(t):
                return t.reshape((accum, t.shape[0] // accum) + t.shape[1:])
            mbs = jax.tree.map(resh, batch)
            g0 = jax.tree.map(lambda t: jnp.zeros(t.shape, t.dtype), params)

            def body(carry, mb):
                tot, acc = carry
                l, g = jax.value_and_grad(lf)(params, mb)
                return (tot + l, jax.tree.map(jnp.add, acc, g)), None

            (loss, grads), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), g0), mbs)
            loss = loss / accum
            grads = jax.tree.map(lambda g: g / accum, grads)
        else:
            loss, grads = jax.value_and_grad(lf)(params, batch)

        with jax.named_scope("update"):
            new_params, new_opt = optimizer.apply(grads, state["opt"], params)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        gnorm = optimizer.last_grad_norm(new_opt)
        return new_state, {"loss": loss, "grad_norm": gnorm}

    return train_step


def make_serve_step(cfg: ModelConfig) -> Callable:
    model = build_model(cfg)

    def serve_step(params, cache, tokens, pos):
        set_active_layout(cfg.serve_layout)
        logits, cache = model.decode(params, cache, tokens, pos)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return next_tok, cache

    return serve_step


def make_prefill_step(cfg: ModelConfig) -> Callable:
    model = build_model(cfg)

    def prefill_step(params, batch):
        set_active_layout(cfg.serve_layout)
        logits, cache = model.prefill(params, batch)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return next_tok, cache

    return prefill_step


# ------------------------------------------------------------- input specs
def input_specs(cfg: ModelConfig, *, kind: str, seq_len: int,
                global_batch: int) -> Dict[str, SpecLeaf]:
    """SpecLeaf stand-ins for the step-function batch argument.

    ``kind``: train | prefill | decode.  Convert with ``shapes_of`` /
    ``sharded_shapes_of`` under the target mesh.
    """
    B, S = global_batch, seq_len
    i32 = jnp.dtype(jnp.int32)
    tok_logical = ("batch", None)

    def toks(s):
        return SpecLeaf((B, s), i32, tok_logical)

    if cfg.family == "vlm":
        text = S - cfg.n_patches
        base = {"patch_embeds": SpecLeaf((B, cfg.n_patches, cfg.d_model),
                                         jnp.dtype(cfg.dtype),
                                         ("batch", None, None)),
                "tokens": toks(text)}
        if kind == "train":
            base["labels"] = toks(text)
        return base
    if cfg.family == "encdec":
        base = {"src_embeds": SpecLeaf((B, S, cfg.d_model),
                                       jnp.dtype(cfg.dtype),
                                       ("batch", None, None)),
                "tokens": toks(S)}
        if kind == "train":
            base["labels"] = toks(S)
        return base
    base = {"tokens": toks(S)}
    if kind == "train":
        base["labels"] = toks(S)
    return base


# -------------------------------------------------------------- accounting
def count_params(cfg: ModelConfig) -> int:
    return _count_params(init_params(cfg, None))


def active_params(cfg: ModelConfig) -> int:
    """Per-token active parameters (for MODEL_FLOPS = 6 * N_active * D)."""
    total = count_params(cfg)
    if cfg.n_experts and cfg.top_k:
        per_expert = 3 * cfg.d_model * cfg.d_ff_expert
        inactive = (cfg.n_experts - cfg.top_k) * per_expert * _n_blocks(cfg)
        total -= inactive
    return total

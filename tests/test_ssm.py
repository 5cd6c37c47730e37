"""Mamba-2 block (``models/ssm.py``) against a sequential recurrence.

The reference here is written from the published equations, one position
at a time: the xBC conv with bias, SiLU, then per head
h_t = exp(dt_t·A)·h_{t-1} + dt_t·B_t ⊗ x_t and y_t = C_t·h_t + D·x_t,
the gated RMSNorm and the out-projection.  It shares no code with the
chunked scan under test, nor with the Pallas kernels, which run here in
interpret mode with the backend taken for a TPU.
"""

from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels import ops
from repro.models.ssm import (CONV_K, HEAD_P, mamba2_cache_spec,
                              mamba2_decode, mamba2_forward, mamba2_init)

D_MODEL, D_STATE = 64, 16          # E = 128: two heads of 64


def _params(seed=0, *, a_log=None, dt=None, d_model=D_MODEL):
    """f32 block parameters with a random conv bias and, by default, the
    published spreads of A (U(1, 16)) and dt (logU(1e-3, 0.1)); ``a_log``
    and ``dt`` pin every head to one value (dt through ``dt_bias`` with a
    zero dt projection)."""
    p = mamba2_init(jax.random.PRNGKey(seed), d_model, D_STATE, jnp.float32)
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


def _x(S, B=2, seed=1, d_model=D_MODEL):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (B, S, d_model)), jnp.float32)


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
        {"path": "scan", "reason": "backend", "conv": "xla",
         "conv_reason": "backend", "x": [2, 40, D_MODEL], "chunk": 16,
         "chunks": 3, "pad": 8, "state_dtype": "float32",
         "return_state": False},
        {"path": "scan", "reason": "backend", "conv": "xla",
         "conv_reason": "backend", "x": [2, 40, D_MODEL], "chunk": 40,
         "chunks": 1, "pad": 0, "state_dtype": "float32",
         "return_state": True}]


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


# ------------------------------------------------ the Pallas kernel path
def _out_and_grads(fn, p, x, r):
    """fn(p, x), and the gradient of sum(fn · r) in every parameter (x, B
    and C through their projections, dt through dt_bias and w_dt, A_log,
    D, ...) and in x."""
    def loss(p, x):
        return jnp.sum(fn(p, x).astype(jnp.float32) * r)
    return fn(p, x), jax.grad(loss, argnums=(0, 1))(p, x)


def _leaves(tree):
    return jax.tree_util.tree_flatten_with_path(tree)[0]


# (B, S, chunk, d_model): S 196 pads to 256 at chunk 128, and to 200 rows
# in the conv kernel; d_model 1024 holds 32 heads, two head blocks of 16;
# chunk 256 tiles in two sub-blocks of 128 with the block above the
# diagonal skipped, over two chunks, and the conv kernel's rows run in two
# blocks of 128 per chunk
KERNEL_CASES = {"pad": (2, 196, 128, D_MODEL),
                "head_blocks": (1, 256, 128, 1024),
                "sub_blocks": (1, 512, 256, D_MODEL)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_kernel_matches_scan_and_recurrence(on_tpu, case, dtype):
    """The kernel path's output and every gradient equal the scan's, and
    in f32 the sequential recurrence's."""
    B, S, chunk, d = KERNEL_CASES[case]
    p = jax.tree.map(lambda a: a.astype(dtype) if a.ndim > 1 else a,
                     _params(d_model=d))
    x, r = _x(S, B, d_model=d).astype(dtype), _x(S, B, seed=2, d_model=d)
    on_tpu(False)
    fwd = jax.jit(lambda p, x: mamba2_forward(p, x, chunk=chunk))
    want = _out_and_grads(fwd, p, x, r)
    on_tpu(True)
    fwd = jax.jit(lambda p, x: mamba2_forward(p, x, chunk=chunk))
    got = _out_and_grads(fwd, p, x, r)
    # the two paths sum in different orders; A_log's gradient, a sum over
    # every position of cancelling terms, differs most (f32: 3.4e-5 at
    # sub_blocks; bf16: 6.1e-3 at w_dt), and the scan itself reads 1.7e-4
    # from the recurrence there in f32
    tol = 1e-4 if dtype == jnp.float32 else 1.5e-2
    for (path, a), (_, b) in zip(_leaves(got), _leaves(want)):
        assert a.dtype == b.dtype, path
        _close(a, b, tol)
    if dtype == jnp.float32:
        ref = _out_and_grads(lambda p, x: _reference(p, x)[0], p, x, r)
        for (path, a), (_, b) in zip(_leaves(got), _leaves(ref)):
            _close(a, b, 5e-4)


def test_kernel_gradients_finite_where_decay_sums_overflow(on_tpu):
    """|A|·dt·chunk = 16 · 0.1 · 128 = 204.8: the kernel masks the upper
    triangle's segment sums before ``exp`` too, and its backward matches
    the recurrence's."""
    p, x = _params(a_log=math.log(16.0), dt=0.1), _x(256, B=1)
    r = _x(256, B=1, seed=2)
    _, g = _out_and_grads(jax.jit(
        lambda p, x: mamba2_forward(p, x, chunk=128)), p, x, r)
    _, g_ref = _out_and_grads(lambda p, x: _reference(p, x)[0], p, x, r)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        _close(a, b, 1e-4)


@pytest.mark.parametrize("S", [200, 512])
def test_kernel_prefill_state_matches_scan(on_tpu, S):
    """``return_state`` on the kernel path gives the scan's final f32 state
    and conv tail (S 200: the padded tail leaves the state as it was)."""
    p, x = _params(), _x(S)
    on_tpu(False)
    fwd = jax.jit(lambda p, x: mamba2_forward(p, x, chunk=128,
                                              return_state=True))
    out, cache = fwd(p, x)
    on_tpu(True)
    fwd = jax.jit(lambda p, x: mamba2_forward(p, x, chunk=128,
                                              return_state=True))
    k_out, k_cache = fwd(p, x)
    _close(k_out, out, 2e-5)
    assert k_cache["state"].dtype == jnp.float32
    assert k_cache["state"].shape == cache["state"].shape
    _close(k_cache["state"], cache["state"], 2e-5)
    np.testing.assert_array_equal(k_cache["conv"], cache["conv"])
    _close(k_cache["state"], _reference(p, x)[1], 1e-5)


def test_kernel_gradient_through_the_state_matches_scan(on_tpu):
    """The final state's cotangent runs the kernels' recurrence backwards
    from a non-zero start: its gradients equal the scan's."""
    p, x = _params(), _x(256, B=1)
    r = _x(256, B=1, seed=2)
    rs = jnp.asarray(np.random.default_rng(3).standard_normal(
        (1, 2, HEAD_P, D_STATE)), jnp.float32)

    def grads():
        def loss(p, x):
            out, cache = mamba2_forward(p, x, chunk=128, return_state=True)
            return jnp.sum(out * r) + jnp.sum(cache["state"] * rs)
        return jax.jit(jax.grad(loss, argnums=(0, 1)))(p, x)
    on_tpu(False)
    want = grads()
    on_tpu(True)
    got = grads()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(a, b, 1e-4)


def test_dispatch_records_the_kernel(on_tpu, ssm_records):
    p, x = _params(), _x(256, B=1)
    jax.jit(lambda p, x: mamba2_forward(p, x, chunk=128))(p, x)
    assert ssm_records() == [
        {"path": "pallas", "conv": "pallas", "x": [1, 256, D_MODEL],
         "chunk": 128, "chunks": 2, "pad": 0, "state_dtype": "float32",
         "return_state": False}]


def test_conv_kernel_runs_where_the_ssd_keeps_the_scan(on_tpu, ssm_records):
    """At chunk 64, which the SSD kernels do not tile, the scan runs after
    the conv kernel, and the block's output and gradients equal the CPU
    path's."""
    p, x = _params(), _x(196)
    r = _x(196, seed=2)
    on_tpu(False)
    fwd = jax.jit(lambda p, x: mamba2_forward(p, x, chunk=64))
    want = _out_and_grads(fwd, p, x, r)
    on_tpu(True)
    fwd = jax.jit(lambda p, x: mamba2_forward(p, x, chunk=64))
    got = _out_and_grads(fwd, p, x, r)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(a, b, 1e-4)
    assert [(a["path"], a.get("reason"), a["conv"]) for a in ssm_records()
            ] == [("scan", "backend", "xla"), ("scan", "chunk", "pallas")]


def test_dispatch_picks_the_kernel_at_the_cells_shape(on_tpu):
    """2 × 4096 positions of 80 heads of 64 at chunk 256 (mamba2-2.7b)."""
    assert ops.ssd_reason((2, 4096, 80 * HEAD_P), 80, 256) is None


@pytest.mark.parametrize("reason,H,chunk", [
    ("chunk", 80, 64), ("chunk", 80, 200), ("chunk", 80, 512),
    ("width", 5, 256)])
def test_dispatch_names_what_the_kernel_cannot_tile(on_tpu, reason, H,
                                                    chunk):
    assert ops.ssd_reason((2, 4096, H * HEAD_P), H, chunk) == reason


def test_dispatch_keeps_the_scan_where_heads_split_apart(on_tpu):
    """On a model axis of 2 the heads would be split across shards; on a
    data axis of 2 the kernels run per batch shard.  The conv kernel
    follows the same rule."""
    from jax.sharding import AbstractMesh
    shape = (2, 4096, 80 * HEAD_P)
    with jax.sharding.use_abstract_mesh(AbstractMesh((1, 2),
                                                     ("data", "model"))):
        assert ops.ssd_reason(shape, 80, 256) == "heads_sharding"
        assert ops.conv_silu_reason(shape) == "heads_sharding"
    with jax.sharding.use_abstract_mesh(AbstractMesh((2, 1),
                                                     ("data", "model"))):
        assert ops.ssd_reason(shape, 80, 256) is None
        assert ops.conv_silu_reason(shape) is None


@pytest.mark.parametrize("H,chunk", [(80, 64), (80, 200), (5, 256)])
def test_conv_dispatch_apart_from_the_ssds(on_tpu, H, chunk):
    """A chunk or a head count the SSD kernels cannot tile leaves the conv
    kernel on."""
    shape = (2, 4096, H * HEAD_P)
    assert ops.ssd_reason(shape, H, chunk) is not None
    assert ops.conv_silu_reason(shape) is None

"""The flash attention kernel vs its pure-jnp oracle and the XLA scan, and
the choice ``attend`` makes through ``kernels/ops`` (interpret mode)."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels import ops, ref
from repro.models import attention
from repro.models.transformer import _remat_wrap


def _qkv(B, H, KH, S, D, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, H, S, D), dtype),
            jax.random.normal(ks[1], (B, KH, S, D), dtype),
            jax.random.normal(ks[2], (B, KH, S, D), dtype),
            jax.random.normal(ks[3], (B, H, S, D), dtype))


def _vjp(fn, q, k, v, do):
    out, back = jax.vjp(fn, q, k, v)
    return (out,) + tuple(back(do))


def _bshd(x):
    return x.transpose(0, 2, 1, 3)


def _flash_bhsd(causal):
    """``ops.flash_attention`` on (B, H, S, D) operands, the oracle's
    layout."""
    return lambda q, k, v: _bshd(ops.flash_attention(  # noqa: E731
        _bshd(q), _bshd(k), _bshd(v), causal=causal))


@pytest.mark.parametrize("B,H,KH,S,D", [
    (1, 2, 1, 128, 64),
    (2, 4, 2, 256, 128),
    (1, 8, 2, 96, 80),        # non-multiple S and D (padding path)
    (1, 1, 1, 64, 128),
    (1, 14, 2, 256, 64),      # the InternVL2 heads, one block
    (2, 14, 2, 100, 64),      # S padded to 104, padded keys masked
    (1, 4, 2, 1100, 64),      # 9 blocks of 128 after padding to 1152
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, H, KH, S, D, dtype, causal):
    """Output, and dq, dk, dv of the kernel's custom VJP against autodiff
    of the f32 full-score oracle."""
    q, k, v, do = _qkv(B, H, KH, S, D, dtype)
    out, dq, dk, dv = _vjp(_flash_bhsd(causal), q, k, v, do)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    atol = 2e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=atol)
    f32 = [x.astype(jnp.float32) for x in (q, k, v, do)]
    grads = _vjp(lambda q, k, v: ref.flash_attention_ref(
        q, k, v, causal=causal), *f32)[1:]
    rtol = 1e-4 if dtype == jnp.float32 else 1e-2
    for name, g, w in zip(("dq", "dk", "dv"), (dq, dk, dv), grads):
        assert g.dtype == dtype, name
        err = float(jnp.max(jnp.abs(g.astype(jnp.float32) - w)))
        assert err <= rtol * float(jnp.max(jnp.abs(w))), (name, err)


@pytest.mark.parametrize("S", [256, 1100])
def test_flash_attention_grad_matches_chunked_scan(S):
    """The kernel and the XLA scan it replaces agree in f32, forward and
    backward (the scan at chunks that do not divide S)."""
    q, k, v, do = map(_bshd, _qkv(1, 14, 2, S, 64, jnp.float32, seed=1))
    got = _vjp(ops.flash_attention, q, k, v, do)
    want = _vjp(lambda q, k, v: attention.chunked_attention(
        q, k, v, chunk=384), q, k, v, do)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=1e-4)


@pytest.fixture
def dispatch_records(tmp_path):
    """The ``attn.dispatch`` records written while a test runs."""
    path = tmp_path / "spans.jsonl"
    obs.configure(str(path))

    def records():
        obs.flush()
        recs = [json.loads(line) for line in path.read_text().splitlines()]
        return [r["attrs"] for r in recs if r["name"] == "attn.dispatch"]
    yield records
    obs.configure(None)


def _attend_shapes(Sq=64, Sk=64, hd=64, hd_v=64):
    f32 = jnp.float32
    return (jax.ShapeDtypeStruct((2, Sq, 14, hd), f32),
            jax.ShapeDtypeStruct((2, Sk, 2, hd), f32),
            jax.ShapeDtypeStruct((2, Sk, 2, hd_v), f32))


@pytest.mark.parametrize("reason,kwargs,shapes", [
    ("window", {"window": 16}, {}),
    ("q_offset", {"q_offset": 8}, {}),
    ("kv_length", {"causal": False}, {"Sk": 48}),
    ("head_dim", {}, {"hd": 96, "hd_v": 64}),
    ("vmem", {}, {"Sq": 40960, "Sk": 40960}),
])
def test_attend_keeps_the_scan(on_tpu, dispatch_records, reason, kwargs,
                               shapes):
    """On a TPU backend, each shape or option the kernel does not take
    sends ``attend`` to the scan, once per trace, and says why."""
    fn = lambda q, k, v: attention.attend(q, k, v, **kwargs)  # noqa: E731
    jax.eval_shape(fn, *_attend_shapes(**shapes))
    [rec] = dispatch_records()
    assert rec["path"] == "scan" and rec["reason"] == reason


def test_attend_keeps_the_scan_off_tpu(dispatch_records):
    jax.eval_shape(attention.attend, *_attend_shapes())
    [rec] = dispatch_records()
    assert rec == {"path": "scan", "reason": "backend", "q": [2, 64, 14, 64],
                   "k": [2, 64, 2, 64], "v": [2, 64, 2, 64], "causal": True}


def test_attend_keeps_the_scan_where_heads_split_apart(on_tpu,
                                                       dispatch_records):
    """On a model axis of 2, 14 q heads split but 7 kv heads do not: the
    GQA map would cross shards, so the scan runs.  With 2 kv heads both
    split and the kernel runs."""
    from jax.sharding import AbstractMesh
    f32 = jnp.float32
    q = jax.ShapeDtypeStruct((2, 64, 14, 64), f32)
    k = jax.ShapeDtypeStruct((2, 64, 7, 64), f32)
    mesh = AbstractMesh((1, 2), ("data", "model"))
    with jax.sharding.use_abstract_mesh(mesh):
        assert ops.flash_attention_reason(q, k, k) == "heads_sharding"
        k2 = jax.ShapeDtypeStruct((2, 64, 2, 64), f32)
        assert ops.flash_attention_reason(q, k2, k2) is None


def test_attend_runs_the_kernel_on_tpu(on_tpu, dispatch_records):
    """With the backend taken for a TPU (the kernel itself interpreted),
    causal self-attention runs the kernel and agrees with the scan,
    gradients included."""
    q, k, v, do = (_bshd(x) for x in _qkv(2, 14, 2, 160, 64, jnp.float32,
                                           seed=2))
    got = _vjp(attention.attend, q, k, v, do)
    [rec] = dispatch_records()
    assert rec["path"] == "pallas" and "reason" not in rec
    assert rec["saved"] == list(ops.REMAT_SAVED)
    want = _vjp(lambda q, k, v: attention.chunked_attention(q, k, v,
                                                            chunk=64),
                q, k, v, do)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=1e-4)


_FULL = types.SimpleNamespace(remat="full")


def _layer_scan_grad(wrap):
    """The gradient in the weights of a ``lax.scan`` over three layers of
    ``wrap(block)``, where a block projects h (2, 64, 64) to q (4 heads of
    16) and k, v (2 heads) and adds ``attend``'s output, projected, to h.
    The block is made anew for each call, so no two share a trace."""
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 64))

    def block(h, w):
        B, S, E = h.shape
        q = (h @ w[0]).reshape(B, S, 4, 16)
        k, v = ((h @ w[i])[..., :32].reshape(B, S, 2, 16) for i in (1, 2))
        o = attention.attend(q, k, v).reshape(B, S, E)
        return h + o @ w[3], None

    def loss(ws):
        return jnp.sum(jax.lax.scan(wrap(block), h, ws)[0] ** 2)
    return jax.grad(loss)


def _flash_forwards(jaxpr) -> int:
    """How many flash forward kernels (the ``pallas_call``s with two
    outputs, o and lse) the jaxpr holds, nested jaxprs included."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += len(eqn.outvars) == 2
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _flash_forwards(sub)
    return n


def test_full_remat_keeps_the_flash_forward_outputs(on_tpu):
    """On the kernel's path the ``"full"`` remat keeps the forward's o and
    lse: the backward runs the forward kernel once, not twice as under a
    plain ``jax.checkpoint``, and the gradients are the same bits."""
    ws = jax.random.normal(jax.random.PRNGKey(1), (3, 4, 64, 64)) * 0.1
    remat = lambda fn: _remat_wrap(_FULL, fn)  # noqa: E731
    counts = [_flash_forwards(jax.make_jaxpr(_layer_scan_grad(w))(ws).jaxpr)
              for w in (remat, jax.checkpoint)]
    assert counts == [1, 2]
    np.testing.assert_array_equal(_layer_scan_grad(remat)(ws),
                                  _layer_scan_grad(jax.checkpoint)(ws))


def test_full_remat_off_the_kernel_is_a_plain_checkpoint():
    """Where attention runs the scan nothing is named, and the ``"full"``
    remat lowers to the very program a plain ``jax.checkpoint`` does."""
    ws = jax.ShapeDtypeStruct((3, 4, 64, 64), jnp.float32)
    remat = lambda fn: _remat_wrap(_FULL, fn)  # noqa: E731
    got, want = (jax.jit(_layer_scan_grad(w)).lower(ws).as_text()
                 for w in (remat, jax.checkpoint))
    assert got == want

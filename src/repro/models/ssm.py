"""Mamba-2 (SSD — state-space duality) block: chunked train scan + O(1) decode.

The block is the published ``mamba_ssm`` ``Mamba2`` mixer (Dao & Gu,
arXiv:2405.21060): projections of the input to z, x, B, C and dt; one
depthwise causal conv with bias over the concatenated x, B and C, then
SiLU on all three; the SSD recurrence h_t = exp(dt_t·A)·h_{t-1} +
dt_t·B_t ⊗ x_t, y_t = C_t·h_t + D·x_t per head; the gated RMSNorm of
y·SiLU(z); the out-projection.

TPU adaptation (DESIGN.md §2): the CUDA Mamba kernel is a fused warp-level
scan; the TPU-native formulation is the SSD *chunked* algorithm — quadratic
attention-like compute inside fixed-size chunks (MXU-friendly (Q,Q) matmuls)
with a sequential inter-chunk state recurrence.  Two paths compute it, the
same math in the same precisions:

  * on a TPU, where the chunk is one the kernels tile (128 or 256) and
    the heads pair into 128 lanes, ``ops.ssd`` (``kernels/ssd_chunk.py``):
    Pallas kernels with their own backward for each chunk's state, the
    recurrence over chunks, and the diagonal block with the read-out and
    D·x over (batch, chunk, head block), so no (Q,Q) tile leaves VMEM; the
    in-chunk ``cumsum`` of the log-decays stays in XLA;
  * elsewhere (the CPU, other chunks or widths, heads split across a mesh)
    a ``lax.scan`` of ``_ssd_chunk`` over the chunks, which is also the
    kernels' oracle in the tests.

The xBC conv and its SiLU choose apart from the SSD, as they tile any
length and width: on a TPU ``ops.conv_silu`` (``kernels/causal_conv.py``),
one pass each way, elsewhere (and where heads are split across a mesh) the
f32 shifted adds of ``_causal_conv``.  ``repro.kernels.ops`` makes both
choices (``ops.ssd_reason``, ``ops.conv_silu_reason``) and runs the
kernels per batch shard under a mesh; ``mamba2_forward`` records them
(``ssm.dispatch``: ``path`` and ``conv``, and for each path kept in XLA
its reason).  Decode carries (conv window, SSM
state) and is O(1) per token — which is why mamba2 runs the ``long_500k``
cell that dense-attention archs skip.

Numerics follow the published kernels: the conv's sums and SiLU, the
carried state, the log-decay sums, every decay factor and the norm's gate
are f32; the intra-chunk, state and read-out matmuls take operands in the
model's dtype and accumulate in f32.  A decay is only ever ``exp`` of a
sum of non-positive log-decays: the upper triangle of the intra-chunk
segment sums is masked to −inf *before* ``exp``, so no positive sum
overflows (its backward would be 0·inf).

Simplifications vs the reference CUDA implementation:
  * n_groups = 1 (B/C shared across heads),
  * the in-projection is held as five matrices (z, x, B, C, dt): the
    published one split by columns,
  * the program's own init keeps A = −1 and dt_bias = 0 (the published
    A ~ U(1, 16), dt ~ logU(1e-3, 0.1) are the benchmark's init rules).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ops
from repro.sharding import shard, use_weight
from .paramdecl import normal_param, zeros_param, ones_param, split_keys
from .layers import rmsnorm_init, rmsnorm

Params = Dict[str, Any]

CONV_K = 4         # short depthwise conv kernel width
HEAD_P = 64        # SSD head dim
F32 = jnp.float32


def mamba2_init(key, d: int, d_state: int, dtype, *, expand: int = 2) -> Params:
    d_inner = expand * d
    n_heads = d_inner // HEAD_P
    conv_dim = d_inner + 2 * d_state          # x, B and C
    k1, k2, k3, k4, k5, k6, k7 = split_keys(key, 7)
    return {
        "wz": normal_param(k1, (d, d_inner), dtype, "fsdp", "ff_mega"),
        "wx": normal_param(k2, (d, d_inner), dtype, "fsdp", "ff_mega"),
        "wB": normal_param(k3, (d, d_state), dtype, "fsdp", "out_fsdp"),
        "wC": normal_param(k4, (d, d_state), dtype, "fsdp", "out_fsdp"),
        "w_dt": normal_param(k5, (d, n_heads), dtype, "fsdp", "heads"),
        "dt_bias": zeros_param(k5, (n_heads,), F32, "heads"),
        "A_log": zeros_param(k5, (n_heads,), F32, "heads"),
        "D": ones_param(k5, (n_heads,), F32, "heads"),
        "conv": normal_param(k6, (CONV_K, conv_dim), dtype, None, None,
                             scale=0.5),
        "conv_b": zeros_param(k6, (conv_dim,), dtype, None),
        "norm": rmsnorm_init(k7, d_inner, dtype),
        "w_out": normal_param(k7, (d_inner, d), dtype, "heads", "out_fsdp"),
    }


def _causal_conv(x: jax.Array, kernel: jax.Array, bias: jax.Array
                 ) -> jax.Array:
    """SiLU of the depthwise causal conv with bias, via shifted adds, in
    x's dtype.  x: (B,S,D); kernel: (K,D), its last row on the current
    position; bias: (D,).  Sums in f32, as the published kernel
    accumulates."""
    xf, kernel = x.astype(F32), kernel.astype(F32)
    out = xf * kernel[-1] + bias.astype(F32)
    for i in range(1, CONV_K):
        shifted = jnp.pad(xf, ((0, 0), (i, 0), (0, 0)))[:, :-i or None, :]
        out = out + shifted * kernel[CONV_K - 1 - i]
    return jax.nn.silu(out).astype(x.dtype)


def _gated_norm(p: Params, y: jax.Array, z: jax.Array) -> jax.Array:
    """RMSNorm(y · SiLU(z)), the gate taken in f32; in y's dtype."""
    g = y.astype(F32) * jax.nn.silu(z.astype(F32))
    return rmsnorm(p, g).astype(y.dtype)


def _ssd_chunk(D: jax.Array, state: jax.Array, inp):
    """One chunk of the SSD scan.  state: (B,H,P,N) f32; inp: x (B,Q,H,P),
    B and C (B,Q,N) in the model's dtype, dt and dt·A (B,Q,H) in f32.
    Returns the state after the chunk and y (B,Q,H,P) in x's dtype."""
    xq, bq, cq, dtq, daq = inp
    Q = xq.shape[1]
    cum = jnp.cumsum(daq, axis=1)                          # (B,Q,H), falling
    # decay from j to i >= j: exp(cum_i - cum_j) <= 1; above the diagonal
    # the sum is positive and is masked before exp, never after
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
    seg = jnp.where(causal, cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf)
    cb = jnp.einsum("bin,bjn->bij", cq, bq, preferred_element_type=F32)
    M = (cb[..., None] * jnp.exp(seg) * dtq[:, None]).astype(xq.dtype)
    y = jnp.einsum("bijh,bjhp->bihp", M, xq, preferred_element_type=F32)
    # read-out of the state carried in from earlier chunks
    y = y + jnp.einsum("bin,bhpn->bihp", cq, state.astype(cq.dtype),
                       preferred_element_type=F32) * jnp.exp(cum)[..., None]
    y = y + xq.astype(F32) * D[:, None]
    # the state after the chunk: decay j..end times dt_j on each input
    w = jnp.exp(cum[:, -1:] - cum) * dtq                   # (B,Q,H)
    xs = (xq * w[..., None]).astype(xq.dtype)
    state = state * jnp.exp(cum[:, -1])[:, :, None, None] + jnp.einsum(
        "bjn,bjhp->bhpn", bq, xs, preferred_element_type=F32)
    return state, y.astype(xq.dtype)


def _ssd_scan(D: jax.Array, X, Bm, Cm, dt, dA, chunk: int):
    """The chunked SSD as a ``lax.scan`` of ``_ssd_chunk`` over the chunks:
    the CPU path and the kernel's oracle.  X (B,S,H,P), Bm/Cm (B,S,N),
    dt/dA (B,S,H) f32, S a multiple of ``chunk``.  Returns the final f32
    state (B,H,P,N) and y (B,S,H·P) in X's dtype."""
    B_, S, H, P = X.shape
    nc = S // chunk

    def to_chunks(t):
        return t.reshape((B_, nc, chunk) + t.shape[2:]).swapaxes(0, 1)

    state0 = jnp.zeros((B_, H, P, Bm.shape[-1]), F32)
    state_f, Yc = jax.lax.scan(
        lambda s, inp: _ssd_chunk(D, s, inp), state0,
        tuple(map(to_chunks, (X, Bm, Cm, dt, dA))))
    return state_f, Yc.swapaxes(0, 1).reshape(B_, S, H * P)


def mamba2_forward(p: Params, x: jax.Array, *, chunk: int = 128,
                   return_state: bool = False):
    """x: (B, S, d) -> (B, S, d) via the SSD chunked algorithm: the Pallas
    kernels where the backend and widths allow it, else the ``lax.scan``
    of ``_ssd_chunk``, after the conv kernel or ``_causal_conv``; the
    choices are recorded as ``ssm.dispatch`` when the caller is traced.
    With ``return_state`` also the decode cache: the last K-1 conv inputs
    and the f32 state."""
    with jax.named_scope("ssm"):
        B_, S, d = x.shape
        d_inner = p["wx"].shape[-1]
        H = d_inner // HEAD_P
        N = p["wB"].shape[-1]
        chunk = min(chunk, S)
        nc = (S + chunk - 1) // chunk
        pad = nc * chunk - S
        reason = ops.ssd_reason((B_, S, d_inner), H, chunk)
        conv_reason = ops.conv_silu_reason((B_, S, d_inner))
        attrs = {"path": "scan" if reason else "pallas",
                 "conv": "xla" if conv_reason else "pallas",
                 "x": list(x.shape), "chunk": chunk, "chunks": nc,
                 "pad": pad, "state_dtype": "float32",
                 "return_state": return_state}
        if reason:
            attrs["reason"] = reason
        if conv_reason:
            attrs["conv_reason"] = conv_reason
        with obs.span("ssm.dispatch", **attrs):
            pass
        z = jnp.einsum("bsd,de->bse", x, use_weight(p["wz"], None, "heads"))
        xbc_pre = jnp.concatenate([
            jnp.einsum("bsd,de->bse", x, use_weight(p["wx"], None, "heads")),
            jnp.einsum("bsd,dn->bsn", x, p["wB"]),
            jnp.einsum("bsd,dn->bsn", x, p["wC"])], axis=-1)
        conv = _causal_conv if conv_reason else ops.conv_silu
        xbc = conv(xbc_pre, p["conv"], p["conv_b"])
        X = xbc[..., :d_inner].reshape(B_, S, H, HEAD_P)
        Bm, Cm = xbc[..., d_inner:d_inner + N], xbc[..., d_inner + N:]
        dt = jax.nn.softplus(
            jnp.einsum("bsd,dh->bsh", x, p["w_dt"]).astype(F32)
            + p["dt_bias"])                                  # (B,S,H)
        dA = dt * -jnp.exp(p["A_log"])                       # log-decay <= 0

        if pad:
            X = jnp.pad(X, ((0, 0), (0, pad), (0, 0), (0, 0)))
            Bm, Cm, dt, dA = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                              for t in (Bm, Cm, dt, dA))
        ssd = _ssd_scan if reason else ops.ssd
        state_f, Y = ssd(p["D"], X, Bm, Cm, dt, dA, chunk)
        Y = _gated_norm(p["norm"], Y[:, :S], z)
        out = jnp.einsum("bse,ed->bsd", Y,
                         use_weight(p["w_out"], "heads", None))
        out = shard(out, "batch", None, None)
        if not return_state:
            return out
        tail = jnp.pad(xbc_pre, ((0, 0), (CONV_K - 1, 0), (0, 0)))[
            :, S:S + CONV_K - 1, :]
        return out, {"conv": tail, "state": state_f}


def mamba2_decode(p: Params, x: jax.Array, cache: Params
                  ) -> Tuple[jax.Array, Params]:
    """One-token step.  x: (B, 1, d); cache: {"conv": (B, K-1, E+2N),
    "state": (B, H, P, N) f32}.  O(1) in sequence length."""
    with jax.named_scope("ssm"):
        B_ = x.shape[0]
        d_inner = p["wx"].shape[-1]
        H = d_inner // HEAD_P
        N = p["wB"].shape[-1]
        xt = x[:, 0]
        z = xt @ p["wz"]
        xbc = jnp.concatenate([xt @ p["wx"], xt @ p["wB"], xt @ p["wC"]],
                              axis=-1)                       # (B, E+2N)
        window = jnp.concatenate([cache["conv"], xbc[:, None, :]], axis=1)
        xbc = jax.nn.silu(jnp.einsum("bkc,kc->bc", window.astype(F32),
                                     p["conv"].astype(F32))
                          + p["conv_b"].astype(F32)).astype(x.dtype)
        X = xbc[:, :d_inner].reshape(B_, H, HEAD_P).astype(F32)
        Bt = xbc[:, d_inner:d_inner + N].astype(F32)
        Ct = xbc[:, d_inner + N:].astype(F32)
        dt = jax.nn.softplus((xt @ p["w_dt"]).astype(F32) + p["dt_bias"])
        a = jnp.exp(dt * -jnp.exp(p["A_log"]))                 # (B,H)
        state = cache["state"] * a[:, :, None, None] \
            + jnp.einsum("bn,bhp->bhpn", Bt, X * dt[..., None])
        y = jnp.einsum("bn,bhpn->bhp", Ct, state) + X * p["D"][:, None]
        y = _gated_norm(p["norm"], y.reshape(B_, d_inner).astype(x.dtype), z)
        out = (y @ p["w_out"])[:, None, :]
        return out, {"conv": window[:, 1:], "state": state}


def mamba2_cache_spec(batch: int, d: int, d_state: int, dtype, *,
                      expand: int = 2) -> Params:
    from .paramdecl import SpecLeaf
    d_inner = expand * d
    H = d_inner // HEAD_P
    return {
        "conv": SpecLeaf((batch, CONV_K - 1, d_inner + 2 * d_state),
                         jnp.dtype(dtype), ("batch", None, None)),
        "state": SpecLeaf((batch, H, HEAD_P, d_state), jnp.dtype(F32),
                          ("batch", "heads", None, None)),
    }

"""Plain reference of ``mamba2-2.7b`` as the benchmark trains it: the
published Mamba-2 language model (``mamba_ssm`` ``MambaLMHeadModel`` with
``Mamba2`` mixers, arXiv:2405.21060), tied embeddings, next-token cross
entropy.

Each layer: pre-norm, in-projection to z, x, B, C and dt, the depthwise
causal conv with bias over x, B and C, SiLU, the SSD in its quadratic
(masked attention) form y = (L ∘ C Bᵀ) (dt·x) + D·x, whose decay matrix
L_ij = exp(Σ_{k=j+1..i} dA_k) is built from an exact segment sum (the
paper's ``segsum``) and masked to −inf above the diagonal before ``exp``;
then the gated RMSNorm of y·SiLU(z), the out-projection and the residual.
Everything is f32 and every matmul goes through ``num.einsum``; one head
at a time, so that the S × S matrices fit.  It shares no code and no
algorithm with the program's chunked scan.  ``model`` is the
configuration file's ``model`` block.
"""

import jax
import jax.numpy as jnp

from lib.plain import ce_mean, rmsnorm

F32 = jnp.float32
HEAD = 64        # published headdim
CONV = 4         # published d_conv


def _segsum(a):
    """a: (B, S) log-decays -> (B, S, S) with [i, j] = Σ_{k=j+1..i} a_k
    for j <= i and −inf above the diagonal."""
    S = a.shape[-1]
    below = jnp.tril(jnp.ones((S, S), bool), -1)
    rep = jnp.where(below, a[:, :, None], 0.0)            # [b, k, j] = a_k, k > j
    sums = jnp.cumsum(rep, axis=1)
    return jnp.where(jnp.tril(jnp.ones((S, S), bool)), sums, -jnp.inf)


def _conv(x, w, b):
    """Depthwise causal conv of x (B, S, C) with w (CONV, C), w[-1] on the
    current position, and bias b."""
    S = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (CONV - 1, 0), (0, 0)))
    return sum(padded[:, k:k + S] * w[k].astype(F32)
               for k in range(CONV)) + b.astype(F32)


def _mixer(num, s, h, model):
    E = model["ssm_expand"] * model["d_model"]
    N, H = model["ssm_state"], E // HEAD
    B, S, _ = h.shape
    z = num.einsum("bsd,de->bse", h, s["wz"])
    xbc = jnp.concatenate([num.einsum("bsd,de->bse", h, s["wx"]),
                           num.einsum("bsd,dn->bsn", h, s["wB"]),
                           num.einsum("bsd,dn->bsn", h, s["wC"])], -1)
    xbc = jax.nn.silu(_conv(xbc, s["conv"], s["conv_b"]))
    x = xbc[..., :E].reshape(B, S, H, HEAD)
    Bm, Cm = xbc[..., E:E + N], xbc[..., E + N:]
    dt = jax.nn.softplus(num.einsum("bsd,dh->bsh", h, s["w_dt"])
                         + s["dt_bias"].astype(F32))
    dA = dt * -jnp.exp(s["A_log"].astype(F32))
    cb = num.einsum("bin,bjn->bij", Cm, Bm)                # (B, S, S)

    @jax.checkpoint
    def head(i):
        L = jnp.exp(_segsum(dA[:, :, i]))
        xh = x[:, :, i] * dt[:, :, i, None]
        return num.einsum("bij,bjp->bip", cb * L, xh)     # (B, S, HEAD)

    y = jax.lax.map(head, jnp.arange(H)).transpose(1, 2, 0, 3)
    y = y + x * s["D"].astype(F32)[:, None]
    y = y.reshape(B, S, E) * jax.nn.silu(z)
    y = rmsnorm(y, s["norm"]["scale"])
    return num.einsum("bse,ed->bsd", y, s["w_out"])


def loss(num, p, batch, model):
    x = jnp.take(p["embed"]["table"].astype(F32), batch["tokens"], axis=0)

    @jax.checkpoint
    def layer(x, lp):
        return x + _mixer(num, lp["ssm"], rmsnorm(x, lp["ln"]["scale"]),
                          model), None

    x, _ = jax.lax.scan(layer, x, p["blocks"])
    h = rmsnorm(x, p["final_norm"]["scale"])
    return ce_mean(num, h, p["embed"]["table"], batch["labels"])

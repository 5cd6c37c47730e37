"""Plain reference pieces for training: f32 arithmetic, matmuls at
``HIGHEST`` precision, no kernels, no fusion tricks.

``Numerics`` carries the one choice that separates the reference from its
control: the control (``control=True``) rounds every matmul operand, and
the gradient that flows back into it, to 8-bit floats with a per-tensor
scale, the precision one step below the bfloat16 the configurations
state.

:func:`train_reference` runs K steps of AdamW from the benchmark's own
weights over the same rows the program was fed, one row at a time, with
the first and second moments kept on the host so that it fits beside
nothing else on one chip.
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from lib.weights import slice_norms

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _quant(x, dtype, top):
    amax = jnp.max(jnp.abs(x))
    scale = top / jnp.maximum(amax, 1e-30)
    return (x * scale).astype(dtype).astype(F32) / scale


@jax.custom_vjp
def _fp8(x):
    return _quant(x, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_quant(g, jnp.float8_e5m2, 57344.0),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


class Numerics:
    """How the reference multiplies: f32 at HIGHEST, or (control) fp8."""

    def __init__(self, control: bool = False):
        self.control = control

    def einsum(self, spec: str, a, b):
        a, b = a.astype(F32), b.astype(F32)
        if self.control:
            a, b = _fp8(a), _fp8(b)
        return jnp.einsum(spec, a, b, precision=HIGHEST,
                          preferred_element_type=F32)


def rmsnorm(x, scale, eps: float = 1e-6):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _divisor_at_most(n: int, cap: int) -> int:
    return max(c for c in range(1, min(n, cap) + 1) if n % c == 0)


def ce_mean(num: Numerics, h, table, labels, chunk: int = 1024):
    """Mean next-token cross entropy of hidden states ``h`` (B, S, d)
    against the unembedding ``table`` (V, d), in sequence chunks so the
    (S, V) logits never exist at once."""
    B, S, D = h.shape
    cs = _divisor_at_most(S, chunk)
    hc = h.reshape(B, S // cs, cs, D).swapaxes(0, 1)
    yc = labels.reshape(B, S // cs, cs).swapaxes(0, 1)

    @jax.checkpoint
    def body(total, inp):
        hb, yb = inp
        logits = num.einsum("bsd,vd->bsv", hb, table)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yb[..., None], axis=-1)[..., 0]
        return total + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(body, jnp.zeros((), F32), (hc, yc))
    return total / (B * S)


# ------------------------------------------------------------------ AdamW
def _adam_leaf(p, g, m, v, count, *, lr, b1, b2, eps, weight_decay):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat = m / (1 - b1 ** count)
    vhat = v / (1 - b2 ** count)
    p32 = p.astype(F32)
    new = p32 - lr * (mhat / (jnp.sqrt(vhat) + eps) + weight_decay * p32)
    return new.astype(p.dtype), m, v


def train_reference(loss_fn, params, batches, opt: dict, num: Numerics):
    """K AdamW steps of ``loss_fn`` from ``params`` over ``batches`` (one
    host dict of rows per step).  Returns the loss of each step, the norm
    of every compared leaf of the first clipped gradient, the parameters
    after the last step, and the seconds it took."""
    t0 = time.perf_counter()

    def grad_row(p, row):
        return jax.value_and_grad(
            lambda q: loss_fn(num, q, row))(jax.tree.map(
                lambda x: x.astype(F32), p))

    grad_row = jax.jit(grad_row)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    scale_tree = jax.jit(lambda t, s: jax.tree.map(lambda x: x * s, t),
                         donate_argnums=(0,))
    gnorm_of = jax.jit(lambda t: jnp.sqrt(sum(
        jnp.sum(jnp.square(x)) for x in jax.tree.leaves(t))))
    norms_of = jax.jit(slice_norms)
    hyper = {k: opt[k] for k in ("lr", "b1", "b2", "eps", "weight_decay")}
    leaf_step = jax.jit(lambda p, g, m, v, c: _adam_leaf(p, g, m, v, c,
                                                         **hyper),
                        donate_argnums=(2, 3))

    leaves, treedef = jax.tree.flatten(params)
    moments = [(np.zeros(x.shape, np.float32), np.zeros(x.shape, np.float32))
               for x in leaves]
    losses, first_grad_norms = [], None
    for step, batch in enumerate(batches, start=1):
        rows = next(iter(batch.values())).shape[0]
        total, acc = 0.0, None
        for r in range(rows):
            row = {k: jnp.asarray(v[r:r + 1]) for k, v in batch.items()}
            loss, g = grad_row(params, row)
            total += float(loss)
            acc = g if acc is None else add(acc, g)
        acc = scale_tree(acc, jnp.asarray(1.0 / rows, F32))
        gnorm = float(gnorm_of(acc))
        clip = opt["grad_clip"]
        if clip and gnorm > clip:
            acc = scale_tree(acc, jnp.asarray(clip / max(gnorm, 1e-12), F32))
        if step == 1:
            first_grad_norms = np.asarray(norms_of(acc))
        losses.append(total / rows)
        grads = jax.tree.leaves(acc)
        del acc
        new_leaves = []
        count = jnp.asarray(float(step), F32)
        for i, (p, g) in enumerate(zip(jax.tree.leaves(params), grads)):
            m, v = moments[i]
            p2, m2, v2 = leaf_step(p, g, jnp.asarray(m), jnp.asarray(v),
                                   count)
            moments[i] = (np.asarray(m2), np.asarray(v2))
            new_leaves.append(p2)
        del grads
        params = jax.tree.unflatten(treedef, new_leaves)
    return losses, first_grad_norms, params, time.perf_counter() - t0


# ------------------------------------------------------------- comparison
def worst_leaf_gap(prog, ref, names, keep=None):
    """max over leaves of |prog - ref| / max(ref, median ref), where prog
    and ref are per-leaf norms; returns (gap, leaf name)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    idx = np.arange(len(ref)) if keep is None else np.flatnonzero(keep)
    med = float(np.median(ref[idx]))
    gaps = np.abs(prog[idx] - ref[idx]) / np.maximum(ref[idx], med)
    j = int(np.argmax(gaps))
    return float(gaps[j]), names[idx[j]]


def train_numbers(prog: dict, ref: dict, names):
    """The three numbers a training cell compares, from the program's and
    the reference's readings (``loss``: per-step losses, ``grad``: norms of
    the first clipped gradient's leaves, ``change``: norms of each leaf's
    change over the K steps).  Leaves whose reference gradient is under a
    thousandth of the median leaf's move by round-off alone and are left
    out of the change."""
    loss_gap = max(abs(a - b) for a, b in zip(prog["loss"], ref["loss"]))
    grad_gap, grad_leaf = worst_leaf_gap(prog["grad"], ref["grad"], names)
    g = np.asarray(ref["grad"], np.float64)
    keep = g >= 1e-3 * np.median(g)
    change_gap, change_leaf = worst_leaf_gap(prog["change"], ref["change"],
                                             names, keep)
    if not all(math.isfinite(x) for x in prog["loss"]):
        loss_gap = float("inf")
    return ({"loss_gap": loss_gap, "grad_gap": grad_gap,
             "change_gap": change_gap},
            {"grad_gap": grad_leaf, "change_gap": change_leaf,
             "left_out_of_change": int((~keep).sum())})

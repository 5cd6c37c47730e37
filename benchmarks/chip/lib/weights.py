"""Random weights and leaf naming, made by the benchmark from ``--seed``.

The program under test and the plain reference are both handed the same
parameter tree, which the benchmark draws here: the tree's structure and
dtypes come from the program's abstract init (``jax.eval_shape``), every
value from the seed and the configuration file's ``init`` rules.  Neither
side makes its own weights, so the reference takes nothing the program
has made.

A rule is a list, looked up by the leaf's dotted name, then by its last
component, then ``"*"``:

- ``["const", c]``: every element ``c``;
- ``["normal", std]``: N(0, std²);
- ``["uniform", lo, hi]``: U(lo, hi);
- ``["log_uniform", lo, hi]``: exp(U(ln lo, ln hi)).

A ``uniform`` or ``log_uniform`` rule may end in a transform of the draw:
``"log"`` (ln x) or ``"softplus_inverse"`` (x + ln(−expm1(−x)), so that
softplus of the leaf is the draw).  The published Mamba-2 init (the
``mamba_ssm`` ``Mamba2`` module) is then ``"A_log": ["uniform", 1, 16,
"log"]``, ``"dt_bias": ["log_uniform", 0.001, 0.1, "softplus_inverse"]``,
``"D": ["const", 1.0]`` and the depthwise conv's weight and bias
``["uniform", -0.5, 0.5]`` (PyTorch's default for fan-in 4).  The module
also clamps dt below at 1e-4, which never binds above 0.001, so no rule
carries it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_words(seed: int):
    """A whole seed, also one wider than 32 bits, as two uint32 words to
    pass into a jitted program (as an argument, so that one compiled
    program serves every seed)."""
    return (jnp.asarray(seed & 0xFFFFFFFF, jnp.uint32),
            jnp.asarray((seed >> 32) & 0xFFFFFFFF, jnp.uint32))


def base_key(words):
    key = jax.random.key(0)
    return jax.random.fold_in(jax.random.fold_in(key, words[0]), words[1])


def leaf_names(tree) -> list:
    """Dotted path of every leaf, in ``jax.tree.leaves`` order."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path) for path, _ in flat]


def _rule(rules: dict, name: str):
    last = name.rsplit(".", 1)[-1]
    return rules.get(name, rules.get(last, rules["*"]))


_TRANSFORMS = {
    "log": jnp.log,
    "softplus_inverse": lambda x: x + jnp.log(-jnp.expm1(-x)),
}


def _draw(key, shape, rule):
    kind = rule[0]
    if kind == "const":
        return jnp.full(shape, rule[1], jnp.float32)
    if kind == "normal":
        return jax.random.normal(key, shape, jnp.float32) * rule[1]
    if kind in ("uniform", "log_uniform") and len(rule) in (3, 4) \
            and all(t in _TRANSFORMS for t in rule[3:]):
        lo, hi = rule[1], rule[2]
        if kind == "uniform":
            x = jax.random.uniform(key, shape, jnp.float32, lo, hi)
        else:
            x = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                           math.log(lo), math.log(hi)))
        for t in rule[3:]:
            x = _TRANSFORMS[t](x)
        return x
    raise ValueError(f"unknown init rule {rule!r}")


def make_params(like, words, rules: dict):
    """Parameters shaped like ``like`` (a tree of ShapeDtypeStructs), drawn
    from the seed's :func:`seed_words`.  Call under ``jax.jit`` so the
    device makes them in one program."""
    names = leaf_names(like)
    leaves, treedef = jax.tree.flatten(like)
    key = base_key(words)
    out = []
    for i, (name, leaf) in enumerate(zip(names, leaves)):
        val = _draw(jax.random.fold_in(key, i), leaf.shape, _rule(rules, name))
        out.append(val.astype(leaf.dtype))
    return jax.tree.unflatten(treedef, out)


STACKED = "blocks"     # the scanned layers: one leaf of each per layer


def slice_names(tree) -> list:
    """Names of the leaves as compared: a leaf under ``blocks`` is one
    leaf per layer (``blocks.attn.wq[3]``), any other leaf is itself."""
    out = []
    for name, leaf in zip(leaf_names(tree), jax.tree.leaves(tree)):
        if name.startswith(STACKED + "."):
            out += [f"{name}[{i}]" for i in range(leaf.shape[0])]
        else:
            out.append(name)
    return out


def slice_norms(tree):
    """Euclidean norm of every compared leaf (see :func:`slice_names`), as
    one f32 vector; runs on the device under ``jax.jit``."""
    parts = []
    for name, leaf in zip(leaf_names(tree), jax.tree.leaves(tree)):
        x = leaf.astype(jnp.float32)
        if name.startswith(STACKED + "."):
            parts.append(jnp.sqrt(jnp.sum(jnp.square(
                x.reshape(x.shape[0], -1)), axis=1)))
        else:
            parts.append(jnp.sqrt(jnp.sum(jnp.square(x)))[None])
    return jnp.concatenate(parts)

"""Compiles for a described TPU v5e, no chip attached.

The TPU compiler refuses what interpret mode and the CPU backend accept: a
kernel tile the chip cannot lay out, or a step that does not fit the chip's
memory.  These tests compile the Pallas kernels at the widths the main
path runs them (the flash, SSD and conv kernels' backwards too) and the
full-width ``tinyllama-1.1b`` training step of ``chip_smoke.py`` for one
chip of a ``v5e:2x2`` topology, and training attention and the Mamba-2
kernels on all four chips, with and without the layers' ``"full"`` remat.
"""

import contextlib
import functools
import os
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import extract_graph, parse_hlo_module, aggregate_costs
from repro.core.task import DEVICE_STREAM
from repro.kernels import causal_conv, flash_attention, ops, ssd_chunk
from repro.models.model import count_params
from repro.models import attention
from repro.models.transformer import _remat_wrap
from repro.train import Trainer, TrainerConfig


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_calls(text: str) -> int:
    return text.count('custom_call_target="tpu_custom_call"')


def _four_chips(topo):
    """The (data=4, model=1) mesh of the four chips, and its batch split."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as np
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    return mesh, NamedSharding(mesh, P("data"))


_FULL = types.SimpleNamespace(remat="full")


def _kernel_case(name, chip):
    """(kernel call with interpret=False, argument shapes) at the widths the
    main path uses: tinyllama's attention heads over 4096 tokens (head dim
    128), the backward at the ``internvl2-1b.train`` cell's widths (3 x 4096,
    14 q / 2 kv heads of 64), the chunked SSD and the xBC conv at the
    ``mamba2-2.7b.train`` cell's (2 x 4096, 80 heads of 64, state 128,
    chunk 256, 5376 conv channels)."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    if name == "flash_attention":
        return (lambda q, k, v: flash_attention.flash_attention(
                    q, k, v, interpret=False),
                [_sds((1, 32, 4096, 128), bf16, chip),
                 _sds((1, 4, 4096, 128), bf16, chip),
                 _sds((1, 4, 4096, 128), bf16, chip)])
    if name == "flash_attention_bwd":
        def loss(q, k, v):
            o = flash_attention.flash_attention(q, k, v, interpret=False)
            return jnp.sum(o.astype(f32))
        return (jax.grad(loss, argnums=(0, 1, 2)),
                [_sds((3, 14, 4096, 64), bf16, chip),
                 _sds((3, 2, 4096, 64), bf16, chip),
                 _sds((3, 2, 4096, 64), bf16, chip)])
    if name.startswith("ssd_chunk"):
        def ssd(x, b, c, dt, cum, d):
            y, state = ssd_chunk.ssd(x, b, c, dt, cum, d, chunk=256,
                                     interpret=False)
            return jnp.sum(y.astype(f32) ** 2) + jnp.sum(state)
        fn = ssd if name == "ssd_chunk" else jax.grad(
            ssd, argnums=(0, 1, 2, 3, 4, 5))
        return (fn, [_sds((2, 4096, 80 * 64), bf16, chip),
                     _sds((2, 4096, 128), bf16, chip),
                     _sds((2, 4096, 128), bf16, chip),
                     _sds((2, 80, 4096), f32, chip),
                     _sds((2, 80, 4096), f32, chip),
                     _sds((80,), f32, chip)])
    def conv(x, w, b):
        y = causal_conv.conv_silu(x, w, b, interpret=False)
        return jnp.sum(y.astype(f32) ** 2)
    return (jax.grad(conv, argnums=(0, 1, 2)),
            [_sds((2, 4096, 5376), bf16, chip),
             _sds((4, 5376), bf16, chip), _sds((5376,), bf16, chip)])


@pytest.mark.parametrize("name", ["flash_attention", "flash_attention_bwd",
                                  "ssd_chunk", "ssd_chunk_bwd",
                                  "causal_conv_bwd"])
def test_kernel_compiles_to_tpu_custom_call(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_full_width_train_step_fits_one_chip(one_chip):
    """22 layers x batch 2 x 2048 tokens compiles (the TPU compiler refuses
    a program over the chip's HBM), and the repo's HLO reader prices the
    TPU program (loop trip counts, dots lowered to convolutions) at about
    fwd + bwd + recompute flops."""
    cfg = get_config("tinyllama-1.1b")
    trainer = Trainer(cfg, TrainerConfig())
    state = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                         jax.eval_shape(trainer.init_state))
    batch = {k: _sds((2, 2048), jnp.int32, one_chip)
             for k in ("tokens", "labels")}
    compiled = jax.jit(trainer.step_fn, donate_argnums=(0,)).lower(
        state, batch).compile()
    module = parse_hlo_module(compiled.as_text())
    assert len(extract_graph(module).lane_tasks(DEVICE_STREAM)) > 1000
    six_nt = 6 * count_params(cfg) * 2 * 2048
    assert six_nt <= aggregate_costs(module)["flops"] <= 2 * six_nt


def test_training_attention_on_four_chips_gathers_no_activation(topo,
                                                                monkeypatch):
    """On a (data=4, model=1) mesh, the kernel runs per shard inside
    ``shard_map``: its forward and backward compile to TPU kernels and no
    all-gather of q, k, v or their gradients appears."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh, rows = _four_chips(topo)
    bf16 = jnp.bfloat16
    q = _sds((8, 1024, 12, 64), bf16, rows)
    kv = _sds((8, 1024, 4, 64), bf16, rows)

    def loss(q, k, v):
        with jax.named_scope("attn"):
            o = attention.attend(q, k, v)
        return jnp.sum(o.astype(jnp.float32))
    with jax.set_mesh(mesh):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv).compile().as_text()
    assert _kernel_calls(text) == 2
    assert " all-gather" not in text and "all-gather-start" not in text


def test_chunked_ssd_on_four_chips_gathers_no_activation(topo, monkeypatch):
    """On a (data=4, model=1) mesh, ``ops.conv_silu`` and ``ops.ssd`` run
    their kernels per batch shard inside ``shard_map``: the SSD's three
    forward and three backward calls and the conv's two compile to TPU
    kernels and no all-gather of an operand or a gradient appears."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh, rows = _four_chips(topo)
    whole = NamedSharding(mesh, P())
    bf16, f32 = jnp.bfloat16, jnp.float32
    args = [_sds((8, 1024, 16 * 64), bf16, rows),
            _sds((8, 1024, 128), bf16, rows), _sds((8, 1024, 128), bf16, rows),
            _sds((8, 1024, 16), f32, rows), _sds((8, 1024, 16), f32, rows),
            _sds((16,), f32, whole), _sds((4, 16 * 64), bf16, whole),
            _sds((16 * 64,), bf16, whole)]

    def loss(x, b, c, dt, da, d, w, wb):
        with jax.named_scope("ssm"):
            x = ops.conv_silu(x, w, wb).reshape(8, 1024, 16, 64)
            state, y = ops.ssd(d, x, b, c, dt, da, 256)
        return jnp.sum(y.astype(f32) ** 2) + jnp.sum(state)
    with jax.set_mesh(mesh):
        text = jax.jit(jax.grad(loss, argnums=tuple(range(8)))).lower(
            *args).compile().as_text()
    assert _kernel_calls(text) == 8
    assert " all-gather" not in text and "all-gather-start" not in text


@pytest.mark.parametrize("chips", [1, 4])
def test_full_remat_runs_the_flash_forward_once(topo, one_chip, monkeypatch,
                                                chips):
    """At the ``internvl2-1b.train`` cell's widths (4096 positions, 14 q /
    2 kv heads of 64; batch 3 on one chip, one row a chip on four through
    ``shard_map``), the gradient of a ``"full"``-remat ``attend`` whose
    output the backward reads, as a layer's is, compiles to the forward and
    the backward kernel: the remat keeps o and lse.  A plain
    ``jax.checkpoint`` compiles the forward a second time."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if chips == 1:
        mesh, rows, B = None, one_chip, 3
    else:
        (mesh, rows), B = _four_chips(topo), 4
    bf16 = jnp.bfloat16
    q = _sds((B, 4096, 14, 64), bf16, rows)
    kv = _sds((B, 4096, 2, 64), bf16, rows)

    def calls(wrap):
        attend = wrap(lambda q, k, v: attention.attend(q, k, v))

        def loss(q, k, v):
            return jnp.sum(attend(q, k, v).astype(jnp.float32) ** 2)
        grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        with jax.set_mesh(mesh) if mesh else contextlib.nullcontext():
            return _kernel_calls(grad.lower(q, kv, kv).compile().as_text())
    assert calls(lambda fn: _remat_wrap(_FULL, fn)) == 2
    if chips == 1:
        assert calls(jax.checkpoint) == 3


def test_full_remat_keeps_nothing_of_the_ssd(one_chip, monkeypatch):
    """A ``"full"``-remat block of ``ops.conv_silu`` and ``ops.ssd`` names
    nothing for the remat to keep: its gradient compiles the same kernel
    calls as under a plain ``jax.checkpoint``, the remat's second run of
    the forward kernels included, which the block without remat has not."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bf16, f32 = jnp.bfloat16, jnp.float32
    args = [_sds((2, 1024, 16 * 64), bf16, one_chip),
            _sds((2, 1024, 128), bf16, one_chip),
            _sds((2, 1024, 128), bf16, one_chip),
            _sds((2, 1024, 16), f32, one_chip),
            _sds((2, 1024, 16), f32, one_chip), _sds((16,), f32, one_chip),
            _sds((4, 16 * 64), bf16, one_chip),
            _sds((16 * 64,), bf16, one_chip)]

    def block(x, b, c, dt, da, d, w, wb):
        x = ops.conv_silu(x, w, wb).reshape(2, 1024, 16, 64)
        return ops.ssd(d, x, b, c, dt, da, 256)

    def calls(wrap):
        def loss(*args):
            state, y = wrap(block)(*args)
            return jnp.sum(y.astype(f32) ** 2) + jnp.sum(state ** 2)
        return _kernel_calls(jax.jit(jax.grad(
            loss, argnums=tuple(range(8)))).lower(*args).compile().as_text())
    full = calls(functools.partial(_remat_wrap, _FULL))
    assert full == calls(jax.checkpoint) > calls(lambda fn: fn)

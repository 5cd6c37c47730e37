"""HLO parsing + cost aggregation against real compiled modules."""

import jax
import jax.numpy as jnp
import pytest

from repro.core import (parse_hlo_module, aggregate_costs, extract_graph,
                        CostModel, simulate, split_op_name)
from repro.core.hlo import _shape_bytes, _shape_elems


def test_shape_helpers():
    assert _shape_bytes("f32[4,8]{1,0}") == 128
    assert _shape_bytes("bf16[10]") == 20
    assert _shape_bytes("(f32[2], s8[3])") == 11
    assert _shape_elems("pred[2,2]") == 4


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_matmul_flops_counted():
    n = 64
    c = _compile(lambda a, b: a @ b,
                 jnp.ones((n, n), jnp.float32), jnp.ones((n, n), jnp.float32))
    m = parse_hlo_module(c.as_text())
    agg = aggregate_costs(m)
    assert agg["flops"] == pytest.approx(2 * n ** 3, rel=0.01)


def test_scan_trip_count_expansion():
    """XLA's cost_analysis visits while bodies once; ours multiplies by the
    known trip count — verify against the analytic total."""
    n, steps = 32, 10

    def f(x):
        def body(c, _):
            return c @ c * 1e-3, None
        y, _ = jax.lax.scan(body, x, None, length=steps)
        return y

    c = _compile(f, jnp.eye(n, dtype=jnp.float32))
    m = parse_hlo_module(c.as_text())
    agg = aggregate_costs(m)
    want = 2 * n ** 3 * steps
    assert agg["flops"] == pytest.approx(want, rel=0.2)
    xla = c.cost_analysis().get("flops", 0.0)
    assert xla < want * 0.5          # demonstrates the undercount we fix


def test_graph_extraction_and_simulation():
    def f(a, b):
        return jnp.tanh(a @ b).sum()

    c = _compile(f, jnp.ones((32, 32), jnp.float32),
                 jnp.ones((32, 32), jnp.float32))
    m = parse_hlo_module(c.as_text())
    g = extract_graph(m, CostModel())
    g.validate()
    r = simulate(g)
    assert r.makespan > 0
    assert any(t.flops > 0 for t in g.tasks())


def test_layer_mapping_from_named_scope():
    def f(x):
        with jax.named_scope("blk0"):
            with jax.named_scope("mlp"):
                x = x * 2.0
        return x

    c = _compile(f, jnp.ones((128, 128), jnp.float32))
    m = parse_hlo_module(c.as_text())
    g = extract_graph(m, CostModel())
    layers = {t.layer for t in g.tasks() if t.layer}
    assert any("blk0" in (l or "") for l in layers)


def test_split_op_name_phases():
    layer, phase = split_op_name("jit(f)/jvp(loss)/blk/mlp/dot_general")
    assert phase == "fwd"
    layer, phase = split_op_name(
        "jit(f)/transpose(jvp(loss))/blk/mlp/dot_general")
    assert phase == "bwd"


def test_collective_payload_parsing():
    # single-device psum still lowers to an all-reduce-free graph; craft text
    text = """
HloModule m, is_scheduled=true, num_partitions=4

ENTRY %main (p0: f32[128]) -> f32[128] {
  %p0 = f32[128]{0} parameter(0)
  ROOT %ar = f32[128]{0} all-reduce(%p0), replica_groups=[2,2]<=[4], to_apply=%add
}
"""
    m = parse_hlo_module(text)
    agg = aggregate_costs(m)
    assert agg["collective_bytes"] == pytest.approx(512)
    assert agg["bytes_all-reduce"] == pytest.approx(512)


# TPU HLO: layouts nest parentheses (``T(8,128)(2,1)``), while loops carry
# no ``known_trip_count`` and every dot is lowered to a convolution.
_TPU_LOOP = """
HloModule m, is_scheduled=true

%cond (c: (s32[], bf16[1,8,128], bf16[128,128,1])) -> pred[] {
  %c = (s32[]{:T(128)}, bf16[1,8,128]{2,1,0:T(8,128)(2,1)}, bf16[128,128,1]{1,0,2:T(8,128)(2,1)}) parameter(0)
  %n = s32[]{:T(128)} constant(22)
  %i = s32[]{:T(128)} get-tuple-element(%c), index=0
  ROOT %lt = pred[]{:T(512)} compare(%i, %n), direction=LT
}

%body (b: (s32[], bf16[1,8,128], bf16[128,128,1])) -> (s32[], bf16[1,8,128], bf16[128,128,1]) {
  %b = (s32[]{:T(128)}, bf16[1,8,128]{2,1,0:T(8,128)(2,1)}, bf16[128,128,1]{1,0,2:T(8,128)(2,1)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%b), index=0
  %x = bf16[1,8,128]{2,1,0:T(8,128)(2,1)} get-tuple-element(%b), index=1
  %w = bf16[128,128,1]{1,0,2:T(8,128)(2,1)} get-tuple-element(%b), index=2
  %y = bf16[1,8,128]{2,1,0:T(8,128)(2,1)S(1)} convolution(%x, %w), window={size=1}, dim_labels=0bf_oi0->0bf
  %one = s32[]{:T(128)} constant(1)
  %j = s32[]{:T(128)} add(%i, %one)
  ROOT %t = (s32[]{:T(128)}, bf16[1,8,128]{2,1,0:T(8,128)(2,1)}, bf16[128,128,1]{1,0,2:T(8,128)(2,1)}) tuple(%j, %y, %w)
}

ENTRY %main (x: bf16[1,8,128], w: bf16[128,128,1]) -> bf16[1,8,128] {
  %x = bf16[1,8,128]{2,1,0:T(8,128)(2,1)} parameter(0)
  %w = bf16[128,128,1]{1,0,2:T(8,128)(2,1)} parameter(1)
  %z = s32[]{:T(128)} constant(0)
  %t = (s32[]{:T(128)}, bf16[1,8,128]{2,1,0:T(8,128)(2,1)}, bf16[128,128,1]{1,0,2:T(8,128)(2,1)}) tuple(%z, %x, %w)
  %loop = (s32[]{:T(128)}, bf16[1,8,128]{2,1,0:T(8,128)(2,1)}, bf16[128,128,1]{1,0,2:T(8,128)(2,1)}) while(%t), condition=%cond, body=%body
  ROOT %out = bf16[1,8,128]{2,1,0:T(8,128)(2,1)} get-tuple-element(%loop), index=1
}
"""


def test_tpu_loop_trip_count_and_dot_convolution():
    m = parse_hlo_module(_TPU_LOOP)
    loop = m.entry_computation.by_name()["loop"]
    assert loop.opcode == "while" and m.trip_count(loop) == 22
    agg = aggregate_costs(m)
    # per trip: a (8 x 128) . (128 x 128) dot plus one scalar add
    assert agg["flops"] == pytest.approx(22 * (2 * 8 * 128 * 128 + 1))
    g = extract_graph(m, CostModel())
    convs = [t for t in g.tasks() if t.attrs.get("opcode") == "convolution"]
    assert len(convs) == 22


def test_tpu_batched_dot_as_dilated_convolution():
    """The attention einsum bqkgc,bckd->bqkgd (b=2, q=2048, k=4, g=8,
    c=1024, d=64) as the TPU lowers it: batch dims folded into dilated
    window dims, contraction over c alone."""
    text = """
HloModule m, is_scheduled=true

ENTRY %main (a: bf16[2,1024,4,64,1], b: bf16[2,2048,4,8,1024]) -> bf16[2,4,64,2048,8] {
  %a = bf16[2,1024,4,64,1]{1,3,4,2,0:T(8,128)(2,1)} parameter(0)
  %b = bf16[2,2048,4,8,1024]{1,4,3,2,0:T(8,128)(2,1)} parameter(1)
  ROOT %c = bf16[2,4,64,2048,8]{3,2,4,1,0:T(8,128)(2,1)} convolution(%a, %b), window={size=2x4x8 stride=1x3x1 pad=0_0x0_0x7_7 lhs_dilate=2x4x1 rhs_reversal=0x0x1}, dim_labels=0f1b2_0o12i->01bf2
}
"""
    agg = aggregate_costs(parse_hlo_module(text))
    assert agg["flops"] == pytest.approx(2 * 2 * 2048 * 4 * 8 * 1024 * 64)

"""Pipeline parallelism: SPMD GPipe correctness + Daydream schedule model."""

import os
import subprocess
import sys
import textwrap

import pytest

from repro.core import simulate
from repro.parallel import pipeline_graph, gpipe_bubble_fraction


class TestDaydreamModel:
    def test_balanced_gpipe_matches_closed_form(self):
        """Simulator vs the classic (M + S - 1) * t makespan."""
        for S, M, t in [(4, 8, 1.0), (2, 16, 0.5), (8, 8, 2.0)]:
            g = pipeline_graph([t] * S, M)
            r = simulate(g)
            assert r.makespan == pytest.approx((M + S - 1) * t)

    def test_bubble_fraction(self):
        g = pipeline_graph([1.0] * 4, 12)
        r = simulate(g)
        ideal = 12 * 1.0
        bubble = 1 - ideal / r.makespan
        assert bubble == pytest.approx(gpipe_bubble_fraction([1.0] * 4, 12))

    def test_unbalanced_stage_dominates(self):
        """A slow stage serializes the pipe: makespan ~ M * t_max."""
        g = pipeline_graph([1.0, 3.0, 1.0], 10)
        r = simulate(g)
        assert r.makespan >= 10 * 3.0
        assert r.makespan <= 10 * 3.0 + 2 * (1.0 + 3.0)

    def test_hop_time_adds_latency(self):
        base = simulate(pipeline_graph([1.0] * 3, 4)).makespan
        hop = simulate(pipeline_graph([1.0] * 3, 4, hop_time_s=0.5)).makespan
        assert hop > base


_SPMD_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp
    import numpy as np
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.parallel import gpipe_spmd

    S, M, mb, d = 4, 6, 2, 8
    mesh = jax.make_mesh((S,), ("stage",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    key = jax.random.PRNGKey(0)
    Ws = jax.random.normal(key, (S, d, d)) * 0.3          # one weight per stage
    x = jax.random.normal(jax.random.fold_in(key, 1), (M, mb, d))

    def stage_body(W, xm):                                 # W: (1, d, d) local
        return jnp.tanh(xm @ W[0])

    def spmd(W, xmb):
        return gpipe_spmd(partial(stage_body, W), xmb, n_microbatches=M)

    f = jax.shard_map(spmd, mesh=mesh,
                      in_specs=(P("stage", None, None), P(None, None, None)),
                      out_specs=P(None, None, None))
    got = jax.jit(f)(Ws, x)

    want = x
    for s in range(S):
        want = jnp.tanh(want @ Ws[s])
    err = float(jnp.max(jnp.abs(got - want)))
    assert err < 1e-5, err
    print("OK", err)
""")


def test_spmd_gpipe_matches_sequential():
    """4-stage GPipe over shard_map == sequential stage application."""
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    code = _SPMD_SNIPPET.format(src=src)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2500:]
    assert proc.stdout.strip().startswith("OK")

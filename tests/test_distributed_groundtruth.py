"""Paper §6.5 methodology with *measured* multi-device ground truth.

Marked ``@pytest.mark.slow``: each test spawns a fresh-XLA_FLAGS subprocess
that compiles multi-device programs (minutes on a cold cache).  The default
tier-1 run deselects them (``addopts = -m "not slow"`` in pyproject.toml);
``pytest -m slow`` still exercises them.

The paper's flagship claim: distributed training runtime predicted from a
single-worker profile.  This container has one physical CPU but XLA can host
N virtual devices; a subprocess (fresh XLA_FLAGS) measures a real 8-way
data-parallel step, and Daydream predicts it from the 1-device trace using
the calibrated local collective bandwidth — predict → implement → measure,
like the paper's Fig. 8.

Also: elastic re-shard ground truth — a checkpoint written under a (4,)
mesh restores bit-exactly onto a (2,) mesh.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

_DDP_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding

    from repro.core import trace_measured, whatif, measure_wallclock
    from repro.core.calibrate import measure_collective_bandwidth

    d, ff, layers = 256, 1024, 4
    per_dev_batch, sq = 4, 32
    W = {{f"l{{i}}": {{
        "w1": jax.random.normal(jax.random.PRNGKey(i), (d, ff)) * 0.05,
        "w2": jax.random.normal(jax.random.PRNGKey(100+i), (ff, d)) * 0.05,
    }} for i in range(layers)}}

    def loss(W, x):
        for i in range(layers):
            with jax.named_scope(f"l{{i}}"):
                x = x + jnp.tanh(x @ W[f"l{{i}}"]["w1"]) @ W[f"l{{i}}"]["w2"]
        return jnp.mean(x * x)

    def step(W, x):
        g = jax.grad(loss)(W, x)
        return jax.tree.map(lambda p, gg: p - 1e-3 * gg, W, g)

    x1 = jax.random.normal(jax.random.PRNGKey(7), (per_dev_batch, sq, d))

    # --- single-device profile -> Daydream prediction for 8 workers
    bundle = trace_measured(step, W, x1, iters=20)
    base = bundle.simulate().makespan
    grad_bytes = {{f"l{{i}}": 2 * d * ff * 4.0 for i in range(layers)}}
    bw = measure_collective_bandwidth(8)
    pred = whatif.what_if_distributed(
        bundle.graph, grad_bytes, num_workers=8, bandwidth=bw,
        cost=bundle.cost).simulate().makespan
    pred_slowdown = pred / base

    # --- ground truth: real 8-way DP on host devices
    mesh = jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    xg = jnp.concatenate([x1] * 8, axis=0)
    xg = jax.device_put(xg, NamedSharding(mesh, P("data", None, None)))
    Wr = jax.device_put(W, NamedSharding(mesh, P()))
    t1 = measure_wallclock(step, W, x1, iters=20)
    with jax.set_mesh(mesh):
        t8 = measure_wallclock(step, Wr, xg, iters=20)
    true_slowdown = t8 / t1

    print(json.dumps({{"pred": pred_slowdown, "true": true_slowdown,
                       "base_ms": base * 1e3, "t1_ms": t1 * 1e3,
                       "t8_ms": t8 * 1e3}}))
""")


@pytest.mark.slow
def test_ddp_prediction_vs_measured_8way():
    code = _DDP_SNIPPET.format(src=_SRC)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    # Both should see a slowdown >= ~1 (comm added); agreement within a wide
    # band (virtual devices share one core: compute scales 8x worse than a
    # real fleet, so we compare the comm-overhead *direction and order*).
    assert r["pred"] >= 1.0
    assert r["true"] >= 0.9
    assert r["pred"] < 30 and r["true"] < 30, r


_ELASTIC_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.ckpt import save_checkpoint, restore_checkpoint

    tmp = {tmp!r}
    tree = {{"w": jnp.arange(64.0).reshape(8, 8),
             "b": jnp.ones((16,), jnp.bfloat16)}}

    auto = (jax.sharding.AxisType.Auto,)
    mesh4 = jax.make_mesh((4,), ("data",), auto, devices=jax.devices()[:4])
    sharded = jax.device_put(tree, NamedSharding(mesh4, P("data")))
    save_checkpoint(tmp, 11, sharded)

    mesh2 = jax.make_mesh((2,), ("data",), auto, devices=jax.devices()[:2])
    sh2 = {{"w": NamedSharding(mesh2, P("data", None)),
            "b": NamedSharding(mesh2, P("data"))}}
    out, step = restore_checkpoint(tmp, tree, shardings=sh2)
    assert step == 11
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
    assert out["w"].sharding.num_devices == 2
    print("ELASTIC_OK")
""")


@pytest.mark.slow
def test_elastic_reshard_across_mesh_sizes(tmp_path):
    code = _ELASTIC_SNIPPET.format(src=_SRC, tmp=str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "ELASTIC_OK" in proc.stdout

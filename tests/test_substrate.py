"""Substrate layers: data, optimizer, checkpoint, fault tolerance, sharding."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import SyntheticLM, host_shard, Prefetcher, make_batch
from repro.optim import AdamW, warmup_cosine, dgc_init, dgc_step, global_norm
from repro.ckpt import save_checkpoint, restore_checkpoint, latest_step, \
    CheckpointManager
from repro.runtime import FaultTolerantRunner, StragglerMonitor, RetryPolicy
from repro.sharding import ShardingRules, logical_spec


# ------------------------------------------------------------------- data
class TestData:
    def test_deterministic(self):
        a = SyntheticLM(100, 16, 4).batch_at(3)
        b = SyntheticLM(100, 16, 4).batch_at(3)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])

    def test_labels_are_next_tokens(self):
        b = SyntheticLM(100, 16, 4).batch_at(0)
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])

    def test_host_shard_partitions(self):
        slices = [host_shard(10, i, 3) for i in range(3)]
        idx = []
        for s in slices:
            idx.extend(range(s.start, s.stop))
        assert sorted(idx) == list(range(10))

    def test_prefetcher_order_and_error(self):
        it = Prefetcher(iter([1, 2, 3]))
        assert list(it) == [1, 2, 3]

        def boom():
            yield 1
            raise ValueError("x")
        it = Prefetcher(boom())
        assert next(it) == 1
        with pytest.raises(ValueError):
            next(it)

    def test_structured_stream_learnable(self):
        b = SyntheticLM(97, 64, 8, noise=0.0).batch_at(0)
        # exact affine map when noise=0
        want = (5 * b["tokens"] + 131) % 97
        np.testing.assert_array_equal(want, b["labels"])


# ------------------------------------------------------------------ optim
def _adamw_ref(opt, p, g, m, v, *, count):
    """One AdamW step in float64 from a state at ``count``: (p, m, v, the
    gradient's norm before clipping)."""
    norm = np.sqrt(np.sum(g * g))
    if opt.grad_clip and norm > opt.grad_clip:
        g = g * (opt.grad_clip / norm)
    t = count + 1
    m = opt.b1 * m + (1 - opt.b1) * g
    v = opt.b2 * v + (1 - opt.b2) * g * g
    step = (m / (1 - opt.b1 ** t)) / (np.sqrt(v / (1 - opt.b2 ** t))
                                      + opt.eps) + opt.weight_decay * p
    return p - opt.lr * step, m, v, norm


class TestOptim:
    def test_adamw_decreases_quadratic(self):
        opt = AdamW(lr=0.1, weight_decay=0.0)
        p = {"x": jnp.asarray([5.0, -3.0])}
        s = opt.init(p)
        for _ in range(50):
            g = {"x": 2 * p["x"]}
            p, s = opt.apply(g, s, p)
        assert float(jnp.abs(p["x"]).max()) < 1.0

    def test_grad_clip_records_norm(self):
        opt = AdamW(lr=0.1, grad_clip=1.0)
        p = {"x": jnp.ones(4)}
        s = opt.init(p)
        g = {"x": jnp.full((4,), 100.0)}
        p, s = opt.apply(g, s, p)
        assert float(opt.last_grad_norm(s)) == pytest.approx(200.0)

    def test_warmup_cosine_shape(self):
        f = warmup_cosine(1.0, 10, 100)
        assert float(f(jnp.asarray(0))) == pytest.approx(0.0)
        assert float(f(jnp.asarray(10))) == pytest.approx(1.0, rel=0.2)
        assert float(f(jnp.asarray(100))) < 0.01

    @pytest.mark.parametrize("n", [100, 1024, 5000, 1 << 14])
    def test_adamw_sweep(self, n):
        """One ``apply`` from a state at count 4 (bias correction at step
        5), the gradient clipped to norm 1 and decoupled weight decay,
        against the float64 oracle."""
        rng = np.random.default_rng(n)
        p, g = rng.standard_normal(n), rng.standard_normal(n)
        m = rng.standard_normal(n) * 0.1
        v = np.abs(rng.standard_normal(n)) * 0.01
        opt = AdamW(lr=3e-4, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1,
                    grad_clip=1.0)
        f32 = lambda a: {"w": jnp.asarray(a, jnp.float32)}   # noqa: E731
        state = {"m": f32(m), "v": f32(v), "count": jnp.asarray(4, jnp.int32),
                 "gnorm": jnp.zeros((), jnp.float32)}
        newp, state = opt.apply(f32(g), state, f32(p))
        want = _adamw_ref(opt, *(np.asarray(f32(a)["w"], np.float64)
                                 for a in (p, g, m, v)), count=4)
        assert int(state["count"]) == 5
        np.testing.assert_allclose(state["gnorm"], want[3], rtol=1e-6)
        for got, w in zip((newp["w"], state["m"]["w"], state["v"]["w"]),
                          want[:3]):
            np.testing.assert_allclose(np.asarray(got, np.float64), w,
                                       rtol=1e-5, atol=1e-7)

    def test_adamw_multi_step_matches_oracle(self):
        """Three ``apply`` steps over a two-leaf tree, from a fresh state,
        against the float64 oracle run as many times."""
        params = {"a": jnp.ones((130,)) * 0.3,
                  "b": {"w": jnp.linspace(-1, 1, 77)}}
        opt = AdamW(lr=1e-2)
        state = opt.init(params)
        flat = lambda t: np.concatenate(   # noqa: E731
            [np.asarray(l, np.float64) for l in jax.tree.leaves(t)])
        p_ref = flat(params)
        m_ref = v_ref = np.zeros_like(p_ref)
        p = params
        for count in range(3):
            grads = jax.tree.map(lambda a: a * 0.1 + 0.01, p)
            g_ref = flat(grads)
            p, state = opt.apply(grads, state, p)
            p_ref, m_ref, v_ref, _ = _adamw_ref(opt, p_ref, g_ref, m_ref,
                                                v_ref, count=count)
            np.testing.assert_allclose(flat(p), p_ref, rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(flat(state["m"]), m_ref, rtol=1e-5,
                                   atol=1e-7)
        np.testing.assert_allclose(flat(state["v"]), v_ref, rtol=1e-5,
                                   atol=1e-9)

    @pytest.mark.parametrize("shape,ratio", [((100,), 0.1),
                                             ((123, 45), 0.01),
                                             ((4096,), 0.001)])
    def test_dgc_keeps_exact_top_k(self, shape, ratio):
        """``dgc_compress`` then ``dgc_decompress`` give the gradient with
        all but its k = round(ratio · size) largest magnitudes zeroed."""
        from repro.optim.dgc import dgc_compress, dgc_decompress
        g = np.random.default_rng(3).standard_normal(shape).astype(np.float32)
        vals, idx = dgc_compress(jnp.asarray(g), ratio)
        got = dgc_decompress(vals, idx, shape)
        flat = g.reshape(-1).astype(np.float64)
        k = max(1, int(round(ratio * flat.size)))
        keep = np.argsort(-np.abs(flat), kind="stable")[:k]
        want = np.zeros_like(flat)
        want[keep] = flat[keep]
        assert vals.shape == (k,)
        np.testing.assert_array_equal(np.asarray(got, np.float64),
                                      want.reshape(shape))

    def test_dgc_error_feedback_conserves(self):
        g = {"w": jax.random.normal(jax.random.PRNGKey(0), (1000,))}
        st = dgc_init(g)
        sent, st = dgc_step(g, st, ratio=0.05)
        # sent + residual == original gradient (error feedback identity)
        total = sent["w"].astype(jnp.float32) + st.residual["w"]
        np.testing.assert_allclose(total, g["w"], atol=1e-6)
        nz = int(jnp.sum(sent["w"] != 0))
        assert 40 <= nz <= 80


# ------------------------------------------------------------------- ckpt
class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.bfloat16),
                "b": {"c": jnp.ones((4,), jnp.float32)}}
        save_checkpoint(str(tmp_path), 7, tree)
        out, step = restore_checkpoint(str(tmp_path), tree)
        assert step == 7
        np.testing.assert_array_equal(np.asarray(out["a"], np.float32),
                                      np.asarray(tree["a"], np.float32))

    def test_uncommitted_ignored(self, tmp_path):
        tree = {"a": jnp.ones(3)}
        p = save_checkpoint(str(tmp_path), 1, tree)
        os.remove(os.path.join(p, "COMMIT"))
        assert latest_step(str(tmp_path)) is None

    def test_keep_last_k(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        for s in range(5):
            mgr.save(s, {"a": jnp.full((2,), s)})
        assert mgr.latest_step() == 4
        out, _ = mgr.restore_latest({"a": jnp.zeros(2)})
        np.testing.assert_array_equal(out["a"], [4, 4])
        steps = sorted(os.listdir(tmp_path))
        assert len([s for s in steps if s.startswith("step_")]) == 2

    def test_async_save(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save_async(3, {"a": jnp.ones(4)})
        mgr.wait()
        assert mgr.latest_step() == 3

    def test_elastic_reshard(self, tmp_path):
        """Checkpoint restores onto a different mesh via NamedSharding."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        n = len(jax.devices())
        tree = {"w": jnp.arange(16.0).reshape(4, 4)}
        save_checkpoint(str(tmp_path), 0, tree)
        mesh = jax.make_mesh((1,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        sh = {"w": NamedSharding(mesh, P("data", None))}
        out, _ = restore_checkpoint(str(tmp_path), tree, shardings=sh)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(tree["w"]))
        assert out["w"].sharding == sh["w"]


# ---------------------------------------------------------------- runtime
class TestRuntime:
    def test_restart_from_checkpoint(self, tmp_path):
        saves = {}

        def make_state():
            return 0

        def step(s, i):
            return s + 1

        def save(s, i):
            saves["latest"] = (s, i)

        def restore():
            return saves.get("latest")

        crash_at = {5}

        def inject(i):
            if i in crash_at:
                crash_at.discard(i)
                raise RuntimeError("node failure")

        r = FaultTolerantRunner(make_state, step, save, restore,
                                save_every=2,
                                policy=RetryPolicy(max_failures=2,
                                                   backoff_s=0.0))
        final = r.run(10, inject_failure=inject)
        assert final == 10
        assert r.restarts == 1

    def test_failure_budget_exceeded(self):
        def step(s, i):
            raise RuntimeError("always")
        r = FaultTolerantRunner(lambda: 0, step, lambda s, i: None,
                                lambda: None,
                                policy=RetryPolicy(max_failures=2,
                                                   backoff_s=0.0))
        with pytest.raises(RuntimeError):
            r.run(3)

    def test_straggler_detection(self):
        mon = StragglerMonitor(threshold=2.0)
        for i in range(8):
            mon.record(i, 1.0)
        assert mon.record(8, 5.0) is True
        assert mon.flagged == [8]

    @pytest.mark.parametrize("env", ["/elsewhere/cache", None])
    def test_compile_cache_dir(self, monkeypatch, env):
        from repro.runtime.compile_cache import CHECKOUT_CACHE, \
            use_compile_cache
        if env is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        before = jax.config.jax_compilation_cache_dir
        try:
            got = use_compile_cache()
            after = jax.config.jax_compilation_cache_dir
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        if env is None:
            assert got == after == str(CHECKOUT_CACHE)
            assert CHECKOUT_CACHE.parent.joinpath("pyproject.toml").exists()
        else:
            assert got == env and after == before   # left to jax


# --------------------------------------------------------------- sharding
class TestSharding:
    def test_no_mesh_resolves_replicated(self):
        spec = logical_spec("batch", None, "heads")
        assert all(s is None for s in spec)

    def test_rules_under_mesh(self):
        mesh = jax.make_mesh((1,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        with jax.set_mesh(mesh):
            rules = ShardingRules()
            spec = rules.spec("batch", "heads", dim_sizes=[4, 4])
            # model axis size 1 -> nothing shardable but no error
            assert len(spec) == 2

    def test_fsdp_toggle(self):
        r_on = ShardingRules(fsdp=True)
        r_off = ShardingRules(fsdp=False)
        assert r_off.physical("fsdp", dim_size=64) is None
        # without a mesh both degrade to None
        assert r_on.physical("fsdp", dim_size=64) is None

"""CPU checks of the benchmark's own yardstick, run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/chip/test_bench.py -q

- the trace reducer (busy as a union of intervals, the window from the
  ``Steps`` line, scope attribution from ``tf_op``, leaf ops inside a
  ``while``) on a hand-made trace and on a recorded v5e capture;
- the weights' init rules: each kind's draws in their bounds, and the
  same parameters from one seed as before the ``uniform`` and
  ``log_uniform`` kinds came;
- the work functions against hand arithmetic at one small shape of each
  family counted, and the parameter count against the program's own
  parameter tree;
- for every configuration whose file has a ``small`` block, at that size:
  the control (the reference in fp8) reads far above a sound run, and a
  run whose timed step is broken underneath (its state returned
  unchanged; half of the batch left out) comes out not correct.
"""

from __future__ import annotations

import glob
import gzip
import hashlib
import json
import math
import os
import sys
import time
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

from lib import bench, devtrace, work  # noqa: E402

RECORDED = os.path.join(HERE, "testdata", "tpu_v5e_step.trace.json.gz")
# configurations whose file has a CPU-sized ``small`` block; each enters the
# small checks below as its cell ``<config>.train``
SMALL_CONFIGS = sorted(
    os.path.basename(p)[:-len(".json")]
    for p in glob.glob(os.path.join(HERE, "configs", "*.json"))
    if "small" in bench.load_json(p))


# ------------------------------------------------------------ trace reducer
def _trace(tmp_path, ops, steps, host=()):
    """A capture file with one device (``XLA Ops`` and ``Steps`` lines) and
    a host ``python`` line; times in microseconds."""
    ev = [{"ph": "M", "pid": 1, "name": "process_name",
           "args": {"name": "/device:TPU:0"}},
          {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
           "args": {"name": "Steps"}},
          {"ph": "M", "pid": 1, "tid": 3, "name": "thread_name",
           "args": {"name": "XLA Ops"}},
          {"ph": "M", "pid": 9, "name": "process_name",
           "args": {"name": "/host:CPU"}},
          {"ph": "M", "pid": 9, "tid": 5, "name": "thread_name",
           "args": {"name": "python3"}}]
    ev += [{"ph": "X", "pid": 1, "tid": 1, "ts": a, "dur": b - a,
            "name": "train"} for a, b in steps]
    ev += [{"ph": "X", "pid": 1, "tid": 3, "ts": a, "dur": b - a, "name": n,
            "args": {"tf_op": tf, "hlo_category": "convolution fusion"}}
           for n, a, b, tf in ops]
    ev += [{"ph": "X", "pid": 9, "tid": 5, "ts": a, "dur": b - a, "name": n}
           for n, a, b in host]
    path = os.path.join(tmp_path, "t.trace.json.gz")
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": ev}, f)
    return path


def test_reducer_by_hand(tmp_path):
    ops = [
        # a while (0..600) whose body ops are the leaves
        ("while.1", 0, 600, "jit(train_step)/while"),
        ("fusion.1", 0, 200, "jit(train_step)/while/body/attn/dot_general"),
        ("fusion.2", 250, 600,
         "jit(train_step)/transpose(jvp(attn))/dot_general"),
        ("fusion.3", 650, 700, "jit(train_step)/update/mul"),
        # 'attention' is not the scope 'attn'
        ("fusion.5", 720, 760, "jit(train_step)/attention_mask/select"),
        # partly outside the window (900): clipped
        ("fusion.4", 800, 1000, "jit(train_step)/mlp/dot_general"),
    ]
    path = _trace(tmp_path, ops, steps=[(0, 450), (450, 900)],
                  host=[("fit", 0, 900), ("device_get", 760, 800)])
    r = devtrace.reduce_trace(path)
    assert r["devices"] == 1 and r["steps"] == 2
    assert r["window_s"] == pytest.approx(900e-6)
    # union: the while once with its body, then 50 + 40 + 100 clipped
    assert r["busy_s"] == pytest.approx((600 + 50 + 40 + 100) * 1e-6)
    assert r["scope_s"]["attn"] == pytest.approx((200 + 350) * 1e-6)
    assert r["scope_s"]["update"] == pytest.approx(50e-6)
    assert r["scope_s"]["mlp"] == pytest.approx(100e-6)
    assert "attention_mask" not in r["scope_s"]
    labels = dict(r["device_ops"])
    assert labels["convolution fusion in attn bwd"] == pytest.approx(350e-6)
    assert labels["convolution fusion in attn"] == pytest.approx(200e-6)
    assert "convolution fusion in other" in labels     # the mask op
    # idle: [600, 650] and [700, 720] under fit, [760, 800] under device_get
    assert r["idle_gaps"] == [["fit", pytest.approx(50e-6)],
                              ["device_get", pytest.approx(40e-6)],
                              ["fit", pytest.approx(20e-6)]]


def test_reducer_ssm_and_moe_scopes_by_hand(tmp_path):
    ops = [
        ("fusion.1", 0, 100, "jit(train_step)/while/body/ssm/dot_general"),
        ("fusion.2", 100, 300,
         "jit(train_step)/while/body/transpose(jvp(ssm))/dot_general"),
        ("fusion.3", 300, 340, "jit(train_step)/while/body/moe/dot_general"),
        ("fusion.4", 340, 400,
         "jit(train_step)/transpose(jvp(moe))/mlp/dot_general"),
        # 'ssm_conv' and 'moe_router' are not the scopes 'ssm' and 'moe'
        ("fusion.5", 400, 410, "jit(train_step)/ssm_conv/mul"),
        ("fusion.6", 410, 420, "jit(train_step)/moe_router/mul"),
    ]
    r = devtrace.reduce_trace(_trace(tmp_path, ops, steps=[(0, 420)]))
    assert r["scope_s"]["ssm"] == pytest.approx(300e-6)
    assert r["scope_s"]["moe"] == pytest.approx(100e-6)
    assert r["scope_s"]["mlp"] == pytest.approx(60e-6)
    labels = dict(r["device_ops"])
    assert labels["convolution fusion in ssm"] == pytest.approx(100e-6)
    assert labels["convolution fusion in ssm bwd"] == pytest.approx(200e-6)
    assert labels["convolution fusion in moe"] == pytest.approx(40e-6)
    # the innermost scope names the op: an expert's mlp under moe
    assert labels["convolution fusion in mlp bwd"] == pytest.approx(60e-6)
    assert labels["convolution fusion in other"] == pytest.approx(20e-6)


def test_union_length():
    assert devtrace.union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4
    assert devtrace.union_length([]) == 0


def test_reducer_on_recorded_capture():
    r = devtrace.reduce_trace(RECORDED)
    with gzip.open(RECORDED) as f:
        ev = json.load(f)["traceEvents"]
    steps = [e for e in ev if e.get("ph") == "X" and e["pid"] == 3
             and e["tid"] == 1]
    w0 = min(e["ts"] for e in steps)
    w1 = max(e["ts"] + e["dur"] for e in steps)
    assert r["devices"] == 1 and r["steps"] == len(steps) == 2
    assert r["window_s"] == pytest.approx((w1 - w0) * 1e-6)
    assert 0 < r["busy_s"] <= r["window_s"]
    # each leaf op lands in at most one known scope on this capture
    assert sum(r["scope_s"].values()) <= r["busy_s"] * (1 + 1e-9)
    assert set(r["scope_s"]) <= set(devtrace.SCOPES)


# the recorded capture as the reducer read it before ``ssm`` and ``moe``
# joined the scopes
RECORDED_SCOPE_S = {"embed": 0.00029044625000015364,
                    "attn": 0.007679911013999961,
                    "norm": 6.529976600005466e-05,
                    "mlp": 0.0031932639060000146}
RECORDED_OPS = [
    ["convolution fusion in attn", 0.005309408672000092],
    ["convolution fusion in mlp", 0.0031932639060000146],
    ["loop fusion in attn", 0.002257380624000063],
    ["custom fusion in embed", 0.00023753375000004599],
    ["data formatting in attn", 0.00011026523399998404],
    ["loop fusion in norm", 6.529976600005466e-05],
    ["loop fusion in other", 5.327226599999267e-05],
    ["data formatting in embed", 3.830750000005355e-05],
    ["loop fusion in embed", 1.4605000000054132e-05],
    ["non-fusion elementwise in attn", 1.5789839999342802e-06]]


def test_recorded_capture_reads_as_before():
    r = devtrace.reduce_trace(RECORDED)
    assert r["scope_s"] == pytest.approx(RECORDED_SCOPE_S, rel=1e-12)
    assert [k for k, _ in r["device_ops"]] == [k for k, _ in RECORDED_OPS]
    assert [v for _, v in r["device_ops"]] == pytest.approx(
        [v for _, v in RECORDED_OPS], rel=1e-12)


# ------------------------------------------------------------- init rules
def _drawn(rule, n=4096, seed=11):
    import jax
    from lib import weights
    return weights._draw(jax.random.key(seed), (n,), rule)


@pytest.mark.parametrize("rule,lo,hi", [
    (["uniform", -0.5, 0.5], -0.5, 0.5),
    (["uniform", 1, 16], 1.0, 16.0),
    (["log_uniform", 0.001, 0.1], 0.001, 0.1),
    (["uniform", 1, 16, "log"], math.log(1), math.log(16)),
], ids=["uniform_conv", "uniform_A", "log_uniform_dt", "uniform_log_A_log"])
def test_init_rule_draws_in_bounds(rule, lo, hi):
    import numpy as np
    x = np.asarray(_drawn(rule), np.float64)
    assert np.all(np.isfinite(x)) and x.min() >= lo and x.max() <= hi
    # spread over the range, not stuck at a value
    assert x.max() - x.min() > 0.9 * (hi - lo)


def test_log_uniform_spreads_in_log_space():
    import numpy as np
    x = np.log10(np.asarray(_drawn(["log_uniform", 0.001, 0.1])))
    # each decade of [0.001, 0.1] holds about half the draws
    assert 0.45 < np.mean(x < -2) < 0.55


def test_softplus_inverse_recovers_dt():
    import jax
    import numpy as np
    bias = _drawn(["log_uniform", 0.001, 0.1, "softplus_inverse"])
    dt = np.asarray(jax.nn.softplus(bias), np.float64)
    assert np.all(bias < 0)            # softplus⁻¹ of dt < ln 2 is negative
    assert dt.min() >= 0.001 * (1 - 1e-5) and dt.max() <= 0.1 * (1 + 1e-5)
    plain_dt = np.asarray(_drawn(["log_uniform", 0.001, 0.1]), np.float64)
    np.testing.assert_allclose(dt, plain_dt, rtol=1e-4)


@pytest.mark.parametrize("rule", [
    ["laplace", 1.0], ["uniform", 0, 1, "exp"], ["log_uniform", 0.1],
    ["uniform", 0, 1, "log", "log"]],
    ids=["unknown_kind", "unknown_transform", "too_few", "too_many"])
def test_unknown_init_rule_raises(rule):
    with pytest.raises(ValueError, match="unknown init rule"):
        _drawn(rule)


# the sha256 of every leaf's name, dtype and bytes of ``internvl2-1b`` at its
# small size from seed 4000000017, taken before the ``uniform`` and
# ``log_uniform`` kinds came
INTERNVL2_SMALL_SHA = ("c8a8c0fc5e0fe85ec2a08a9031cb63da"
                       "43a3b6b8be4e45dd2baf51919eb3eebb")


def test_internvl2_weights_unchanged():
    import functools
    import jax
    import numpy as np
    import readings
    from lib import weights
    from repro.models.model import ModelConfig, init_params
    from repro.models.paramdecl import SpecLeaf
    cfg = readings.shrink(bench.config_files("internvl2-1b")[0])
    spec = init_params(ModelConfig(**cfg["model"]), None)
    like = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                        spec, is_leaf=lambda x: isinstance(x, SpecLeaf))
    params = jax.jit(functools.partial(weights.make_params, like,
                                       rules=cfg["init"]))(
        weights.seed_words(4_000_000_017))
    h = hashlib.sha256()
    for name, leaf in zip(weights.leaf_names(params),
                          jax.tree.leaves(params)):
        a = np.asarray(leaf)
        h.update(name.encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == INTERNVL2_SMALL_SHA


# ------------------------------------------------------------ work counts
SMALL_ATTN = {"model": {"family": "vlm", "n_layers": 2, "d_model": 8,
                        "n_heads": 2, "n_kv_heads": 1, "d_ff": 16,
                        "vocab": 32, "n_patches": 2},
              "train": {"batch": 1, "seq_len": 4}}


def test_attn_work_by_hand():
    # head dim 4; q,k,v,o weights 8*(2+2)*4 + 2*4*8 = 192
    # forward: 2*4*192 = 1536 for projections; scores and values over the
    # 10 causal pairs of 4 positions, 2 heads, head dim 4: 2*2*4*2*10 = 320
    ops, nbytes = work.attn(SMALL_ATTN)
    assert ops == 3 * (1536 + 320) * 2
    acts = 4 * (8 + 2 * 4 * 4 + 2 * 2 * 4 + 8) * 2
    assert nbytes == 2 * (192 * 2 * 3 + 3 * acts)


SMALL_SSM = {"model": {"family": "ssm", "n_layers": 2, "d_model": 64,
                       "ssm_state": 16, "ssm_expand": 2, "vocab": 32,
                       "tie_embeddings": True},
             "train": {"batch": 1, "seq_len": 4}}


def test_ssm_work_by_hand():
    # E = 128, N = 16, H = 128 / 64 = 2, K = 4, T = 4 positions
    # in-projection 64 * (2*128 + 2*16 + 2) = 64 * 290 = 18560
    # conv weight 4 * (128 + 32) = 640, bias 160; dt_bias, A_log, D 3 * 2;
    # gated norm 128; out-projection 128 * 64 = 8192: 27686 under ssm
    # forward: 2*4*18560 = 148480; conv 2*4*4*160 = 5120; recurrence
    # 2*4*128*16 twice = 32768; out-projection 2*4*8192 = 65536: 251904
    ops, nbytes = work.ssm(SMALL_SSM)
    assert ops == 3 * 251904 * 2
    # x 64, z 128, xBC 160, dt 2, y 128, each written and read back
    acts = 4 * 2 * (64 + 128 + 160 + 2 + 128) * 2
    assert nbytes == 2 * (27686 * 2 * 3 + 3 * acts)
    # a layer adds its pre-norm (64); one tied 32 x 64 table; final norm
    assert work.n_params(SMALL_SSM) == 2 * (27686 + 64) + 32 * 64 + 64
    # the unembedding multiplies every position, tied or not
    assert work.step_ops(SMALL_SSM) == 3 * 251904 * 2 + 6 * 32 * 64 * 4
    untied = {**SMALL_SSM, "model": {**SMALL_SSM["model"],
                                     "tie_embeddings": False}}
    assert work.n_params(untied) == work.n_params(SMALL_SSM) + 32 * 64
    assert work.step_ops(untied) == work.step_ops(SMALL_SSM)


def test_internvl2_counts_unchanged():
    # the integers before the ``ssm`` family was counted
    cfg, _ = bench.config_files("internvl2-1b")
    assert work.n_params(cfg) == 630439040
    assert work.step_ops(cfg) == 42273336655872
    assert work.update_bytes(cfg) == 15130536960
    assert work.attn(cfg) == (9742571274240, 10682892288)


def test_update_bytes_by_hand():
    assert work.update_bytes(SMALL_ATTN) == work.n_params(SMALL_ATTN) * 24


@pytest.mark.parametrize("config", SMALL_CONFIGS)
def test_param_count_matches_program(config):
    import jax
    from repro.models.model import ModelConfig, init_params
    from repro.models.paramdecl import SpecLeaf
    cfg, _ = bench.config_files(config)
    spec = init_params(ModelConfig(**cfg["model"]), None)
    leaves = jax.tree.leaves(spec, is_leaf=lambda x: isinstance(x, SpecLeaf))
    n = sum(math.prod(s.shape) for s in leaves)
    assert work.n_params(cfg) == n


def test_step_ops_bounds_attention():
    cfg, _ = bench.config_files("internvl2-1b")
    assert work.attn(cfg)[0] < work.step_ops(cfg)


# ------------------------------------------------- control and faults, small
def _small(config):
    import readings
    cfg, ref = bench.config_files(config)
    return readings.shrink(cfg), ref, bench.mix_file("train")


def _run(config, seed, fault=None):
    from lib import train
    args = types.SimpleNamespace(seed=seed, seconds=0.0, trace=0,
                                 spec={"per_layer": []}, peaks={})
    return train.run({"name": config + ".train"}, *_small(config), args,
                     time.perf_counter(), fault=fault)


def _unchanged(fn):
    import jax
    import jax.numpy as jnp

    def step(state, batch):
        _, metrics = fn(jax.tree.map(jnp.copy, state), batch)
        return state, metrics
    return step


def _half_batch(fn):
    def step(state, batch):
        keep = next(iter(batch.values())).shape[0] // 2
        return fn(state, {k: v[:keep] for k, v in batch.items()})
    return step


SEED = 4_000_000_017


@pytest.mark.parametrize("config", SMALL_CONFIGS)
def test_sound_run_is_correct_small(config):
    res = _run(config, SEED)
    assert res["correct"], res["check"]


@pytest.mark.parametrize("config", SMALL_CONFIGS)
@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(fault, config):
    res = _run(config, SEED, fault=fault)
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("config", SMALL_CONFIGS)
def test_control_reads_above_the_program_small(config):
    import readings
    from lib import weights
    cfg, ref, mix = _small(config)
    cfg = dict(cfg, _check_steps=mix["check_steps"])
    prog = _run(config, SEED)["check"]
    ctrl, _ = readings.placed(cfg, ref, mix, SEED,
                              weights.seed_words(SEED), "control")
    limits = bench.limits_of(config + ".train")
    assert any(ctrl[k] > limits[k] for k in limits), ctrl
    assert ctrl["grad_gap"] > 3 * prog["grad_gap"]["value"]

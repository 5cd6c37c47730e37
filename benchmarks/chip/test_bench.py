"""CPU checks of the benchmark's own yardstick, run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/chip/test_bench.py -q

- the trace reducer (busy as a union of intervals, the window from the
  ``Steps`` line, scope attribution from ``tf_op``, leaf ops inside a
  ``while``) on a hand-made trace and on a recorded v5e capture;
- the work functions against hand arithmetic at one small shape, and the
  parameter count against the program's own parameter tree;
- at a small size: the control (the reference in fp8) reads far above a
  sound run, and a run whose timed step is broken underneath (its state
  returned unchanged; half of the batch left out) comes out not correct.
"""

from __future__ import annotations

import copy
import gzip
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


def test_update_bytes_by_hand():
    assert work.update_bytes(SMALL_ATTN) == work.n_params(SMALL_ATTN) * 24


def test_param_count_matches_program():
    import jax
    from repro.models.model import ModelConfig, init_params
    from repro.models.paramdecl import SpecLeaf
    cfg, _ = bench.config_files("internvl2-1b")
    spec = init_params(ModelConfig(**cfg["model"]), None)
    leaves = jax.tree.leaves(spec, is_leaf=lambda x: isinstance(x, SpecLeaf))
    n = sum(math.prod(s.shape) for s in leaves)
    assert work.n_params(cfg) == n


def test_step_ops_bounds_attention():
    cfg, _ = bench.config_files("internvl2-1b")
    assert work.attn(cfg)[0] < work.step_ops(cfg)


# ------------------------------------------------- control and faults, small
def _small(config="internvl2-1b"):
    import readings
    cfg, ref = bench.config_files(config)
    cfg = copy.deepcopy(cfg)
    cfg["model"].update(readings.SMALL[config][0])
    cfg["train"] = readings.SMALL[config][1]
    return cfg, ref, bench.mix_file("train")


def _run(cfg, ref, mix, seed, fault=None):
    from lib import train
    args = types.SimpleNamespace(seed=seed, seconds=0.0, trace=0,
                                 spec={"per_layer": []}, peaks={})
    return train.run({"name": "internvl2-1b.train"}, cfg, ref, mix, args,
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


def test_sound_run_is_correct_small():
    res = _run(*_small(), SEED)
    assert res["correct"], res["check"]


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(fault):
    res = _run(*_small(), SEED, fault=fault)
    assert not res["correct"], res["check"]


def test_control_reads_above_the_program_small():
    import readings
    from lib import weights
    cfg, ref, mix = _small()
    cfg = dict(cfg, _check_steps=mix["check_steps"])
    prog = _run(*_small(), SEED)["check"]
    ctrl, _ = readings.placed(cfg, ref, mix, SEED,
                              weights.seed_words(SEED), "control")
    limits = bench.limits_of("internvl2-1b.train")
    assert any(ctrl[k] > limits[k] for k in limits), ctrl
    assert ctrl["grad_gap"] > 3 * prog["grad_gap"]["value"]

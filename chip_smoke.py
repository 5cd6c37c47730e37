"""Daydream's main path, end to end, on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the data-parallel path on four chips

One chip: train ``tinyllama-1.1b`` at its published widths (all 22 layers,
random weights from a seed) for 5 steps of batch 2 x 2048 tokens through
``repro.train.Trainer``; capture steps 3-4 with ``jax.profiler``; import
the capture with ``repro.traceio`` and check it against the measured step;
predict from it with ``Scenario`` (``noop`` and ``amp``) and from the
compiled step with ``trace_compiled``; run the flash attention kernel
compiled for the chip against its ``kernels/ref.py`` oracle.

Four chips: the same model and per-chip batch trained on a ``(data=4,
model=1)`` mesh (FSDP, global batch 8 x 2048), captured, imported as four
device workers and predicted from its traces; beside it, a one-device run
captured and predicted as 4-way ``ddp``.

Everything runs in this one process, which holds the chips.  A failed phase
raises; nothing falls back to the CPU.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")

ARCH = "tinyllama-1.1b"
BATCH = 2            # per chip
SEQ = 2048
STEPS = 5
CAPTURED = (3, 4)    # steps inside the profiler window; the import keeps 4


def check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {what}")


def batches(cfg, batch: int):
    from repro.data import make_batch
    step = 0
    while True:
        yield make_batch(cfg, seq_len=SEQ, batch=batch, step=step)
        step += 1


def train_and_capture(cfg, batch: int, capture_dir: str, *, mesh=None,
                      rules=None):
    """``Trainer.fit`` for STEPS steps with the CAPTURED steps profiled.
    Returns (trainer, final state)."""
    import jax
    import numpy as np
    from repro.data import Prefetcher
    from repro.sharding import DEFAULT_RULES
    from repro.train import Trainer, TrainerConfig

    def hook(i, metrics):
        if i == CAPTURED[0] - 1:
            jax.profiler.start_trace(capture_dir)
        elif i == CAPTURED[1]:
            jax.profiler.stop_trace()

    trainer = Trainer(cfg, TrainerConfig(steps=STEPS, log_every=1),
                      mesh=mesh, rules=rules or DEFAULT_RULES)
    if mesh is None:
        state = trainer.fit(Prefetcher(batches(cfg, batch)), hooks=hook)
    else:
        with jax.set_mesh(mesh):
            state = trainer.fit(Prefetcher(batches(cfg, batch)), hooks=hook)
    losses = [m["loss"] for m in trainer.metrics_log]
    check(len(losses) == STEPS and all(np.isfinite(losses)),
          f"{STEPS} finite losses, got {losses}")
    return trainer, state


def step_time(trainer) -> float:
    return trainer.metrics_log[CAPTURED[1]]["step_time_s"]


def device_busy(trace) -> float:
    # lanes never overlap (the importer clips them), so busy is the sum
    return sum(e.dur for e in trace.events if e.thread == "device")


def lane_summary(trace) -> str:
    lanes = collections.Counter(e.thread for e in trace.events)
    return ", ".join(f"{k}={v}" for k, v in sorted(lanes.items()))


def grad_bytes_by_layer(graph, params):
    """Gradient payload of the parameter tree, split over the backward
    layer tags of the imported graph."""
    import jax
    total = float(sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(params)))
    layers = sorted({t.layer for t in graph.tasks()
                     if t.layer and t.phase == "bwd"})
    check(bool(layers), "backward layer tags on the imported device lane")
    return {layer: total / len(layers) for layer in layers}


# ----------------------------------------------------------------- phases
def device_phase(chips: int):
    import jax
    from repro.core import hardware_for
    from repro.runtime.compile_cache import use_compile_cache
    cache = use_compile_cache()
    devs = jax.devices()
    d = devs[0]
    print(f"[device] platform={d.platform} kind={d.device_kind!r} "
          f"count={len(devs)} jax={jax.__version__} compile_cache={cache}",
          flush=True)
    check(d.platform == "tpu", f"a TPU, found {d.platform!r}")
    check(len(devs) >= chips, f"{chips} chip(s), found {len(devs)}")
    hw = hardware_for(d.device_kind)
    print(f"[device] peaks ({hw.name}): {hw.peak_flops / 1e12:.0f} TFLOP/s "
          f"bf16, {hw.hbm_bandwidth / 1e9:.0f} GB/s HBM", flush=True)
    return d, hw


def one_chip(dev, hw) -> None:
    import jax
    from repro.configs import get_config
    from repro.core import CostModel, Scenario, trace_compiled
    from repro.core.task import DEVICE_STREAM, TaskKind
    from repro.data import make_batch
    from repro.models.model import count_params
    from repro.traceio import load_trace_dir

    cfg = get_config(ARCH)
    capture = os.path.join(OUT, "capture")

    # -- train
    print(f"[train] {ARCH}: {cfg.n_layers} layers, d_model={cfg.d_model}, "
          f"{count_params(cfg) / 1e9:.3f}B params, batch {BATCH} x {SEQ}",
          flush=True)
    trainer, state = train_and_capture(cfg, BATCH, capture)
    measured = step_time(trainer)
    peak = dev.memory_stats()["peak_bytes_in_use"]
    mfu = 6 * count_params(cfg) * BATCH * SEQ / measured / hw.peak_flops
    print(f"[train] losses {[round(m['loss'], 5) for m in trainer.metrics_log]}"
          f"; step {CAPTURED[1]} took {measured * 1e3:.3f} ms "
          f"(MFU {mfu:.3f}, 6*N*T over peak); peak_bytes_in_use {peak}",
          flush=True)

    # -- capture and import
    imp = load_trace_dir(capture)
    check(imp.num_workers == 1,
          f"one-chip capture imports as one worker, got {imp.num_workers}")
    trace = imp.traces[0]
    busy = device_busy(trace)
    check(all(e.attrs.get("xla_thread") == "XLA Ops"
              for e in trace.events if e.thread == "device"),
          "device lane holds op-level slices only")
    print(f"[import] 1 worker, lanes: {lane_summary(trace)}; device busy "
          f"{busy * 1e3:.3f} ms of measured step {measured * 1e3:.3f} ms "
          f"({busy / measured:.3f})", flush=True)
    check(busy <= measured, "device busy <= measured step time")
    check(busy > 0.5 * measured, "device busy > 50% of measured step time")

    # -- predict
    cost = CostModel(hw=hw)
    scn = Scenario(traces=imp, cost=cost)
    noop, amp = scn.predict("noop"), scn.predict("amp")
    print(f"[predict] measured step {measured * 1e3:.3f} ms; predicted noop "
          f"makespan {noop.predicted * 1e3:.3f} ms; predicted amp makespan "
          f"{amp.predicted * 1e3:.3f} ms ({amp.speedup:.3f}x)", flush=True)
    batch = {k: jax.numpy.asarray(v)
             for k, v in make_batch(cfg, seq_len=SEQ, batch=BATCH,
                                    step=0).items()}
    bundle = trace_compiled(trainer.step_fn, state, batch, cost=cost)
    lane = bundle.graph.lane_tasks(DEVICE_STREAM)
    check(len(lane) > 100, f"compiled TPU step parses into device tasks, "
                           f"got {len(lane)}")
    kinds = collections.Counter(
        t.attrs.get("opcode") if t.attrs.get("opcode") in (
            "custom-call", "fusion") else t.kind.value for t in lane)
    colls = sum(1 for t in lane if t.kind == TaskKind.COLLECTIVE)
    res = bundle.simulate()
    print(f"[predict] trace_compiled: {len(lane)} device tasks "
          f"({dict(kinds)}; {colls} collectives), "
          f"{bundle.aggregates['flops']:.4g} flops; predicted makespan {res.makespan * 1e3:.3f} ms", flush=True)


def kernels() -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    # flash attention at tinyllama's head layout, 4096 tokens, (B, S, H, D)
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k[0], (1, 4096, 32, 64), jnp.bfloat16)
    kk = jax.random.normal(k[1], (1, 4096, 4, 64), jnp.bfloat16)
    vv = jax.random.normal(k[2], (1, 4096, 4, 64), jnp.bfloat16)
    fn = jax.jit(ops.flash_attention)
    text = fn.lower(q, kk, vv).compile().as_text()
    got = fn(q, kk, vv).astype(jnp.float32)
    bhsd = lambda x: x.transpose(0, 2, 1, 3)      # noqa: E731
    want = bhsd(jax.jit(ref.flash_attention_ref)(*map(bhsd, (q, kk, vv)))
                ).astype(jnp.float32)
    # |got - want| <= atol + one bf16 ulp (2^-7) of |want|, elementwise
    diff = jnp.abs(got - want)
    err = float(jnp.max(diff))
    over = float(jnp.max(diff - 3e-2 - 2.0 ** -7 * jnp.abs(want)))
    custom = "tpu_custom_call" in text
    print(f"[kernels] flash_attention: max |err| {err:.3g} (allowed 0.03 + "
          f"2^-7 * |ref|), tpu_custom_call={custom}", flush=True)
    check(over <= 0, "flash_attention matches kernels/ref.py")
    check(custom, "flash_attention compiled to a TPU custom call")


def four_chips(hw) -> None:
    import jax
    from repro.configs import get_config
    from repro.core import CostModel, Scenario
    from repro.launch.mesh import make_mesh
    from repro.sharding import ShardingRules
    from repro.traceio import load_trace_dir

    cfg = get_config(ARCH)
    cost = CostModel(hw=hw)
    devs = jax.devices()[:4]

    # -- (data=4, model=1) FSDP mesh, global batch 4 x BATCH
    mesh = make_mesh((4, 1), ("data", "model"))
    capture = os.path.join(OUT, "capture_mesh")
    print(f"[mesh] {ARCH} on mesh {dict(mesh.shape)}, layout "
          f"{cfg.layout!r}, global batch {4 * BATCH} x {SEQ}", flush=True)
    trainer, state = train_and_capture(cfg, 4 * BATCH, capture, mesh=mesh,
                                       rules=ShardingRules(layout=cfg.layout))
    del state
    measured = step_time(trainer)
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devs]
    print(f"[mesh] losses {[round(m['loss'], 5) for m in trainer.metrics_log]}"
          f"; step {CAPTURED[1]} took {measured * 1e3:.3f} ms; "
          f"peak_bytes_in_use per device {peaks}", flush=True)
    check(max(peaks) <= 2 * min(peaks),
          "per-device peak memory within 2x (state is sharded)")
    imp = load_trace_dir(capture)
    check(imp.num_workers == 4,
          f"mesh capture imports as four device workers, got "
          f"{imp.num_workers}")
    for i, tr in enumerate(imp.traces):
        print(f"[mesh] worker {i}: lanes {lane_summary(tr)}; device busy "
              f"{device_busy(tr) * 1e3:.3f} ms", flush=True)
    traced = Scenario(traces=imp, cost=cost,
                      collective_mode="fused").predict("noop")

    # -- the same model and per-chip batch on device 0 alone, as 4-way ddp
    capture1 = os.path.join(OUT, "capture_one")
    solo, state = train_and_capture(cfg, BATCH, capture1)
    solo_measured = step_time(solo)
    imp1 = load_trace_dir(capture1)
    check(imp1.num_workers == 1, "one-device capture imports as one worker")
    g1 = imp1.graphs[0]
    grads = grad_bytes_by_layer(g1, state["params"])
    ddp = Scenario(g1, cost=cost, workers=4,
                   layer_grad_bytes=grads).predict("ddp")
    print(f"[compare] one device: measured step {solo_measured * 1e3:.3f} ms"
          f"; 4 chips (data=4): measured step {measured * 1e3:.3f} ms; "
          f"predicted ddp x4 from the one-device capture "
          f"{ddp.predicted * 1e3:.3f} ms "
          f"({sum(grads.values()) / 1e9:.3f} GB of gradients); predicted "
          f"from the 4-chip traces {traced.predicted * 1e3:.3f} ms",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the data-parallel path")
    args = ap.parse_args(argv)
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    t0 = time.time()
    dev, hw = device_phase(args.chips)
    if args.chips == 4:
        four_chips(hw)
    else:
        one_chip(dev, hw)   # returns with the training state freed
        kernels()
    import jax
    print(f"[done] {time.time() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The ``train`` generator: ``repro.train.Trainer.fit`` on one chip.

Set-up builds one ``Trainer`` whose state is the benchmark's weights for
``--seed`` (made on the device in one jitted call) and drives it through
its first steps with ``fit``: the first ``check_steps`` are the steps the
reference follows, one more warms up.  The window is the same ``fit``
call going on, timed from its per-step hooks, until ``--seconds`` have
passed (or, traced, for ``trace_steps`` steps under ``jax.profiler``).

Then the state is freed and the plain reference runs the check steps on
the same weights and rows.  Compared: each check step's loss, the norm of
every leaf of the first gradient as AdamW got it (worked out from its
first moment after one step: m1 = (1 - b1) g), and the norm of every
leaf's change over the check steps.
"""

from __future__ import annotations

import functools
import gc
import math
import os
import queue
import shutil
import statistics
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from lib import bench, devtrace, plain, weights


class WindowClosed(Exception):
    """Raised from a ``fit`` hook to end the run."""


def batch_at(cfg: dict, mix: dict, seed: int, step: int) -> dict:
    """The rows of one step, drawn from (seed, step) on the host: token ids
    uniform over the vocabulary, labels the next id, and for a VLM
    ``n_patches`` N(0, patch_std) patch embeddings in front."""
    m, t = cfg["model"], cfg["train"]
    B, S, P = t["batch"], t["seq_len"], m.get("n_patches", 0)
    rng = np.random.default_rng([seed, step])
    ids = rng.integers(0, m["vocab"], (B, S - P + 1), dtype=np.int32)
    out = {"tokens": ids[:, :-1], "labels": ids[:, 1:]}
    if P:
        out["patch_embeds"] = (rng.standard_normal(
            (B, P, m["d_model"]), np.float32) * mix["patch_std"]
        ).astype(jnp.bfloat16)
    return out


class Prefetch:
    """The rows of step 0, 1, 2, ... made ahead on one host thread, as a
    data pipeline would, so that drawing them does not idle the device.
    The rows of a step depend on (seed, step) alone."""

    def __init__(self, make, depth: int = 2):
        self.make, self.q = make, queue.Queue(depth)
        self.stop = threading.Event()
        self.thread = threading.Thread(target=self._fill, daemon=True)
        self.thread.start()

    def _fill(self):
        step = 0
        while not self.stop.is_set():
            try:
                item = self.make(step)
            except Exception as e:      # raised again in the consumer
                item = e
            while not self.stop.is_set():
                try:
                    self.q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    pass
            step += 1

    def __iter__(self):
        while True:
            with jax.profiler.TraceAnnotation("wait for rows"):
                item = self.q.get()
            if isinstance(item, Exception):
                raise item
            yield item

    def close(self):
        self.stop.set()
        self.thread.join()


def make_trainer(cfg: dict, words, fault=None):
    """A ``Trainer`` for the configuration whose state starts from the
    benchmark's weights, and whose jitted step records the first
    gradient's and the check steps' change norms as it goes.  ``fault``
    (tests only) wraps the program's step to plant a fault."""
    from repro.models.model import ModelConfig
    from repro.optim import AdamW
    from repro.train import Trainer, TrainerConfig

    check_steps = cfg["_check_steps"]
    b1 = cfg["optimizer"]["b1"]

    class BenchTrainer(Trainer):
        calls = 0
        grad_norms = change_norms = None

        def init_state(self):
            like = jax.eval_shape(super().init_state)["params"]
            rules = cfg["init"]

            def make(w):
                params = weights.make_params(like, w, rules)
                return {"params": params, "opt": self.opt.init(params),
                        "step": jnp.zeros((), jnp.int32)}
            self.like = like
            return jax.jit(make)(words)

        def jitted_step(self):
            fn = super().jitted_step()
            if fault is not None:
                fn = fault(fn)
            grad_of = jax.jit(lambda m: weights.slice_norms(m) / (1 - b1))
            change_of = jax.jit(lambda p, w: weights.slice_norms(jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                p, weights.make_params(self.like, w, cfg["init"]))))

            def step(state, batch):
                state, metrics = fn(state, batch)
                self.calls += 1
                if self.calls == 1:
                    self.grad_norms = np.asarray(grad_of(state["opt"]["m"]))
                if self.calls == check_steps:
                    self.change_norms = np.asarray(
                        change_of(state["params"], words))
                return state, metrics
            return step

    return BenchTrainer(ModelConfig(**cfg["model"]),
                        TrainerConfig(steps=1 << 40, log_every=0),
                        optimizer=AdamW(**cfg["optimizer"]))


def run(cell: dict, cfg: dict, ref, mix: dict, args, t_start: float,
        chips: int = 1, fault=None) -> dict:
    cfg = dict(cfg, _check_steps=mix["check_steps"])
    seed = args.seed
    words = weights.seed_words(seed)
    warm = mix["check_steps"] + mix["warmup_steps"]
    trainer = make_trainer(cfg, words, fault)
    trace_dir = os.path.join(bench.HERE, ".cache", "trace", cell["name"])
    log, clock = [], {}

    def hook(i, metrics):
        now = time.perf_counter()
        log.append((i, now, metrics["loss"]))
        if i == warm - 1:
            clock["t0"] = now
            if args.trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(trace_dir)
        elif i >= warm:
            if args.trace and i == warm - 1 + mix["trace_steps"]:
                jax.profiler.stop_trace()
                raise WindowClosed
            if not args.trace and now - clock["t0"] >= args.seconds:
                raise WindowClosed

    rows = Prefetch(lambda step: batch_at(cfg, mix, seed, step))
    try:
        trainer.fit(rows, hooks=hook)
    except WindowClosed:
        pass
    finally:
        rows.close()
    setup_s = clock["t0"] - t_start
    peak = bench.memory_peak(chips)
    timed = [(i, t, loss) for i, t, loss in log if i >= warm]
    ends = [clock["t0"]] + [t for _, t, _ in timed]
    steps_s = [b - a for a, b in zip(ends, ends[1:])]
    print(f"set-up: first step done at {log[0][1] - t_start:.3f} s, "
          f"window opened at {setup_s:.3f} s", file=sys.stderr, flush=True)
    if steps_s:
        print(f"window: {len(steps_s)} steps, step s min "
              f"{min(steps_s):.4f} median {statistics.median(steps_s):.4f} "
              f"max {max(steps_s):.4f}", file=sys.stderr, flush=True)
    prog = {"loss": [loss for i, _, loss in log if i < mix["check_steps"]],
            "grad": trainer.grad_norms, "change": trainer.change_norms}
    names = weights.slice_names(trainer.like)
    like = trainer.like
    del trainer
    gc.collect()

    numbers, where = check(cfg, ref, mix, seed, words, like, prog, names)
    limits = bench.limits_of(cell["name"])
    correct, checked = bench.judge(numbers, limits)
    where["not_compared"] = {k: v for k, v in numbers.items()
                             if k not in checked}
    result = {"correct": correct, "attempted": len(timed),
              "failed": sum(not math.isfinite(x) for _, _, x in timed),
              "check": checked, "where": where}
    if args.trace:
        red = devtrace.reduce_trace(devtrace.trace_file(trace_dir))
        ctx = {"trace": red, "config": cfg, "peaks": args.peaks}
        result["metrics"] = bench.read_metrics(args.spec, cell, ctx)
        result["trace"] = red
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        window = timed[-1][1] - clock["t0"]
        tokens = len(timed) * cfg["train"]["batch"] * cfg["train"]["seq_len"]
        result["metrics"] = {
            "train_tokens_per_s": {"value": tokens / window,
                                   "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    result["memory_peak_bytes"] = peak
    return result


def check(cfg, ref, mix, seed, words, like, prog, names, num=None):
    """Run the reference over the check steps and compare; returns the
    numbers compared and the leaves where the gradient and change gaps
    are worst."""
    params = jax.jit(functools.partial(weights.make_params, like,
                                       rules=cfg["init"]))(words)
    rows = [batch_at(cfg, mix, seed, s) for s in range(mix["check_steps"])]
    loss_fn = functools.partial(ref.loss, model=cfg["model"])
    losses, grad, final, secs = plain.train_reference(
        loss_fn, params, rows, cfg["optimizer"], num or plain.Numerics())
    change = np.asarray(jax.jit(lambda a, b: weights.slice_norms(
        jax.tree.map(lambda x, y: x.astype(jnp.float32)
                     - y.astype(jnp.float32), a, b)))(final, params))
    refr = {"loss": losses, "grad": grad, "change": change}
    numbers, where = plain.train_numbers(prog, refr, names)
    where["reference_s"] = secs
    return numbers, where

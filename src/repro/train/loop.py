"""Training loop: jit-compiled step, sharded state, checkpoints.

Composition of the substrate layers:
  models.make_train_step  (loss + AdamW update, grad-accum aware)
  data.SyntheticLM        (per-host batch slices, prefetch)
  ckpt.CheckpointManager  (atomic, async, elastic re-shard)
  obs.span                (the loop's phases, when telemetry is on)

``fit`` records a ``train.fit`` span around the call, ``train.init_state``
around restoring or making the state, and per step ``train.next_batch``,
``train.to_device``, ``train.dispatch`` (the first call's trace, lower and
compile included), ``train.sync`` (the metrics' ``device_get``),
``train.hooks`` and, on a save, ``train.checkpoint``, each with ``step``,
and writes the records out as it returns.  Disabled, each span costs one
``None`` check.

Works on a laptop (no mesh), the single-pod mesh, and the multi-pod mesh —
the sharding rules resolve against whatever mesh is active.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import (ModelConfig, init_params, make_train_step)
from repro.models.paramdecl import SpecLeaf, specs_of
from repro.optim import AdamW
from repro.ckpt import CheckpointManager
from repro.obs import flush, span
from repro.sharding import ShardingRules, DEFAULT_RULES


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    log_every: int = 10
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    ckpt_async: bool = True
    seed: int = 0


class Trainer:
    def __init__(self, cfg: ModelConfig, tc: TrainerConfig,
                 optimizer: Optional[AdamW] = None, mesh=None,
                 rules: ShardingRules = DEFAULT_RULES) -> None:
        self.cfg = cfg
        self.tc = tc
        self.opt = optimizer or AdamW()
        self.mesh = mesh
        self.rules = rules
        self.step_fn = make_train_step(cfg, self.opt)
        self.ckpt = (CheckpointManager(tc.ckpt_dir) if tc.ckpt_dir else None)
        self.metrics_log: list = []
        self._jitted = None

    # ------------------------------------------------------------- state
    def init_state(self) -> Dict[str, Any]:
        def init():
            params = init_params(self.cfg, jax.random.PRNGKey(self.tc.seed))
            return {"params": params, "opt": self.opt.init(params),
                    "step": jnp.zeros((), jnp.int32)}
        sh = self.state_shardings()
        # under a mesh, each device materializes only its shard of the state
        return init() if sh is None else jax.jit(init, out_shardings=sh)()

    def state_shardings(self):
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec_state = {"params": init_params(self.cfg, None), "opt": None,
                      "step": SpecLeaf((), jnp.dtype(jnp.int32), ())}
        spec_state["opt"] = self.opt.init(spec_state["params"])
        spec_tree = specs_of(spec_state, self.rules)
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), spec_tree,
                            is_leaf=lambda x: isinstance(x, P))

    def jitted_step(self):
        if self._jitted is None:
            sh = self.state_shardings()
            self._jitted = jax.jit(self.step_fn, in_shardings=(sh, None),
                                   out_shardings=(sh, None),
                                   donate_argnums=(0,))
        return self._jitted

    # --------------------------------------------------------------- loop
    def restore_or_init(self) -> Dict[str, Any]:
        if self.ckpt and self.ckpt.latest_step() is not None:
            like = jax.eval_shape(self.init_state)
            state, _ = self.ckpt.restore_latest(
                like, mesh=self.mesh, shardings=self.state_shardings())
            return state
        return self.init_state()

    def fit(self, batches: Iterator[Dict[str, np.ndarray]],
            steps: Optional[int] = None,
            hooks: Optional[Callable[[int, Dict], None]] = None
            ) -> Dict[str, Any]:
        steps = steps or self.tc.steps
        with span("train.fit", steps=steps):
            with span("train.init_state"):
                state = self.restore_or_init()
            start = int(jax.device_get(state["step"]))
            step_fn = self.jitted_step()
            it = iter(batches)
            for i in range(start, steps):
                with span("train.next_batch", step=i):
                    rows = next(it)
                with span("train.to_device", step=i):
                    batch = {k: jnp.asarray(v) for k, v in rows.items()}
                # a no-op unless a profiler trace is active; marks the step
                # window the capture importer slices on
                with jax.profiler.StepTraceAnnotation("train", step_num=i):
                    t0 = time.perf_counter()
                    with span("train.dispatch", step=i):
                        state, metrics = step_fn(state, batch)
                    with span("train.sync", step=i):
                        metrics = {k: float(jax.device_get(v))
                                   for k, v in metrics.items()}
                    dt = time.perf_counter() - t0
                metrics.update(step=i, step_time_s=dt)
                self.metrics_log.append(metrics)
                with span("train.hooks", step=i):
                    if hooks:
                        hooks(i, metrics)
                    if self.tc.log_every and (i % self.tc.log_every == 0):
                        print(f"step {i:6d} loss={metrics['loss']:.4f} "
                              f"gnorm={metrics.get('grad_norm', 0):.3f} "
                              f"dt={dt*1e3:.1f}ms", flush=True)
                if self.ckpt and ((i + 1) % self.tc.ckpt_every == 0
                                  or i + 1 == steps):
                    with span("train.checkpoint", step=i):
                        if self.tc.ckpt_async:
                            self.ckpt.save_async(i, state)
                        else:
                            self.ckpt.save(i, state)
            if self.ckpt:
                self.ckpt.wait()
        flush()             # the loop's records reach the file as fit ends
        return state

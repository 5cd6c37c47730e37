"""Trace acquisition: jitted JAX step functions -> Daydream dependency graphs.

Daydream Phase 1 (paper §4.1).  Two acquisition modes:

* :func:`trace_compiled` — AOT: lower+compile the step (optionally under a
  sharded mesh with ShapeDtypeStruct inputs — zero allocation), parse the HLO,
  assign analytical durations.  This is the mode every dry-run / roofline /
  what-if query uses, and needs no hardware at all.

* :func:`trace_measured` — runs the compiled step on the *local* backend and
  rescales the analytical graph so total device time matches measured
  wall-clock (host-calibrated).  Used by the validation benchmarks that compare
  predicted vs ground-truth speedups on CPU, mirroring the paper's
  predict -> implement -> compare methodology.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax

from .costmodel import CostModel, MeshTopology
from .graph import DependencyGraph
from .hlo import aggregate_costs, extract_graph, parse_hlo_module, HloModule
from .simulate import simulate, SimResult
from .task import Task, TaskKind, DEVICE_STREAM


@dataclasses.dataclass
class TraceBundle:
    """Everything Daydream knows about one step function."""

    graph: DependencyGraph
    module: HloModule
    aggregates: Dict[str, float]
    cost: CostModel
    compiled: Any = None
    measured_step_s: Optional[float] = None

    def simulate(self, schedule=None) -> SimResult:
        return simulate(self.graph, schedule)

    def xla_cost_analysis(self) -> Dict[str, float]:
        if self.compiled is None:
            return {}
        return self.compiled.cost_analysis()

    def memory_analysis(self):
        if self.compiled is None:
            return None
        try:
            return self.compiled.memory_analysis()
        except Exception:
            return None

    def export_chrome(self, path: str,
                      result: Optional[SimResult] = None) -> Dict[str, Any]:
        """Export the (simulated) step timeline as Chrome trace-event JSON.

        Opens in Perfetto / ``chrome://tracing``; re-importable via
        :mod:`repro.traceio` (the round-trip reproduces the simulated
        makespan).  ``result`` defaults to a fresh :meth:`simulate`.
        """
        from repro.traceio import export_graph_trace
        return export_graph_trace(self.graph, result or self.simulate(),
                                  path)


def lower_and_compile(fn: Callable, *args, mesh=None, in_shardings=None,
                      out_shardings=None, donate_argnums=(), static_argnums=(),
                      **kwargs):
    jitted = jax.jit(fn, in_shardings=in_shardings, out_shardings=out_shardings,
                     donate_argnums=donate_argnums, static_argnums=static_argnums)
    if mesh is not None:
        with mesh:
            lowered = jitted.lower(*args, **kwargs)
            return lowered, lowered.compile()
    lowered = jitted.lower(*args, **kwargs)
    return lowered, lowered.compile()


def trace_compiled(fn: Callable, *args, cost: Optional[CostModel] = None,
                   mesh=None, in_shardings=None, out_shardings=None,
                   donate_argnums=(), static_argnums=(),
                   overlap_collectives: bool = False,
                   devices_per_pod: Optional[int] = None,
                   max_tasks: int = 60_000, **kwargs) -> TraceBundle:
    """AOT trace: compile, parse HLO, build graph + aggregates."""
    cost = cost or CostModel()
    _, compiled = lower_and_compile(
        fn, *args, mesh=mesh, in_shardings=in_shardings,
        out_shardings=out_shardings, donate_argnums=donate_argnums,
        static_argnums=static_argnums, **kwargs)
    module = parse_hlo_module(compiled.as_text())
    graph = extract_graph(module, cost, overlap_collectives=overlap_collectives,
                          devices_per_pod=devices_per_pod, max_tasks=max_tasks)
    agg = aggregate_costs(module, cost, devices_per_pod)
    return TraceBundle(graph=graph, module=module, aggregates=agg, cost=cost,
                       compiled=compiled)


def measure_wallclock(fn: Callable, *args, iters: int = 10, warmup: int = 3,
                      **kwargs) -> float:
    """Median wall-clock of a jitted callable (blocks on outputs)."""
    jitted = jax.jit(fn) if not hasattr(fn, "lower") else fn
    times = []
    for i in range(warmup + iters):
        t0 = time.perf_counter()
        out = jitted(*args, **kwargs)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        if i >= warmup:
            times.append(dt)
    times.sort()
    return times[len(times) // 2]


def trace_measured(fn: Callable, *args, cost: Optional[CostModel] = None,
                   iters: int = 10, max_tasks: int = 60_000,
                   **kwargs) -> TraceBundle:
    """Compiled trace rescaled so simulated device time == measured wall-clock.

    This mirrors the paper's use of *profiled* durations: the graph topology
    comes from the compiled program, per-task durations keep their analytical
    *relative* weights, and the absolute scale is pinned by measurement.  The
    simulated baseline therefore matches ground truth by construction and every
    what-if perturbs from a measured starting point (paper §4.1 Phase 1).
    """
    bundle = trace_compiled(fn, *args, cost=cost, max_tasks=max_tasks, **kwargs)
    wall = measure_wallclock(fn, *args, iters=iters, **kwargs)
    sim = bundle.simulate()
    device_time = sum(t.duration for t in bundle.graph.tasks()
                      if t.thread == DEVICE_STREAM)
    host_time = sim.makespan - device_time if sim.makespan > device_time else 0.0
    target_device = max(wall - host_time, 1e-9)
    scale = target_device / max(device_time, 1e-12)
    for t in bundle.graph.tasks():
        if t.thread == DEVICE_STREAM:
            t.duration *= scale
    # calibrate the cost model so *new* task durations (insertions in
    # what-ifs) land in the same wall-clock units as the rescaled trace
    base = bundle.cost
    bundle.cost = dataclasses.replace(
        base, compute_scale=base.compute_scale * scale,
        memory_scale=base.memory_scale * scale)
    bundle.measured_step_s = wall
    return bundle

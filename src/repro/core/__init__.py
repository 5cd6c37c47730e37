"""Daydream core: dependency-graph what-if performance prediction for DNN
training/serving on TPU-class hardware (paper: Zhu et al., USENIX ATC 2020).

Public surface:

    from repro.core import (
        Task, TaskKind, DependencyGraph, simulate, GraphTransform,
        trace_compiled, trace_measured, CostModel, whatif,
        ClusterGraph, WorkerSpec,          # N-worker global-graph simulation
        Optimization, Scenario, Stack, Prediction,   # unified what-if API
        register, get_optimization,        # the optimization registry
    )

The unified what-if API (:mod:`repro.core.optimize`) is the preferred
surface: optimizations are registered, typed, composable via ``|``, and
``Scenario.sweep`` evaluates parameter grids reusing one ClusterGraph
build.  The ``whatif.what_if_*`` functions remain as thin wrappers.
``Scenario(trace_dir=...)`` (and ``ClusterGraph.from_traces``) runs the
same registry on *real* per-worker profiler traces imported via
:mod:`repro.traceio` (Chrome trace-event JSON / native JSONL, dPRO-style
clock alignment, asymmetric per-worker graphs).

Simulation engines: :func:`simulate` is the O(E log V) event-driven heap
engine; :func:`simulate_reference` keeps the paper's Algorithm 1 frontier
scan as the equivalence oracle.  :class:`ClusterGraph` replicates a profiled
single-worker graph across N (possibly heterogeneous) workers with
cross-worker collective edges (ring / hierarchical / fused) and returns a
per-worker :class:`SimResult` breakdown — see :mod:`repro.core.cluster`.
"""

from .task import (Task, TaskKind, HardwareSpec, TPU_V5E, PEAKS,
                   hardware_for, HOST_THREAD,
                   DEVICE_STREAM, DATA_THREAD, DMA_CHANNEL, ici_channel,
                   p2p_channel, worker_thread, split_worker_thread)
from .graph import DependencyGraph, GraphError
from .simulate import (simulate, simulate_incremental, simulate_reference,
                       SimResult, default_schedule, lane_utilization,
                       make_priority_schedule)
from .cluster import (ClusterGraph, ClusterResult, WorkerSpec,
                      match_collective_gid_groups, match_collective_groups,
                      match_push_pull_groups, match_wired_p2p)
from .fold import (FoldedClusterGraph, FoldedClusterResult, WorkerClass,
                   fold_cluster, fold_plan, partition_workers)
from .transform import (GraphTransform, predicted_speedup, by_kind, by_name,
                        by_layer, by_phase, on_device, all_of, any_of)
from .costmodel import CostModel, CollectiveModel, MeshTopology
from .hlo import parse_hlo_module, extract_graph, aggregate_costs, split_op_name
from .layermap import LayerMap, LayerProfile, bucket_layers
from .trace import (TraceBundle, trace_compiled, trace_measured,
                    measure_wallclock, lower_and_compile)
from .optimize import (Optimization, OptimizationError, PipelineParallel,
                       Prediction, Scenario, Stack, available,
                       get_optimization, greedy_search, parse_stack,
                       register)
from . import optimize
from . import whatif

__all__ = [
    "Task", "TaskKind", "HardwareSpec", "TPU_V5E", "PEAKS", "hardware_for",
    "HOST_THREAD", "DEVICE_STREAM", "DATA_THREAD", "DMA_CHANNEL", "ici_channel",
    "p2p_channel", "worker_thread", "split_worker_thread",
    "DependencyGraph", "GraphError",
    "simulate", "simulate_incremental", "simulate_reference", "SimResult",
    "default_schedule", "lane_utilization", "make_priority_schedule",
    "ClusterGraph", "ClusterResult", "WorkerSpec",
    "match_collective_gid_groups", "match_collective_groups",
    "match_push_pull_groups", "match_wired_p2p",
    "FoldedClusterGraph", "FoldedClusterResult", "WorkerClass",
    "fold_cluster", "fold_plan", "partition_workers",
    "GraphTransform", "predicted_speedup",
    "by_kind", "by_name", "by_layer", "by_phase", "on_device", "all_of", "any_of",
    "CostModel", "CollectiveModel", "MeshTopology",
    "parse_hlo_module", "extract_graph", "aggregate_costs", "split_op_name",
    "LayerMap", "LayerProfile", "bucket_layers",
    "TraceBundle", "trace_compiled", "trace_measured", "measure_wallclock",
    "lower_and_compile",
    "Optimization", "OptimizationError", "PipelineParallel", "Prediction",
    "Scenario", "Stack",
    "available", "get_optimization", "greedy_search", "parse_stack",
    "register",
    "optimize", "whatif",
]

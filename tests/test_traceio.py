"""Trace I/O subsystem (repro.traceio) acceptance tests.

The ISSUE's acceptance criteria live here:

* **Round-trip invariant**: exporting a simulated uniform N-worker cluster
  to per-worker Chrome traces and re-importing via
  ``ClusterGraph.from_traces`` reproduces the predicted makespan within
  1e-6 relative error (a golden copy of the makespan is pinned under
  ``tests/golden/trace_roundtrip.json``).
* **Replicate equivalence**: a trace-imported cluster of N identical
  workers matches the replicate path (``ClusterGraph.build``) to float
  precision, for every collective mode.
* **Skew handling**: a synthetic trace set with per-worker clock offsets /
  drift and a straggler is aligned (dPRO-style least-squares offset+drift
  on collective-end anchors) and predicted correctly.
"""

import json
import math
import os

import pytest

from repro.core import (ClusterGraph, CostModel, GraphError, Task, TaskKind,
                        WorkerSpec, simulate, whatif, DEVICE_STREAM,
                        HOST_THREAD)
from repro.core.cluster import match_collective_groups
from repro import traceio
from repro.traceio import (TraceEvent, TraceImportError, WorkerTrace,
                           align_traces, apply_alignment, events_from_graph,
                           graph_from_events, load_trace_dir, read_jsonl,
                           synthetic_cluster_traces, write_jsonl,
                           write_synthetic_trace_dir)
from synthgraphs import training_step_graph

LAYERS = 6
GRADS = {f"l{i}": 30e6 for i in range(LAYERS)}
GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "trace_roundtrip.json")


@pytest.fixture()
def ddp_graph():
    g = training_step_graph(layers=LAYERS)
    return whatif.what_if_distributed(g, GRADS, num_workers=4).graph


def write_traces(tmp_path, traces):
    os.makedirs(str(tmp_path), exist_ok=True)
    for tr in traces:
        write_jsonl(tr.events, str(tmp_path / f"worker{tr.worker}.jsonl"))
    return str(tmp_path)


# ================================================================ round trip
class TestRoundTrip:
    def test_uniform_cluster_export_import_recovers_makespan(self, ddp_graph,
                                                             tmp_path):
        """THE acceptance invariant: simulate -> export -> import -> same
        makespan within 1e-6 relative."""
        cost = CostModel()
        cg = ClusterGraph.build(ddp_graph, 4, cost=cost)
        res = cg.simulate()
        traceio.export_cluster_traces(cg, res, str(tmp_path))
        res2 = ClusterGraph.from_traces(str(tmp_path), cost=cost).simulate()
        assert res2.makespan == pytest.approx(res.makespan, rel=1e-6)

    def test_roundtrip_matches_golden(self, ddp_graph, tmp_path):
        """The fixed synthetic cluster's makespan is pinned by a golden
        file: format/importer drift that changes predictions fails here."""
        with open(GOLDEN) as f:
            golden = json.load(f)
        cost = CostModel()
        cg = ClusterGraph.build(ddp_graph, golden["workers"], cost=cost)
        res = cg.simulate()
        assert res.makespan == pytest.approx(golden["makespan_s"], rel=1e-9)
        traceio.export_cluster_traces(cg, res, str(tmp_path))
        res2 = ClusterGraph.from_traces(str(tmp_path), cost=cost).simulate()
        assert res2.makespan == pytest.approx(golden["makespan_s"], rel=1e-6)

    def test_single_graph_chrome_roundtrip_exact(self, ddp_graph, tmp_path):
        """graph -> Chrome JSON -> graph reproduces the simulated makespan
        exactly (all edges/durations/gaps survive)."""
        res = simulate(ddp_graph)
        path = str(tmp_path / "step.trace.json")
        traceio.export_graph_trace(ddp_graph, res, path)
        tr = traceio.load_worker_trace(path)
        g2 = graph_from_events(tr)
        assert len(g2) == len(ddp_graph)
        assert simulate(g2).makespan == pytest.approx(res.makespan,
                                                      rel=1e-12)

    def test_export_tolerates_none_valued_attrs(self):
        """HLO-extracted graphs tag non-collective comm tasks with
        ``collective=None`` / ``group_size=None``; export must not choke."""
        from repro.core import DependencyGraph
        g = DependencyGraph()
        g.add_task(Task("permute", TaskKind.COLLECTIVE, "ici:x", 1e-3,
                        attrs={"collective": None, "group_size": None}))
        evs = events_from_graph(g)
        assert evs[0].group_size == 0 and evs[0].collective is None
        tr = read_jsonl(iter(write_jsonl(evs)))
        assert simulate(graph_from_events(tr)).makespan == \
            pytest.approx(1e-3)

    def test_jsonl_roundtrip_in_memory(self, ddp_graph):
        events = events_from_graph(ddp_graph)
        lines = write_jsonl(events)            # no path: in-memory
        tr = read_jsonl(iter(lines))
        g2 = graph_from_events(tr)
        assert simulate(g2).makespan == \
            pytest.approx(simulate(ddp_graph).makespan, rel=1e-12)

    def test_exported_cluster_trace_opens_as_chrome_json(self, ddp_graph,
                                                         tmp_path):
        cg = ClusterGraph.build(ddp_graph, 2)
        traceio.export_cluster_traces(cg, cg.simulate(), str(tmp_path))
        with open(tmp_path / "worker0.trace.json") as f:
            data = json.load(f)
        evs = data["traceEvents"]
        assert any(e.get("ph") == "X" for e in evs)
        assert any(e.get("ph") == "M" and e.get("name") == "thread_name"
                   for e in evs)
        # collective pieces collapsed back to one event per all-reduce
        names = [e["name"] for e in evs if e.get("ph") == "X"]
        assert not any(":leg" in n for n in names)
        assert any(e.get("args", {}).get("collective") == "all-reduce"
                   for e in evs if e.get("ph") == "X")


# ===================================================== replicate equivalence
class TestReplicateEquivalence:
    @pytest.mark.parametrize("n", [2, 4, 8])
    @pytest.mark.parametrize("mode", ["ring", "fused", "hierarchical"])
    def test_identical_workers_match_replicate_path(self, ddp_graph, n, mode,
                                                    tmp_path):
        """N identical imported traces == ClusterGraph.build to float
        precision, for every collective mode."""
        cost = CostModel()
        build = ClusterGraph.build(ddp_graph, n, cost=cost,
                                   collective_mode=mode).simulate()
        events = events_from_graph(ddp_graph)
        for w in range(n):
            write_jsonl(events, str(tmp_path / f"worker{w}.jsonl"))
        imported = ClusterGraph.from_traces(
            str(tmp_path), cost=cost, collective_mode=mode).simulate()
        assert imported.makespan == pytest.approx(build.makespan, rel=1e-12)
        assert imported.worker_makespans() == \
            pytest.approx(build.worker_makespans(), rel=1e-12)

    def test_from_worker_graphs_single_worker_identity(self, ddp_graph):
        res = ClusterGraph.from_worker_graphs([ddp_graph]).simulate()
        assert res.makespan == pytest.approx(simulate(ddp_graph).makespan,
                                             rel=1e-12)

    def test_worker_specs_layer_on_top_of_traces(self, ddp_graph):
        """Explicit WorkerSpecs scale the *traced* durations — the
        straggler what-if on imported traces."""
        uni = ClusterGraph.from_worker_graphs([ddp_graph] * 4).simulate()
        specs = [WorkerSpec(compute_scale=2.0 if i == 0 else 1.0)
                 for i in range(4)]
        slow = ClusterGraph.from_worker_graphs([ddp_graph] * 4,
                                               specs).simulate()
        assert slow.makespan > uni.makespan * 1.2
        assert slow.straggler() == 0


# ============================================================ clock alignment
class TestAlignment:
    OFFSETS = [0.0, 0.05, -0.03, 0.12]
    DRIFTS = [1.0, 1.0002, 0.9999, 1.0]

    def test_alignment_recovers_offset_and_drift(self):
        traces = synthetic_cluster_traces(
            4, clock_offsets=self.OFFSETS, clock_drifts=self.DRIFTS)
        aligns = align_traces(traces)
        for al, off, drift in zip(aligns, self.OFFSETS, self.DRIFTS):
            assert al.anchors == LAYERS
            # local = true*d + o  =>  true = (1/d)*local - o/d
            assert al.scale == pytest.approx(1.0 / drift, rel=1e-9)
            assert al.offset == pytest.approx(-off / drift, rel=1e-6,
                                              abs=1e-12)
            assert al.residual < 1e-9

    def test_skewed_clocks_do_not_change_prediction(self, tmp_path):
        """Prediction from offset/drifted traces == prediction from clean
        traces: alignment undoes the clocks."""
        cost = CostModel()
        clean = synthetic_cluster_traces(4)
        skewed = synthetic_cluster_traces(
            4, clock_offsets=self.OFFSETS, clock_drifts=self.DRIFTS)
        d1 = write_traces(tmp_path / "clean", clean)
        d2 = write_traces(tmp_path / "skewed", skewed)
        r1 = ClusterGraph.from_traces(d1, cost=cost).simulate()
        r2 = ClusterGraph.from_traces(d2, cost=cost).simulate()
        assert r2.makespan == pytest.approx(r1.makespan, rel=1e-6)

    def test_skewed_straggler_predicted_correctly(self, tmp_path):
        """Acceptance: clock-offset + straggler trace set is aligned and
        predicted correctly — the straggler's extra compute shifts the
        makespan by the analytical amount (everyone waits on the ring)."""
        cost = CostModel()
        slowdown = 2.0
        uni = synthetic_cluster_traces(4)
        strag = synthetic_cluster_traces(
            4, compute_scales=[slowdown, 1.0, 1.0, 1.0],
            clock_offsets=self.OFFSETS, clock_drifts=self.DRIFTS)
        d1 = write_traces(tmp_path / "uni", uni)
        d2 = write_traces(tmp_path / "strag", strag)
        r_uni = ClusterGraph.from_traces(d1, cost=cost).simulate()
        r = ClusterGraph.from_traces(d2, cost=cost).simulate()
        device_compute = sum(e.dur for e in uni[0].events
                             if e.thread == DEVICE_STREAM)
        expected = r_uni.makespan + (slowdown - 1.0) * device_compute
        assert r.makespan == pytest.approx(expected, rel=0.02)
        assert r.straggler() == 0

    def test_start_skew_gates_late_worker(self, tmp_path):
        """A worker whose (aligned) trace starts late stays late in the
        simulation — the start-skew gate tasks."""
        traces = synthetic_cluster_traces(2)
        late = 5e-3
        for ev in traces[1].events:
            ev.ts += late                     # true late start, not clock
        d = write_traces(tmp_path, traces)
        imp = load_trace_dir(d, align=False)
        assert imp.start_skews[1] == pytest.approx(late)
        res = ClusterGraph.from_traces(imp).simulate()
        base = ClusterGraph.from_traces(
            write_traces(tmp_path / "clean", synthetic_cluster_traces(2))
        ).simulate()
        assert res.makespan > base.makespan
        assert res.makespan == pytest.approx(base.makespan + late, rel=0.2)

    def test_single_worker_alignment_is_identity(self):
        traces = synthetic_cluster_traces(1)
        aligns = align_traces(traces)
        assert aligns[0].is_identity


# =============================================================== importing
class TestImport:
    def test_stream_order_and_deps_reconstructed(self):
        evs = [
            TraceEvent("a", "host", ts=0.0, dur=1e-3, eid=0),
            TraceEvent("b", "device", ts=2e-3, dur=1e-3, eid=1, deps=[0]),
            TraceEvent("c", "device", ts=4e-3, dur=1e-3, eid=2),
            TraceEvent("d", "ici:x", ts=5e-3, dur=1e-3, eid=3, deps=[2]),
        ]
        g = graph_from_events(WorkerTrace(0, evs))
        assert len(g) == 4
        by_name = {t.name: t for t in g.tasks()}
        # cross-thread dep a->b, lane edge b->c, cross-thread c->d
        assert by_name["b"] in g.children(by_name["a"])
        assert by_name["c"] in g.children(by_name["b"])
        assert by_name["d"] in g.children(by_name["c"])

    def test_host_gap_inference(self):
        evs = [
            TraceEvent("h1", "host", ts=0.0, dur=1e-3, eid=0),
            TraceEvent("h2", "host", ts=5e-3, dur=1e-3, eid=1),
            TraceEvent("k1", "device", ts=0.0, dur=1e-3, eid=2),
            TraceEvent("k2", "device", ts=5e-3, dur=1e-3, eid=3),
        ]
        g = graph_from_events(WorkerTrace(0, evs))
        by_name = {t.name: t for t in g.tasks()}
        assert by_name["h1"].gap == pytest.approx(4e-3)   # host: inferred
        assert by_name["k1"].gap == 0.0                   # device: not
        # explicit gap wins over inference
        evs[0].gap = 1e-3
        g2 = graph_from_events(WorkerTrace(0, evs))
        assert {t.name: t for t in g2.tasks()}["h1"].gap == 1e-3

    def test_kind_and_collective_inference(self):
        ev = TraceEvent("ncclAllReduce_f32", "comm", ts=0.0, dur=1e-3)
        t = ev.to_task()
        assert t.kind == TaskKind.COLLECTIVE
        assert t.attrs["collective"] == "all-reduce"
        assert traceio.infer_collective("fusion.123") is None
        assert traceio.classify("matmul", "device") == TaskKind.COMPUTE
        assert traceio.classify("enqueue", "host") == TaskKind.HOST

    def test_bad_dep_id_raises(self):
        evs = [TraceEvent("a", "device", ts=0.0, dur=1e-3, eid=0, deps=[7])]
        with pytest.raises(TraceImportError, match="unknown event id"):
            graph_from_events(WorkerTrace(0, evs))

    def test_cyclic_flow_raises(self):
        evs = [
            TraceEvent("a", "device", ts=0.0, dur=1e-3, eid=0, deps=[1]),
            TraceEvent("b", "ici:x", ts=0.5e-3, dur=1e-3, eid=1, deps=[0]),
        ]
        with pytest.raises(TraceImportError, match="DAG"):
            graph_from_events(WorkerTrace(0, evs))

    def test_missing_required_field_raises(self, tmp_path):
        p = tmp_path / "worker0.jsonl"
        p.write_text('{"name": "a", "thread": "device", "ts": 0.0}\n')
        with pytest.raises(TraceImportError, match="dur"):
            load_trace_dir(str(tmp_path))

    def test_empty_dir_raises(self, tmp_path):
        with pytest.raises(TraceImportError, match="no .*worker files"):
            load_trace_dir(str(tmp_path))
        with pytest.raises(TraceImportError, match="does not exist"):
            load_trace_dir(str(tmp_path / "nope"))

    def test_mismatched_collectives_raise(self, tmp_path):
        traces = synthetic_cluster_traces(2)
        # drop one collective from worker 1 -> matching must fail loudly
        drop = next(e for e in traces[1].events if e.name == "allreduce:l0")
        traces[1].events = [e for e in traces[1].events if e is not drop]
        for e in traces[1].events:
            e.deps = [dd for dd in e.deps if dd != drop.eid]
        d = write_traces(tmp_path, traces)
        with pytest.raises(GraphError, match="missing collective"):
            ClusterGraph.from_traces(d)

    def test_worker_file_ordering(self, tmp_path):
        for name, worker in [("worker10.jsonl", 10), ("worker2.jsonl", 2),
                             ("worker0.jsonl", 0)]:
            write_jsonl([TraceEvent("a", "device", ts=0.0, dur=1e-3,
                                    eid=0)], str(tmp_path / name))
        files = traceio.find_worker_files(str(tmp_path))
        assert [os.path.basename(f) for f in files] == \
            ["worker0.jsonl", "worker2.jsonl", "worker10.jsonl"]

    def test_chrome_flow_timestamp_binding(self, tmp_path):
        """Foreign Chrome traces (no args.bind extension) bind flows by
        timestamp: s -> enclosing slice, f -> next slice."""
        trace = {"traceEvents": [
            {"ph": "X", "name": "producer", "pid": 0, "tid": 1,
             "ts": 0.0, "dur": 100.0},
            {"ph": "X", "name": "consumer", "pid": 0, "tid": 2,
             "ts": 200.0, "dur": 50.0},
            {"ph": "s", "cat": "dep", "name": "dep", "id": 1, "pid": 0,
             "tid": 1, "ts": 50.0},
            {"ph": "f", "cat": "dep", "name": "dep", "id": 1, "pid": 0,
             "tid": 2, "ts": 200.0},
        ]}
        p = tmp_path / "worker0.json"
        p.write_text(json.dumps(trace))
        tr = traceio.read_chrome(str(p))
        consumer = next(e for e in tr.events if e.name == "consumer")
        producer = next(e for e in tr.events if e.name == "producer")
        assert consumer.deps == [producer.eid]

    def test_chrome_correlation_binding(self, tmp_path):
        trace = {"traceEvents": [
            {"ph": "X", "name": "launch", "pid": 0, "tid": 1, "ts": 0.0,
             "dur": 10.0, "args": {"correlation": 42}},
            {"ph": "X", "name": "kernel", "pid": 0, "tid": 2, "ts": 30.0,
             "dur": 99.0, "args": {"correlation": 42}},
        ]}
        p = tmp_path / "worker0.json"
        p.write_text(json.dumps(trace))
        tr = traceio.read_chrome(str(p))
        kernel = next(e for e in tr.events if e.name == "kernel")
        launch = next(e for e in tr.events if e.name == "launch")
        assert kernel.deps == [launch.eid]
        assert kernel.ts == pytest.approx(30e-6)   # us -> s


# ======================================================== scenario + sweeps
class TestTraceScenario:
    def test_scenario_trace_route_runs_registry_stack(self, tmp_path):
        """Acceptance: the PR-2 registry runs end-to-end on imported
        traces — amp|bandwidth composes and speeds up the cluster."""
        from repro.core import Scenario
        write_synthetic_trace_dir(str(tmp_path), 4)
        scn = Scenario(trace_dir=str(tmp_path))
        assert scn.is_cluster
        pred = scn.predict("amp,bandwidth:factor=2")
        assert pred.cluster is not None
        assert len(pred.cluster.per_worker) == 4
        assert pred.speedup > 1.5
        base = scn.predict("noop")
        assert base.predicted == pytest.approx(base.baseline, rel=1e-12)

    def test_scenario_sweep_reuses_trace_cluster(self, tmp_path):
        """Worker-spec sweeps on the trace route retune one imported
        build; predictions match per-point rebuilds exactly."""
        from repro.core import Scenario
        from repro.core.optimize import straggler_specs
        write_synthetic_trace_dir(str(tmp_path), 4)
        scn = Scenario(trace_dir=str(tmp_path))
        grid = {"workers": straggler_specs(4, [1.0, 1.5, 2.0])}
        reused = scn.sweep("noop", grid, reuse=True)
        rebuilt = scn.sweep("noop", grid, reuse=False)
        assert [p.predicted for p in reused] == \
            [p.predicted for p in rebuilt]
        assert reused[0].predicted < reused[-1].predicted

    def test_scenario_worker_count_mismatch_raises(self, tmp_path):
        from repro.core import Scenario
        from repro.core.optimize import OptimizationError
        write_synthetic_trace_dir(str(tmp_path), 4)
        with pytest.raises(OptimizationError, match="4 trace worker"):
            Scenario(trace_dir=str(tmp_path), workers=8)
        with pytest.raises(OptimizationError, match="4 trace worker"):
            Scenario(trace_dir=str(tmp_path), workers=[WorkerSpec()] * 3)


# ========================================================== build invariants
class TestClusterBuildGuards:
    def test_hierarchical_rejects_unequal_pods(self, ddp_graph):
        """Satellite: unequal pod sizes would silently mis-group the
        cross-pod shard exchange; build must reject them loudly."""
        bad = [WorkerSpec(pod=0), WorkerSpec(pod=0), WorkerSpec(pod=0),
               WorkerSpec(pod=1)]
        with pytest.raises(GraphError, match="equal-size pods"):
            ClusterGraph.build(ddp_graph, bad,
                               collective_mode="hierarchical")
        with pytest.raises(GraphError, match="equal-size pods"):
            ClusterGraph.from_worker_graphs([ddp_graph] * 4, bad,
                                            collective_mode="hierarchical")
        # equal pods still fine (and ring mode never cares)
        ClusterGraph.build(ddp_graph, [WorkerSpec(pod=i // 2)
                                       for i in range(4)],
                           collective_mode="hierarchical")
        ClusterGraph.build(ddp_graph, bad, collective_mode="ring")

    def test_from_worker_graphs_spec_count_mismatch(self, ddp_graph):
        with pytest.raises(GraphError, match="pair up 1:1"):
            ClusterGraph.from_worker_graphs([ddp_graph] * 2,
                                            [WorkerSpec()] * 3)

    def test_match_collective_groups_on_identical_graphs(self, ddp_graph):
        groups = match_collective_groups([ddp_graph, ddp_graph])
        n_coll = sum(1 for t in ddp_graph.tasks()
                     if t.attrs.get("collective"))
        assert len(groups) == n_coll
        for op, members in groups:
            assert op == "all-reduce"
            assert members[0].name == members[1].name


def test_hop_latency_calibration_plumbing():
    """Satellite: measured hop latency flows CostModel -> CollectiveModel ->
    ring legs, the way compute calibration already flows into durations."""
    from repro.core.calibrate import (hop_latency_from_measurement,
                                      measure_collective_hop_latency)
    from repro.core.costmodel import CollectiveModel
    # formula: solve the ring model for hop
    n, bw, payload = 4, 8e9, 4096.0
    hop = 3e-6
    t = 2 * (n - 1) / n * payload / bw + 2 * (n - 1) * hop
    assert hop_latency_from_measurement(t, payload, n, bw) == \
        pytest.approx(hop, rel=1e-9)
    # degenerate inputs fall back to the analytical default
    assert hop_latency_from_measurement(t, payload, 1, bw) == \
        CollectiveModel.HOP_LATENCY
    assert measure_collective_hop_latency(1) == CollectiveModel.HOP_LATENCY
    # plumbing: CostModel(hop_latency=...) reaches ring legs
    cost = CostModel(hop_latency=hop)
    assert cost.collectives.hop_latency == hop
    base = CostModel()
    assert base.collectives.hop_latency == CollectiveModel.HOP_LATENCY
    g = training_step_graph(layers=2)
    tf = whatif.what_if_distributed(g, {"l0": 1e6, "l1": 1e6}, 4,
                                    cost=cost)
    cg = ClusterGraph.build(tf.graph, 4, cost=cost)
    legs = [t for t in cg.graph.tasks() if "ring_round" in t.attrs]
    assert legs
    hw = cost.hw
    # both layers land in one 2 MB bucket; leg = (payload/n)/link_bw + hop
    expected = (2e6 / 4) / (hw.ici_bandwidth * hw.ici_links_per_axis) + hop
    assert min(t.duration for t in legs) == pytest.approx(expected,
                                                          rel=1e-12)


# ===================================================== degenerate clock fits
class TestAlignmentGuards:
    """Satellite: _fit on noisy/degenerate anchors can produce a
    non-positive or wildly-off scale; apply_alignment would then negate
    every duration.  The fit must fall back to offset-only instead."""

    @staticmethod
    def _trace(worker, ends):
        evs = [TraceEvent(name, "ici:grad", ts=end - 1e-3, dur=1e-3,
                          eid=i, collective="all-reduce")
               for i, (name, end) in enumerate(ends)]
        return WorkerTrace(worker, evs)

    def test_negative_slope_anchors_fall_back_to_offset(self):
        # anchor pairs with anti-correlated times: least squares gives a
        # negative scale, which must be rejected
        t0 = self._trace(0, [("allreduce:a", 0.2), ("allreduce:b", 0.1)])
        t1 = self._trace(1, [("allreduce:a", 0.1), ("allreduce:b", 0.2)])
        aligns = align_traces([t0, t1])
        al = aligns[1]
        assert al.fallback
        assert al.scale == 1.0
        assert al.anchors == 2
        apply_alignment(t1, al)
        assert all(ev.dur > 0 for ev in t1.events)

    def test_wildly_off_scale_falls_back(self):
        # nearly-coincident local anchors against well-spread reference
        # ones: the regression slope explodes past any physical drift
        t0 = self._trace(0, [("allreduce:a", 0.1), ("allreduce:b", 0.9)])
        t1 = self._trace(1, [("allreduce:a", 0.5), ("allreduce:b", 0.502)])
        aligns = align_traces([t0, t1])
        assert aligns[1].fallback
        assert aligns[1].scale == 1.0
        # offset-only map still centers the anchors
        assert aligns[1].offset == pytest.approx(0.5 - 0.501, abs=1e-9)

    def test_physical_drift_is_not_rejected(self):
        traces = synthetic_cluster_traces(
            2, clock_offsets=[0.0, 0.1], clock_drifts=[1.0, 1.0005])
        aligns = align_traces(traces)
        assert not aligns[1].fallback
        assert aligns[1].scale == pytest.approx(1.0 / 1.0005, rel=1e-9)

    def test_degenerate_durations_never_go_negative(self, tmp_path):
        """End to end: an adversarial capture imports with positive
        durations everywhere (the graph would reject negatives)."""
        t0 = self._trace(0, [("allreduce:a", 0.2), ("allreduce:b", 0.1)])
        t1 = self._trace(1, [("allreduce:a", 0.1), ("allreduce:b", 0.2)])
        d = write_traces(tmp_path, [t0, t1])
        imp = load_trace_dir(d)
        for tr in imp.traces:
            assert all(ev.dur > 0 for ev in tr.events)


# ==================================================== unanchored multi-worker
class TestAlignmentQualityChecks:
    """Satellite: multi-worker captures whose traces share zero matched
    collectives must not silently proceed with identity alignment."""

    @staticmethod
    def _disjoint_dir(tmp_path):
        # two workers with no common collective names -> zero anchors
        t0 = WorkerTrace(0, [
            TraceEvent("allreduce:x", "ici:grad", ts=0.0, dur=1e-3, eid=0,
                       collective="all-reduce"),
            TraceEvent("k", "device", ts=0.0, dur=1e-3, eid=1)])
        t1 = WorkerTrace(1, [
            TraceEvent("allreduce:y", "ici:grad", ts=0.0, dur=1e-3, eid=0,
                       collective="all-reduce"),
            TraceEvent("k", "device", ts=0.0, dur=1e-3, eid=1)])
        return write_traces(tmp_path, [t0, t1])

    def test_zero_anchor_import_warns_by_default(self, tmp_path):
        d = self._disjoint_dir(tmp_path)
        with pytest.warns(UserWarning,
                          match="share no matched collectives"):
            imp = load_trace_dir(d)
        assert imp.num_workers == 2            # still usable, just flagged

    def test_strict_alignment_raises(self, tmp_path):
        d = self._disjoint_dir(tmp_path)
        with pytest.raises(TraceImportError, match="unreliable"):
            load_trace_dir(d, align="strict")

    def test_strict_rejects_fallback_fits(self, tmp_path):
        t0 = TestAlignmentGuards._trace(
            0, [("allreduce:a", 0.2), ("allreduce:b", 0.1)])
        t1 = TestAlignmentGuards._trace(
            1, [("allreduce:a", 0.1), ("allreduce:b", 0.2)])
        d = write_traces(tmp_path, [t0, t1])
        with pytest.raises(TraceImportError, match="degenerate drift"):
            load_trace_dir(d, align="strict")

    def test_align_false_stays_silent(self, tmp_path, recwarn):
        d = self._disjoint_dir(tmp_path)
        load_trace_dir(d, align=False)
        assert not [w for w in recwarn
                    if "collectives" in str(w.message)]

    def test_anchored_import_does_not_warn(self, tmp_path, recwarn):
        d = write_traces(tmp_path, synthetic_cluster_traces(2))
        load_trace_dir(d, align="strict")      # anchors exist: no raise
        assert not [w for w in recwarn
                    if "collectives" in str(w.message)]

    def test_bad_align_value_rejected(self, tmp_path):
        d = write_traces(tmp_path, synthetic_cluster_traces(2))
        with pytest.raises(ValueError, match="align must be"):
            load_trace_dir(d, align="loose")


# ============================================================ XLA profiler
class TestXlaImport:
    """jax.profiler / XLA capture reader (repro.traceio.xla) on
    handcrafted captures — the real-capture fixture lives in
    test_calibrate.py."""

    @staticmethod
    def _write_capture(path, events, gz=True):
        import gzip as _gzip
        doc = {"displayTimeUnit": "ns", "metadata": {},
               "traceEvents": events}
        if gz:
            with _gzip.open(path, "wt") as f:
                json.dump(doc, f)
        else:
            with open(path, "w") as f:
                json.dump(doc, f)

    @classmethod
    def _profile_dir(cls, tmp_path, events):
        run = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
        os.makedirs(str(run))
        cls._write_capture(str(run / "host.trace.json.gz"), events)
        return str(tmp_path)

    @staticmethod
    def _meta(pid, tid, pname, tname):
        return [{"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                 "args": {"name": pname}},
                {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
                 "args": {"name": tname}}]

    def _step_capture(self):
        evs = self._meta(7, 1, "/host:CPU", "tf_XLAPjRtCpuClient/1")
        evs += self._meta(7, 2, "/host:CPU", "python")[1:]
        for step, base in ((0, 1000.0), (1, 2000.0)):
            evs.append({"ph": "X", "name": "train", "pid": 7, "tid": 2,
                        "ts": base, "dur": 500.0,
                        "args": {"step_num": str(step)}})
            # nested python flame: outer frame contains two leaves
            evs.append({"ph": "X", "name": "$m outer", "pid": 7, "tid": 2,
                        "ts": base + 10, "dur": 100.0, "args": {}})
            evs.append({"ph": "X", "name": "$m leaf1", "pid": 7, "tid": 2,
                        "ts": base + 20, "dur": 30.0, "args": {}})
            evs.append({"ph": "X", "name": "$m leaf2", "pid": 7, "tid": 2,
                        "ts": base + 60, "dur": 40.0, "args": {}})
            evs.append({"ph": "X", "name": "dot.1", "pid": 7, "tid": 1,
                        "ts": base + 120, "dur": 200.0,
                        "args": {"hlo_op": "dot.1",
                                 "hlo_module": "jit_f"}})
            evs.append({"ph": "X", "name": "all-reduce.2", "pid": 7,
                        "tid": 1, "ts": base + 330, "dur": 50.0,
                        "args": {"hlo_op": "all-reduce.2",
                                 "hlo_module": "jit_f"}})
        return evs

    def test_step_slicing_keeps_last_step_only(self, tmp_path):
        d = self._profile_dir(tmp_path, self._step_capture())
        imp = traceio.load_xla_profile(d)          # step="last"
        names = [e.name for e in imp.traces[0].events]
        assert "dot.1" in names and "all-reduce.2" in names
        assert names.count("dot.1") == 1           # one step, not two
        assert "train" not in names                # marker itself excluded
        # leaf extraction: the container frame is gone, leaves survive
        assert "$m outer" not in names
        assert "$m leaf1" in names and "$m leaf2" in names

    def test_explicit_and_all_step_selection(self, tmp_path):
        d = self._profile_dir(tmp_path, self._step_capture())
        imp0 = traceio.load_xla_profile(d, step=0)
        assert [e.name for e in imp0.traces[0].events].count("dot.1") == 1
        imp_all = traceio.load_xla_profile(d, step=None)
        assert [e.name
                for e in imp_all.traces[0].events].count("dot.1") == 2
        with pytest.raises(TraceImportError, match="not in capture"):
            traceio.load_xla_profile(d, step=9)

    def test_lanes_kinds_and_units(self, tmp_path):
        d = self._profile_dir(tmp_path, self._step_capture())
        imp = traceio.load_xla_profile(d)
        by_name = {}
        for ev in imp.traces[0].events:
            by_name[ev.name] = ev
        assert by_name["dot.1"].thread == "device"
        assert by_name["$m leaf1"].thread == "host"
        assert by_name["dot.1"].dur == pytest.approx(200e-6)  # us -> s
        g = imp.graphs[0]
        kinds = {t.name: t.kind for t in g.tasks()}
        assert kinds["dot.1"] == TaskKind.COMPUTE
        assert kinds["all-reduce.2"] == TaskKind.COLLECTIVE
        assert kinds["$m leaf1"] == TaskKind.HOST

    def test_load_trace_dir_detects_xla_profiles(self, tmp_path):
        d = self._profile_dir(tmp_path, self._step_capture())
        imp = load_trace_dir(d)                    # auto-detected
        assert imp.num_workers == 1
        assert any(e.thread == "device" for e in imp.traces[0].events)

    def test_latest_run_wins_and_file_paths_accepted(self, tmp_path):
        d = self._profile_dir(tmp_path, self._step_capture())
        older = tmp_path / "plugins" / "profile" / "2020_01_01_00_00_00"
        os.makedirs(str(older))
        self._write_capture(str(older / "host.trace.json.gz"),
                            self._meta(1, 1, "/host:CPU", "python"))
        files = traceio.find_xla_trace_files(str(tmp_path))
        assert len(files) == 1 and "2026_01_01" in files[0]
        # a single trace file is also a valid entry point
        assert traceio.find_xla_trace_files(files[0]) == [files[0]]

    def test_native_chrome_exports_are_not_claimed(self, tmp_path):
        """Regression: a directory of native ``worker<N>.trace.json``
        exports must NOT be detected as an XLA capture — that would
        bypass the provenance-aware importer."""
        g = whatif.what_if_distributed(
            training_step_graph(layers=2),
            {f"l{i}": 1e6 for i in range(2)}, num_workers=2).graph
        cg = ClusterGraph.build(g, 2, cost=CostModel())
        res = cg.simulate()
        traceio.export_cluster_traces(cg, res, str(tmp_path))
        assert traceio.find_xla_trace_files(str(tmp_path)) == []
        imp = load_trace_dir(str(tmp_path))
        assert imp.num_workers == 2

    def test_capture_without_steps_keeps_everything(self, tmp_path):
        evs = self._meta(7, 1, "/host:CPU", "tf_XLAPjRtCpuClient/1")
        evs.append({"ph": "X", "name": "dot.9", "pid": 7, "tid": 1,
                    "ts": 100.0, "dur": 10.0, "args": {"hlo_op": "dot.9"}})
        d = self._profile_dir(tmp_path, evs)
        imp = traceio.load_xla_profile(d)
        assert [e.name for e in imp.traces[0].events] == ["dot.9"]

    def test_empty_or_malformed_captures_raise(self, tmp_path):
        d = self._profile_dir(tmp_path, self._meta(1, 1, "/host:CPU",
                                                   "python"))
        with pytest.raises(TraceImportError, match="no complete"):
            traceio.load_xla_profile(d)
        with pytest.raises(TraceImportError, match="no XLA profile"):
            traceio.load_xla_profile(str(tmp_path / "nope"))


class TestRecordedTpuCapture:
    """An excerpt of a real one-chip capture (TPU v5e, ``tinyllama-1.1b``
    training steps 3-4 of ``chip_smoke.py``): the first few milliseconds of
    each step's ``XLA Ops`` line, the full ``Steps`` and ``XLA Modules``
    lines, and the host threads around the step markers."""

    PATH = os.path.join(os.path.dirname(__file__), "golden",
                        "tpu_v5e_train_step.trace.json.gz")

    @staticmethod
    def _raw_lines(path):
        import gzip
        with gzip.open(path, "rt") as f:
            doc = json.load(f)
        names = {(e["pid"], e["tid"]): e["args"]["name"]
                 for e in doc["traceEvents"]
                 if e.get("ph") == "M" and e["name"] == "thread_name"}
        lines = {}
        for e in doc["traceEvents"]:
            if e.get("ph") == "X":
                lines.setdefault(names.get((e["pid"], e["tid"])),
                                 []).append(e)
        return lines

    def test_one_worker_one_device_lane(self):
        imp = traceio.load_xla_profile(self.PATH)
        assert imp.num_workers == 1               # /host:CPU is no worker
        lanes = {e.thread for e in imp.traces[0].events}
        assert "device" in lanes
        assert all(lane == "device" or lane.startswith("host")
                   for lane in lanes)
        dev = [e for e in imp.traces[0].events if e.thread == "device"]
        assert all(e.attrs["xla_thread"] == "XLA Ops" for e in dev)
        kinds = {t.kind for t in imp.graphs[0].tasks()
                 if t.thread == "device"}
        assert kinds == {TaskKind.COMPUTE}
        # host idle beside the device is waiting, not inferred host work
        assert all(t.gap == 0.0 for t in imp.graphs[0].tasks()
                   if t.thread.startswith("host"))

    def test_device_time_is_counted_once(self):
        imp = traceio.load_xla_profile(self.PATH)
        dev = sorted((e for e in imp.traces[0].events
                      if e.thread == "device"), key=lambda e: e.ts)
        for a, b in zip(dev, dev[1:]):
            assert b.ts >= a.end - 1e-12          # no overlapping slices
        busy = sum(e.dur for e in dev)
        assert busy <= dev[-1].end - dev[0].ts
        # the module slice spans the whole step; only its ops are counted
        module = max(e["dur"] for e in self._raw_lines(self.PATH)
                     ["XLA Modules"]) / 1e6
        assert busy < 0.1 * module

    def test_steps_sliced_on_the_device_clock(self):
        """The device starts a step before the host's step marker opens on
        the host clock; slicing by the device's own Steps line keeps the
        step's first op."""
        lines = self._raw_lines(self.PATH)
        for step, nth in ((3, 0), (4, 1)):
            imp = traceio.load_xla_profile(self.PATH, step=step)
            dev = [e for e in imp.traces[0].events if e.thread == "device"]
            marker = next(e for e in lines["python"]
                          if e["args"].get("step_num") == str(step))
            module = sorted(lines["XLA Modules"], key=lambda e: e["ts"])[nth]
            assert min(e.ts for e in dev) * 1e6 < marker["ts"]
            assert min(e.ts for e in dev) * 1e6 == pytest.approx(
                module["ts"], abs=1.0)

    def test_ops_carry_layer_phase_and_cost(self):
        imp = traceio.load_xla_profile(self.PATH)
        tasks = [t for t in imp.graphs[0].tasks() if t.thread == "device"]
        assert any(t.phase == "fwd" and t.layer for t in tasks)
        assert any(t.flops > 0 for t in tasks)
        assert all(t.bytes_accessed >= 0 for t in tasks)

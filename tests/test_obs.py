"""Counter-track telemetry (repro.obs) acceptance tests.

Covers the ISSUE's observability contracts:

* :class:`Timeline` math — delta construction, time-weighted rollups,
  Perfetto-shaped samples;
* the busy-interval helpers in ``core.simulate`` ARE ``obs.timeline``'s
  (single implementation, no drift);
* the acceptance golden: the live-memory timeline's peak equals the
  analytic sum over the live set at the peak instant to float precision
  on a DDP-transformed step graph;
* ``Prediction.timelines`` / ``ServingPrediction.timelines`` wiring
  (byte maps threaded, stale-retune guard raises instead of lying);
* counter round-trip: counter-carrying Chrome / XProf exports re-import
  byte-identically to counter-free ones;
* self-instrumentation spans: nested JSONL emission, error tagging,
  free disabled path, and the hot-path wiring (build/retune/sweep/import);
  records buffered and written in bulk (at a fixed count, at ``configure``,
  as ``Trainer.fit`` returns), JAX's compile listeners registered and
  removed with it;
* ``Trainer.fit``'s phase spans in a CPU ``jax.profiler`` capture, and the
  compile records that name the step that compiled.
"""

import glob
import gzip
import json
import os

import pytest

from repro.core import (ClusterGraph, DependencyGraph, OptimizationError,
                        Scenario, Task, TaskKind, WorkerSpec, simulate,
                        whatif, DEVICE_STREAM)
# repro.core re-exports the simulate() function under the submodule's
# name, so fetch the module itself for the identity checks
import importlib
simulate_mod = importlib.import_module("repro.core.simulate")
from repro.obs import (Timeline, TimelineSet, compute_timelines,
                       check_result_fresh, format_timeline_report,
                       interval_overlap, interval_union, lane_utilization,
                       span)
from repro.obs import spans as spans_mod
from repro.obs import timeline as timeline_mod
from repro import traceio
from repro.traceio import (counter_track_events, export_cluster_traces,
                           export_graph_trace, read_chrome)
from repro.traceio.xla import read_xla_trace
from synthgraphs import training_step_graph

LAYERS = 6
GRADS = {f"l{i}": 30e6 for i in range(LAYERS)}
ACTS = {f"l{i}": 50e6 for i in range(LAYERS)}


# ============================================================ Timeline math
class TestTimeline:
    def test_from_deltas_merges_and_drops_zero_net(self):
        tl = Timeline.from_deltas(
            [(1.0, 2.0), (1.0, 3.0), (2.0, 1.0), (2.0, -1.0), (4.0, -5.0)],
            end=10.0)
        assert tl.times == (1.0, 4.0)          # t=2 net-zero point dropped
        assert tl.values == (5.0, 0.0)
        assert tl.end == 10.0

    def test_value_at_and_segments_cover_horizon(self):
        tl = Timeline.from_deltas([(1.0, 2.0), (3.0, -2.0)], end=5.0)
        assert tl.value_at(0.5) == 0.0          # before first change
        assert tl.value_at(1.0) == 2.0          # inclusive at change point
        assert tl.value_at(2.9) == 2.0
        assert tl.value_at(3.0) == 0.0
        segs = list(tl.segments())
        assert segs == [(0.0, 1.0, 0.0), (1.0, 3.0, 2.0), (3.0, 5.0, 0.0)]
        assert segs[0][0] == 0.0 and segs[-1][1] == tl.end   # gapless

    def test_peak_and_peak_time(self):
        tl = Timeline.from_deltas(
            [(1.0, 2.0), (2.0, 3.0), (3.0, -3.0), (4.0, -2.0)], end=6.0)
        assert tl.peak == 5.0
        assert tl.peak_time == 2.0
        # a series that starts below zero still reports peak >= 0 (the
        # implicit zero before the first change point counts)
        neg = Timeline.from_deltas([(1.0, -4.0), (2.0, 4.0)], end=3.0)
        assert neg.peak == 0.0

    def test_time_weighted_rollups(self):
        # 2.0 for 2s, 0 for the other 3s of a 5s horizon
        tl = Timeline.from_deltas([(1.0, 2.0), (3.0, -2.0)], end=5.0)
        assert tl.integral() == pytest.approx(4.0)
        assert tl.mean() == pytest.approx(0.8)
        # value <= 0 holds for 3/5 of the horizon -> p60 is 0, p61 is 2
        assert tl.percentile(0.60) == 0.0
        assert tl.percentile(0.61) == 2.0
        assert tl.percentile(1.0) == 2.0
        with pytest.raises(ValueError, match="percentile"):
            tl.percentile(1.5)

    def test_empty_timeline_rollups(self):
        tl = Timeline((), (), 4.0)
        assert tl.peak == 0.0 and tl.mean() == 0.0
        assert tl.value_at(2.0) == 0.0
        assert list(tl.segments()) == [(0.0, 4.0, 0.0)]
        assert tl.samples() == [(0.0, 0.0), (4.0, 0.0)]

    def test_samples_open_and_close_the_track(self):
        tl = Timeline.from_deltas([(1.0, 2.0), (3.0, -2.0)], end=5.0)
        s = tl.samples()
        assert s[0] == (0.0, 0.0)               # leading zero sample
        assert s[-1] == (5.0, 0.0)              # closing sample at end
        assert (1.0, 2.0) in s and (3.0, 0.0) in s


# ==================================================== single implementation
class TestHelperIdentity:
    def test_simulate_reexports_obs_helpers(self):
        """core.simulate's interval/utilization helpers must BE the obs
        ones — the dedup satellite, not a parallel re-implementation."""
        assert simulate_mod.lane_utilization is timeline_mod.lane_utilization
        assert simulate_mod._interval_union is timeline_mod.interval_union
        assert simulate_mod._overlap is timeline_mod.interval_overlap

    def test_interval_helpers(self):
        assert interval_union([(3, 4), (0, 1), (1, 2)]) == [(0, 2), (3, 4)]
        assert interval_overlap([(0, 2), (3, 4)], [(1, 5)]) == \
            pytest.approx(2.0)

    def test_lane_utilization_agrees_with_busy_timelines(self):
        g = training_step_graph()
        res = simulate(g)
        ts = compute_timelines(g, res)
        direct = lane_utilization(res)
        derived = ts.lane_utilization()
        assert set(direct) == set(derived)
        for th in direct:
            assert derived[th] == pytest.approx(direct[th], rel=1e-12)


# ========================================================= compute_timelines
class TestComputeTimelines:
    def test_utilization_bounded_and_scaled_by_lanes(self):
        g = training_step_graph()
        ts = compute_timelines(g, simulate(g))
        util = ts.utilization[0]
        assert all(0.0 <= v <= 1.0 + 1e-12 for v in util.values)
        assert ts.lanes_per_worker[0] >= 2      # device + host lanes

    def test_queue_depth_counts_ready_but_undispatched(self):
        # two free-floating unit tasks on ONE lane: both ready at t=0, the
        # second waits a full second for the lane -> depth 1 on [0, 1)
        g = DependencyGraph()
        g.add_task(Task("a", TaskKind.COMPUTE, DEVICE_STREAM, 1.0),
                   link_lane=False)
        g.add_task(Task("b", TaskKind.COMPUTE, DEVICE_STREAM, 1.0),
                   link_lane=False)
        ts = compute_timelines(g, simulate(g))
        q = ts.queue_depth[0]
        assert q.peak == 1.0
        assert q.value_at(0.5) == 1.0
        assert q.value_at(1.5) == 0.0
        assert q.integral() == pytest.approx(1.0)

    def test_zero_duration_barriers_never_queue(self):
        g = DependencyGraph()
        a = g.add_task(Task("a", TaskKind.COMPUTE, DEVICE_STREAM, 1.0))
        b = g.add_task(Task("barrier", TaskKind.SYNC, DEVICE_STREAM, 0.0))
        g.add_edge(a, b)
        ts = compute_timelines(g, simulate(g))
        assert 0 not in ts.queue_depth or ts.queue_depth[0].peak == 0.0

    def test_comm_bytes_in_flight(self):
        tf = whatif.what_if_distributed(training_step_graph(), GRADS,
                                        num_workers=4)
        ts = compute_timelines(tf.graph, tf.simulate())
        comm = ts.comm_bytes[0]
        assert comm.peak > 0.0
        assert comm.peak <= sum(GRADS.values()) + 1e-6

    def test_stale_result_raises(self):
        g = training_step_graph()
        res = simulate(g)
        next(iter(g.tasks())).duration *= 2.0   # retune after simulating
        with pytest.raises(ValueError, match="stale"):
            check_result_fresh(g, res)
        with pytest.raises(ValueError, match="stale"):
            compute_timelines(g, res)

    def test_report_renders(self):
        scn = Scenario(graph=training_step_graph(), layer_grad_bytes=GRADS,
                       activation_bytes=ACTS,
                       workers=[WorkerSpec()] * 4)
        text = format_timeline_report(scn.predict("ddp").timelines)
        assert "== timelines:" in text
        assert "w0" in text and "w3" in text
        assert "MiB" in text and "busiest lanes:" in text


# ==================================================== memory-timeline golden
def _brute_force_live_bytes(graph, res, t_star):
    """Analytic live bytes per worker at instant ``t_star``, straight from
    the documented alloc/free semantics — independent of the delta-merge
    path compute_timelines takes."""
    from repro.core.task import split_worker_thread
    comm_kinds = (TaskKind.COLLECTIVE, TaskKind.COMM)
    spans = {}          # (w, layer) -> [last_fwd, last_bwd, last_consumer]
    for t in graph.tasks():
        if not t.layer:
            continue
        w, _ = split_worker_thread(t.thread)
        w = 0 if w is None else w
        slot = spans.setdefault((w, t.layer), [None, None, None])
        fin = res.finish[t.uid]
        if t.phase == "fwd" and (slot[0] is None or fin > slot[0]):
            slot[0] = fin
        if t.phase == "bwd" and (slot[1] is None or fin > slot[1]):
            slot[1] = fin
        if (t.phase == "update" or t.kind in comm_kinds) \
                and (slot[2] is None or fin > slot[2]):
            slot[2] = fin
    live = {}
    for (w, layer), (fwd, bwd, consume) in spans.items():
        if fwd is not None:
            free = bwd if (bwd is not None and bwd > fwd) else res.makespan
            if fwd <= t_star < free:
                live[w] = live.get(w, 0.0) + ACTS[layer]
        if bwd is not None:
            free = consume if (consume is not None and consume > bwd) \
                else res.makespan
            if bwd <= t_star < free:
                live[w] = live.get(w, 0.0) + GRADS[layer]
    return live


class TestMemoryGolden:
    """Acceptance: the memory timeline's peak equals the analytic sum over
    the live set at the peak instant to float precision."""

    @pytest.fixture(scope="class")
    def ddp_cluster(self):
        tf = whatif.what_if_distributed(training_step_graph(), GRADS,
                                        num_workers=4)
        cg = ClusterGraph.build(tf.graph, 4)
        return cg, cg.simulate()

    def test_peak_equals_analytic_live_set(self, ddp_cluster):
        cg, cres = ddp_cluster
        ts = compute_timelines(cg.graph, cres, activation_bytes=ACTS,
                               layer_grad_bytes=GRADS)
        assert ts.workers == [0, 1, 2, 3]
        for w in ts.workers:
            mem = ts.memory[w]
            assert mem.peak > 0.0
            live = _brute_force_live_bytes(cg.graph, cres.global_result,
                                           mem.peak_time)
            assert mem.peak == pytest.approx(live[w], rel=1e-12)
        assert ts.peak_memory() == max(ts.memory[w].peak
                                       for w in ts.workers)

    def test_value_at_matches_analytic_everywhere(self, ddp_cluster):
        cg, cres = ddp_cluster
        ts = compute_timelines(cg.graph, cres, activation_bytes=ACTS,
                               layer_grad_bytes=GRADS)
        mem = ts.memory[0]
        probes = [0.5 * (t0 + t1) for t0, t1, _ in mem.segments()
                  if t1 > t0]
        for t_star in probes:
            live = _brute_force_live_bytes(cg.graph, cres.global_result,
                                           t_star)
            assert mem.value_at(t_star) == \
                pytest.approx(live.get(0, 0.0), rel=1e-12, abs=1e-6)

    def test_all_memory_eventually_freed(self, ddp_cluster):
        cg, cres = ddp_cluster
        ts = compute_timelines(cg.graph, cres, activation_bytes=ACTS,
                               layer_grad_bytes=GRADS)
        for w in ts.workers:
            assert ts.memory[w].value_at(ts.makespan) == pytest.approx(0.0)

    def test_no_byte_maps_no_memory_series(self, ddp_cluster):
        cg, cres = ddp_cluster
        ts = compute_timelines(cg.graph, cres)
        assert ts.memory == {}
        assert ts.peak_memory() == 0.0


# ===================================================== Prediction.timelines
class TestPredictionTimelines:
    def _scenario(self, workers):
        return Scenario(graph=training_step_graph(),
                        layer_grad_bytes=GRADS, activation_bytes=ACTS,
                        workers=workers)

    def test_cluster_route_carries_byte_maps(self):
        pred = self._scenario([WorkerSpec()] * 4).predict("ddp")
        ts = pred.timelines
        assert isinstance(ts, TimelineSet)
        assert ts.workers == [0, 1, 2, 3]
        assert ts.peak_memory(0) > 0.0
        assert pred.timelines is ts             # cached

    def test_single_route_carries_byte_maps(self):
        pred = self._scenario(4).predict("ddp")
        assert pred.timelines.peak_memory(0) > 0.0

    def test_sweep_reuse_stale_guard(self):
        """Spec-only sweep points retune one shared build in place; an
        earlier point's .timelines must raise, not describe the wrong
        point."""
        scn = self._scenario([WorkerSpec()] * 4)
        grid = {"workers": [[WorkerSpec()] * 4,
                            [WorkerSpec(compute_scale=2.0)]
                            + [WorkerSpec()] * 3]}
        preds = scn.sweep("ddp", grid, reuse=True)
        assert preds[1].predicted > preds[0].predicted   # retune took hold
        assert preds[-1].timelines.makespan > 0  # last point is fresh
        with pytest.raises(OptimizationError, match="stale"):
            preds[0].timelines

    def test_serving_prediction_timelines(self):
        from repro.serving import (ServingCostModel, ServingPolicy,
                                   ServingScenario, explicit_workload)
        scn = ServingScenario(
            workload=explicit_workload([(0.0, 64, 8)] * 4),
            policy=ServingPolicy(mode="static", slots=4),
            serving_cost=ServingCostModel())
        ts = scn.predict("noop").timelines
        assert ts.makespan > 0.0
        assert ts.utilization[0].mean() > 0.0


# ======================================================= counter round-trip
class TestCounterRoundTrip:
    def test_chrome_counter_events_shape(self):
        g = training_step_graph()
        res = simulate(g)
        ts = compute_timelines(g, res, activation_bytes=ACTS,
                               layer_grad_bytes=GRADS)
        cevs = counter_track_events(ts)
        names = {e["name"] for e in cevs}
        assert names == {"utilization", "memory_bytes", "ready_queue"}
        assert all(e["ph"] == "C" and "value" in e["args"] for e in cevs)

    def test_single_file_export_reimports_identically(self, tmp_path):
        g = training_step_graph()
        res = simulate(g)
        p_ctr = str(tmp_path / "with.trace.json")
        p_off = str(tmp_path / "without.trace.json")
        export_graph_trace(g, res, p_ctr, activation_bytes=ACTS,
                           layer_grad_bytes=GRADS)
        export_graph_trace(g, res, p_off, counters=False)
        with open(p_ctr) as f:
            assert any(e.get("ph") == "C"
                       for e in json.load(f)["traceEvents"])
        tr_ctr, tr_off = read_chrome(p_ctr), read_chrome(p_off)
        assert tr_ctr.events == tr_off.events   # reader skips counters

    def test_cluster_export_reimports_identically(self, tmp_path):
        tf = whatif.what_if_distributed(training_step_graph(), GRADS,
                                        num_workers=4)
        cg = ClusterGraph.build(tf.graph, 4)
        cres = cg.simulate()
        d_ctr, d_off = str(tmp_path / "ctr"), str(tmp_path / "off")
        paths = export_cluster_traces(cg, cres, d_ctr,
                                      activation_bytes=ACTS,
                                      layer_grad_bytes=GRADS)
        export_cluster_traces(cg, cres, d_off, counters=False)
        # every worker file carries C events, per-worker pid, plain names
        for i, p in enumerate(paths):
            with open(p) as f:
                cevs = [e for e in json.load(f)["traceEvents"]
                        if e.get("ph") == "C"]
            assert cevs and all(e["pid"] == i for e in cevs)
            assert {e["name"] for e in cevs} >= {"utilization",
                                                 "memory_bytes",
                                                 "ready_queue"}
        imp_ctr = traceio.load_trace_dir(d_ctr, align=False)
        imp_off = traceio.load_trace_dir(d_off, align=False)
        for a, b in zip(imp_ctr.traces, imp_off.traces):
            assert a.events == b.events
        re_ctr = ClusterGraph.from_worker_graphs(imp_ctr.graphs).simulate()
        assert re_ctr.makespan == pytest.approx(cres.makespan, rel=1e-9)

    def test_xla_reader_skips_counters(self, tmp_path):
        def meta(pid, tid, pname, tname):
            return [{"ph": "M", "name": "process_name", "pid": pid,
                     "tid": 0, "args": {"name": pname}},
                    {"ph": "M", "name": "thread_name", "pid": pid,
                     "tid": tid, "args": {"name": tname}}]
        evs = meta(7, 1, "/host:CPU", "tf_XLAPjRtCpuClient/1")
        evs.append({"ph": "X", "name": "dot.1", "pid": 7, "tid": 1,
                    "ts": 100.0, "dur": 200.0,
                    "args": {"hlo_op": "dot.1", "hlo_module": "jit_f"}})
        counters = [{"ph": "C", "name": "utilization", "pid": 7, "tid": 0,
                     "ts": float(t), "args": {"value": v}}
                    for t, v in ((0.0, 0.0), (100.0, 1.0), (300.0, 0.0))]
        p_off = str(tmp_path / "plain.trace.json.gz")
        p_ctr = str(tmp_path / "ctr.trace.json.gz")
        for path, events in ((p_off, evs), (p_ctr, evs + counters)):
            with gzip.open(path, "wt") as f:
                json.dump({"displayTimeUnit": "ns", "metadata": {},
                           "traceEvents": events}, f)
        tr_off = read_xla_trace(p_off, step=None)
        tr_ctr = read_xla_trace(p_ctr, step=None)
        assert len(tr_ctr) == len(tr_off) == 1
        assert tr_ctr[0].events == tr_off[0].events


# ================================================= self-instrumentation spans
class TestSpans:
    @pytest.fixture(autouse=True)
    def _clean(self):
        spans_mod.configure(None)
        yield
        spans_mod.configure(None)

    def _read(self, path):
        with open(path) as f:
            return [json.loads(line) for line in f]

    def test_disabled_is_shared_noop(self):
        assert not spans_mod.enabled()
        s = span("anything", x=1)
        assert s is span("other")               # the shared singleton
        with s as inner:
            inner.note(ignored=True)            # all no-ops

    def test_nested_emission_and_attrs(self, tmp_path):
        path = str(tmp_path / "spans.jsonl")
        spans_mod.configure(path)
        assert spans_mod.enabled()
        assert spans_mod.telemetry_path() == path
        with span("outer", a=1) as s:
            s.note(b=2)
            with span("inner"):
                pass
        spans_mod.configure(None)
        recs = self._read(path)
        assert [r["span"] for r in recs] == ["outer.inner", "outer"]
        assert recs[1]["attrs"] == {"a": 1, "b": 2}
        assert all(r["dur_s"] >= 0.0 for r in recs)
        assert "error" not in recs[0] and "error" not in recs[1]

    def test_error_tagged(self, tmp_path):
        path = str(tmp_path / "spans.jsonl")
        spans_mod.configure(path)
        with pytest.raises(RuntimeError):
            with span("boom"):
                raise RuntimeError("x")
        spans_mod.configure(None)
        (rec,) = self._read(path)
        assert rec["error"] == "RuntimeError"

    def test_hot_paths_are_wired(self, tmp_path):
        """build/retune/sweep/import all emit spans when enabled."""
        path = str(tmp_path / "spans.jsonl")
        d = str(tmp_path / "traces")
        traceio.write_synthetic_trace_dir(d, 2)
        spans_mod.configure(path)
        try:
            imp = traceio.load_trace_dir(d)
            cg = ClusterGraph.from_worker_graphs(imp.graphs)
            cg.retune([WorkerSpec(compute_scale=2.0), WorkerSpec()])
            scn = Scenario(graph=training_step_graph(),
                           layer_grad_bytes=GRADS,
                           workers=[WorkerSpec()] * 2)
            scn.sweep("ddp", {"bucket_bytes": [1e6, 120e6]})
        finally:
            spans_mod.configure(None)
        names = {r["name"] for r in self._read(path)}
        assert {"traceio.load_trace_dir", "cluster.from_worker_graphs",
                "cluster.build", "cluster.retune",
                "scenario.sweep_point"} <= names

    def test_records_buffered_until_configure(self, tmp_path):
        first, second = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
        spans_mod.configure(first)
        with span("a"):
            pass
        assert not os.path.exists(first)        # nothing written yet
        spans_mod.configure(second)             # flushes to the old path
        with span("b"):
            pass
        (rec,) = self._read(first)
        assert rec["name"] == "a" and rec["t0"] > 0 and rec["dur_s"] >= 0
        assert not os.path.exists(second)
        spans_mod.configure(None)
        assert [r["name"] for r in self._read(second)] == ["b"]
        assert [r["name"] for r in self._read(first)] == ["a"]

    def test_written_in_bulk_at_fixed_count(self, tmp_path):
        """A long run's records reach the file before ``configure(None)``:
        memory stays bounded and a killed process keeps what was written."""
        path = str(tmp_path / "spans.jsonl")
        spans_mod.configure(path)
        for i in range(spans_mod._FLUSH_AT - 1):
            with span("s", i=i):
                pass
        assert not os.path.exists(path)
        with span("s", i=spans_mod._FLUSH_AT - 1):
            pass
        recs = self._read(path)                 # still enabled
        assert [r["attrs"]["i"] for r in recs] == \
            list(range(spans_mod._FLUSH_AT))
        assert spans_mod._records == []
        with span("tail"):
            pass
        spans_mod.configure(None)
        assert self._read(path)[-1]["name"] == "tail"
        assert len(self._read(path)) == spans_mod._FLUSH_AT + 1

    def test_compile_listeners_follow_configure(self, tmp_path):
        import jax
        from jax._src import monitoring
        spans_mod.configure(str(tmp_path / "spans.jsonl"))
        assert spans_mod._on_time_span in \
            monitoring.get_event_time_span_listeners()
        assert spans_mod._on_duration in \
            monitoring.get_event_duration_listeners()
        with span("outer", step=7):
            jax.jit(lambda x: x * 3 + 1)(jax.numpy.ones(5)).block_until_ready()
        spans_mod.configure(None)
        assert spans_mod._on_time_span not in \
            monitoring.get_event_time_span_listeners()
        assert spans_mod._on_duration not in \
            monitoring.get_event_duration_listeners()
        recs = self._read(str(tmp_path / "spans.jsonl"))
        jaxr = [r for r in recs if r["name"].startswith("jax.")]
        assert {"jax.trace", "jax.lower", "jax.compile"} <= \
            {r["name"] for r in jaxr}
        for r in jaxr:
            assert r["span"] == "outer." + r["name"]
            assert r["attrs"]["step"] == 7 and r["attrs"]["fun_name"]
        (outer,) = [r for r in recs if r["name"] == "outer"]
        # on one clock with the enclosing span
        assert all(outer["t0"] <= r["t0"] and r["t0"] + r["dur_s"]
                   <= outer["t0"] + outer["dur_s"] + 1e-3 for r in jaxr)


# ======================================================= Trainer.fit's spans
PHASES = ("train.next_batch", "train.to_device", "train.dispatch",
          "train.sync")


def _tiny_fit(tmp_path, seqs, capture=None):
    """A smoke-size ``Trainer.fit`` of ``len(seqs)`` steps, step i fed rows
    of length ``seqs[i]``, with telemetry on (and a profiler capture into
    ``capture``); returns the span records as written when ``fit``
    returned, and as written once telemetry was turned off."""
    import jax
    from repro.configs import get_smoke_config
    from repro.data import make_batch
    from repro.optim import AdamW
    from repro.train import Trainer, TrainerConfig
    cfg = get_smoke_config("tinyllama-1.1b")
    rows = (make_batch(cfg, seq_len=s, batch=2, step=i)
            for i, s in enumerate(seqs))
    path = str(tmp_path / "fit.jsonl")
    spans_mod.configure(path)
    try:
        trainer = Trainer(cfg, TrainerConfig(steps=len(seqs), log_every=0),
                          optimizer=AdamW(lr=1e-3))
        if capture:
            # host TraceMe events only: the Python tracer's frames of the
            # first step's compile would fill the capture's event limit
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(capture, profiler_options=opts)
        try:
            trainer.fit(rows)
        finally:
            if capture:
                jax.profiler.stop_trace()
        at_exit = _read_jsonl(path)
    finally:
        spans_mod.configure(None)
    return at_exit, _read_jsonl(path)


def _read_jsonl(path):
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f]


class TestTrainerSpans:
    @pytest.fixture(scope="class")
    def captured(self, tmp_path_factory):
        tmp = tmp_path_factory.mktemp("fit")
        at_exit, recs = _tiny_fit(tmp, [16, 16, 16],
                                  capture=str(tmp / "cap"))
        (trace,) = glob.glob(str(tmp / "cap" / "plugins" / "profile" / "*"
                                 / "*.trace.json.gz"))
        with gzip.open(trace) as f:
            events = json.load(f)["traceEvents"]
        pname = {e["pid"]: e["args"]["name"] for e in events
                 if e.get("ph") == "M" and e["name"] == "process_name"}
        tname = {(e["pid"], e["tid"]): e["args"]["name"] for e in events
                 if e.get("ph") == "M" and e["name"] == "thread_name"}
        host = [e for e in events if e.get("ph") == "X"
                and pname.get(e["pid"], "").startswith("/host:")
                and tname.get((e["pid"], e["tid"]), "").startswith("python")]
        return recs, host, at_exit

    def test_phase_spans_in_the_capture(self, captured):
        _, host, _ = captured
        steps = {int(e["args"]["step_num"]): e for e in host
                 if e["name"] == "train"}
        assert sorted(steps) == [0, 1, 2]
        for i, marker in steps.items():
            m0, m1 = marker["ts"], marker["ts"] + marker["dur"]
            prev_end = (steps[i - 1]["ts"] + steps[i - 1]["dur"]
                        if i else float("-inf"))
            for name in PHASES:
                (e,) = [e for e in host if e["name"].split("#")[0] == name
                        and e.get("args", {}).get("step") == str(i)]
                a, b = e["ts"], e["ts"] + e["dur"]
                if name in ("train.dispatch", "train.sync"):
                    # inside the step's StepTraceAnnotation
                    assert m0 <= a and b <= m1, (name, i)
                else:
                    # the step's rows are drawn and moved before it
                    assert prev_end <= a and b <= m0, (name, i)

    def test_records_per_step(self, captured):
        recs, _, _ = captured
        for name in PHASES + ("train.hooks",):
            got = [r["attrs"]["step"] for r in recs if r["name"] == name]
            assert got == [0, 1, 2], name
            assert all(r["span"] == "train.fit." + name
                       for r in recs if r["name"] == name)
        assert [r["name"] for r in recs].count("train.fit") == 1
        assert [r["name"] for r in recs].count("train.init_state") == 1

    def test_written_when_fit_returns(self, captured):
        recs, _, at_exit = captured
        assert at_exit == recs                  # nothing left in memory
        assert at_exit[-1]["name"] == "train.fit"

    def test_compile_records_carry_step_zero(self, captured):
        recs, _, _ = captured
        jaxr = [r for r in recs if r["name"].startswith("jax.")]
        assert jaxr and all(r["span"].startswith("train.fit.")
                            for r in jaxr)
        assert {r["attrs"]["step"] for r in jaxr
                if "step" in r["attrs"]} == {0}
        assert any(r["name"] == "jax.compile"
                   and r["attrs"].get("step") == 0
                   and r["attrs"].get("fun_name") == "train_step"
                   and r["span"] == "train.fit.train.dispatch.jax.compile"
                   for r in jaxr)

    def test_recompile_names_its_step(self, tmp_path):
        _, recs = _tiny_fit(tmp_path, [16, 16, 24])
        compiles = [r["attrs"] for r in recs if r["name"] == "jax.compile"
                    and r["attrs"].get("fun_name") == "train_step"]
        assert [a["step"] for a in compiles] == [0, 2]

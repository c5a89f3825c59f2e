"""Observability layer (ISSUE 10): tracer, metrics, timelines.

Covers the four contracts the unified layer promises:

* **Chrome trace schema** — every emitted event is a valid
  ``trace_event`` dict (``ph``/``ts``/``pid``/``tid``), complete
  events nest monotonically per lane, and a full multi-layer replay
  lands its layers on disjoint track ids.
* **Near-zero disabled cost** — the disabled module-level path returns
  one shared singleton (identity, not equality), allocates nothing,
  and instrumented runs emit an event count bounded by *buckets*, not
  cells (a call-count budget, deliberately not a wall-clock assert).
* **Metrics registry** — labeled counters/gauges/histograms with
  percentiles that agree with :func:`repro.serving.stream.percentile`,
  a stable snapshot schema, and deterministic bounded reservoirs.
* **Power timelines** — counter samples from a ``node_trace=True``
  simulation never exceed the bound, and the bound line rides along.
"""

import json
import threading
import tracemalloc

import pytest

from repro.core import (SweepEngine, homogeneous_cluster,
                        listing2_graph, scenario_grid, simulate)
from repro.obs import Tracer, trace
from repro.obs.metrics import (DEFAULT_RESERVOIR, Histogram,
                               MetricsRegistry)
from repro.obs.timeline import power_tracks, sim_tracks
from repro.serving import SweepService, percentile, poisson_replay


@pytest.fixture
def tracer():
    """A fresh installed tracer, uninstalled afterwards."""
    t = trace.install(Tracer())
    yield t
    trace.uninstall()


def grid(bounds=(6.0, 9.0), policies=("equal-share",), **kwargs):
    return scenario_grid({"l2": listing2_graph()},
                         homogeneous_cluster(3), list(bounds),
                         list(policies), **kwargs)


# --------------------------------------------------------------- schema
REQUIRED_KEYS = {"ph", "name", "ts", "pid", "tid"}


def assert_valid_events(events):
    for ev in events:
        required = (REQUIRED_KEYS - {"ts"} if ev.get("ph") == "M"
                    else REQUIRED_KEYS)
        missing = required - set(ev)
        assert not missing, f"{ev} lacks {missing}"
        assert isinstance(ev["pid"], int) and ev["pid"] >= 1
        assert isinstance(ev["tid"], int) and ev["tid"] >= 0
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0
        elif ev["ph"] == "i":
            assert ev["s"] == "t"
        elif ev["ph"] == "C":
            assert all(isinstance(v, float)
                       for v in ev["args"].values())
        elif ev["ph"] in ("b", "e"):
            assert ev["id"]


class TestTracerSchema:
    def test_all_phases_valid(self, tracer):
        with trace.span("outer", cat="t", track="a", args={"k": 1}):
            trace.instant("mark", track="a")
        trace.counter("load", {"x": 1.0, "y": 2.0}, track="b", ts=0.5)
        trace.complete("done", 0.0, 0.25, track="b", ts=1.0)
        trace.async_begin("req", "r1", track="a")
        trace.async_end("req", "r1", track="a")
        events = tracer.events()
        assert_valid_events(events)
        assert {"M", "X", "i", "C", "b", "e"} <= {e["ph"]
                                                 for e in events}

    def test_json_roundtrip(self, tracer, tmp_path):
        with trace.span("s", track="a"):
            pass
        path = tmp_path / "trace.json"
        tracer.write(str(path))
        parsed = json.loads(path.read_text())
        assert isinstance(parsed, list)
        assert_valid_events(parsed)
        assert parsed == tracer.events()

    def test_track_and_lane_metadata(self, tracer):
        trace.instant("a", track="service")
        trace.instant("b", track="engine", lane="worker-1")
        names = {(e["args"]["name"], e["pid"]) for e in tracer.events()
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert {n for n, _ in names} == {"service", "engine"}
        pids = tracer.track_ids()
        assert pids["service"] != pids["engine"]
        lanes = [e for e in tracer.events()
                 if e["ph"] == "M" and e["name"] == "thread_name"]
        assert any(e["args"]["name"] == "worker-1" for e in lanes)

    def test_simulated_ts_in_microseconds(self, tracer):
        trace.complete("job", 0.0, 2.0, track="cluster", ts=1.5)
        ev = [e for e in tracer.events() if e["ph"] == "X"][0]
        assert ev["ts"] == pytest.approx(1.5e6)
        assert ev["dur"] == pytest.approx(2.0e6)

    def test_spans_nest_monotonically(self, tracer):
        with trace.span("outer", track="a"):
            with trace.span("mid", track="a"):
                with trace.span("inner", track="a"):
                    pass
        xs = {e["name"]: e for e in tracer.events() if e["ph"] == "X"}
        assert len({(e["pid"], e["tid"]) for e in xs.values()}) == 1
        for child, parent in (("inner", "mid"), ("mid", "outer")):
            c, p = xs[child], xs[parent]
            assert c["ts"] >= p["ts"]
            assert c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-6

    def test_threads_get_distinct_lanes(self, tracer):
        def emit():
            trace.instant("tick", track="svc")

        threads = [threading.Thread(target=emit, name=f"lane{i}")
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        ticks = [e for e in tracer.events()
                 if e["ph"] == "i" and e["name"] == "tick"]
        assert len({e["tid"] for e in ticks}) == 4

    def test_installed_empty_tracer_is_truthy(self):
        assert bool(Tracer())
        assert len(Tracer()) == 0


# --------------------------------------------------- disabled-path cost
class TestDisabledPath:
    def test_disabled_span_is_shared_singleton(self):
        assert not trace.enabled()
        s1, s2 = trace.span("a", track="x"), trace.span("b")
        assert s1 is s2                    # identity: zero allocation
        with s1:
            pass

    def test_disabled_emitters_allocate_nothing(self):
        assert not trace.enabled()
        args = {"k": 1}
        values = {"x": 1.0}
        trace.instant("warm", args=args)   # warm up any lazy state
        tracemalloc.start()
        try:
            tracemalloc.clear_traces()
            for _ in range(1000):
                trace.complete("n", 0.0, 0.0, args=args)
                trace.instant("n", args=args)
                trace.counter("n", values)
                with trace.span("n", args=args):
                    pass
            current, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        # the loop itself allocates nothing; allow slack for
        # interpreter-internal bookkeeping
        assert peak < 4096, f"disabled tracing allocated {peak}B"

    def test_event_count_budget_is_per_bucket_not_per_cell(self, tracer):
        # a call-count budget, not a wall-clock assert: tracing a sweep
        # must emit O(buckets) events, never O(cells)
        cells = grid(bounds=(2.5, 6.0, 9.0, 12.0))
        result = SweepEngine(executor="vector").run(cells)
        assert not result.failures
        events = [e for e in tracer.events() if e["ph"] != "M"]
        buckets = sum(1 for e in events if e["name"] == "bucket")
        assert buckets >= 1
        assert len(events) <= 4 * buckets + 4


# ------------------------------------------------------ merged replay
class TestMergedReplay:
    def test_layers_land_on_disjoint_tracks(self, tracer):
        cells = grid()
        with SweepService(executor="vector",
                          flush_deadline_s=0.02) as svc:
            report = poisson_replay(svc, cells, rate_hz=200.0, seed=0)
        assert not report.failures
        r = simulate(listing2_graph(), homogeneous_cluster(3), 9.0,
                     node_trace=True)
        sim_tracks(r, 9.0, label="l2")
        pids = tracer.track_ids()
        assert {"service", "engine", "power:l2"} <= set(pids)
        assert len(set(pids.values())) == len(pids)   # no collisions
        assert_valid_events(tracer.events())

    def test_service_emits_request_lifecycle(self, tracer):
        cells = grid()
        with SweepService(executor="vector",
                          flush_deadline_s=0.02) as svc:
            for t in svc.submit_many(cells):
                t.result(timeout=60)
        names = {(e["ph"], e["name"]) for e in tracer.events()}
        assert ("b", "request") in names
        assert ("e", "request") in names
        assert ("i", "flush") in names
        begins = [e for e in tracer.events() if e["ph"] == "b"]
        ends = [e for e in tracer.events() if e["ph"] == "e"]
        assert {e["id"] for e in begins} == {e["id"] for e in ends}


# ------------------------------------------------------ power timeline
class TestPowerTimeline:
    def test_counter_sums_stay_under_bound(self, tracer):
        bound = 9.0
        r = simulate(listing2_graph(), homogeneous_cluster(3), bound,
                     node_trace=True)
        assert r.node_power_trace, "node_trace=True must record nodes"
        n = sim_tracks(r, bound, label="l2")
        assert n >= len(r.node_power_trace)
        power = [e for e in tracer.events()
                 if e["ph"] == "C" and e["name"] == "power_w"]
        assert power
        for ev in power:
            assert sum(ev["args"].values()) <= bound + 1e-6
        bound_line = [e for e in tracer.events()
                      if e["ph"] == "C" and e["name"] == "bound_w"]
        assert all(e["args"]["bound"] == bound for e in bound_line)

    def test_job_spans_cover_every_start(self, tracer):
        r = simulate(listing2_graph(), homogeneous_cluster(3), 9.0,
                     node_trace=True)
        sim_tracks(r, 9.0, label="l2")
        jobs = [e for e in tracer.events()
                if e["ph"] == "X" and e["cat"] == "job"]
        assert len(jobs) == len(r.job_starts)

    def test_freq_track_with_specs(self, tracer):
        specs = homogeneous_cluster(3)
        r = simulate(listing2_graph(), specs, 9.0, node_trace=True)
        sim_tracks(r, 9.0, label="l2", specs=specs)
        freq = [e for e in tracer.events()
                if e["ph"] == "C" and e["name"] == "freq_mhz"]
        assert len(freq) == len(r.node_power_trace)
        f_max = specs[0].lut.f_max
        for ev in freq:
            assert all(0.0 <= v <= f_max for v in ev["args"].values())

    def test_fallback_to_cluster_total(self, tracer):
        r = simulate(listing2_graph(), homogeneous_cluster(3), 9.0)
        assert not r.node_power_trace
        sim_tracks(r, 9.0, label="l2")
        power = [e for e in tracer.events() if e["name"] == "power_w"]
        assert power and all(set(e["args"]) == {"cluster"}
                             for e in power)

    def test_explicit_tracer_beats_installed(self):
        mine = Tracer()
        n = power_tracks([(0.0, {"a": 1.0})], 2.0, tracer=mine)
        assert n == 3 and len(mine) > 0        # samples + bound steps

    def test_disabled_returns_zero(self):
        assert not trace.enabled()
        assert power_tracks([(0.0, {"a": 1.0})], 2.0) == 0


# ------------------------------------------------------------- metrics
class TestMetrics:
    def test_counter_labels(self):
        reg = MetricsRegistry()
        c = reg.counter("flushes")
        c.inc(cause="full")
        c.inc(cause="full")
        c.inc(cause="deadline")
        assert c.value(cause="full") == 2
        assert c.value(cause="deadline") == 1
        assert c.value(cause="never") == 0
        assert c.total() == 3

    def test_gauge_set_and_add(self):
        reg = MetricsRegistry()
        g = reg.gauge("depth")
        g.set(4)
        g.add(-1)
        assert g.value() == 3
        g.set(10, node="n1")
        assert g.value(node="n1") == 10

    def test_accessors_idempotent_and_kind_checked(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_histogram_matches_serving_percentile(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat")
        values = [0.7, 0.1, 0.9, 0.3, 0.5]
        for v in values:
            h.observe(v)
        for p in (50, 90, 99):
            assert h.pct(p) == percentile(values, p)
        assert h.pct(50, phase="steady") is None

    def test_histogram_reservoir_bounded_and_deterministic(self):
        def fill():
            h = Histogram("h", threading.Lock(), reservoir=64)
            for i in range(5000):
                h.observe(float(i))
            return h

        a, b = fill(), fill()
        assert a.count() == 5000
        series = a._series[""]
        assert len(series.samples) == 64
        assert series.lo == 0.0 and series.hi == 4999.0
        assert a._series[""].samples == b._series[""].samples

    def test_snapshot_schema(self):
        reg = MetricsRegistry()
        reg.counter("c").inc(cause="full")
        reg.gauge("g").set(2.0)
        reg.histogram("h").observe(1.0)
        snap = reg.snapshot()
        assert set(snap) == {"counters", "gauges", "histograms"}
        assert snap["counters"]["c"] == {"cause=full": 1.0}
        assert snap["gauges"]["g"] == {"": 2.0}
        entry = snap["histograms"]["h"][""]
        assert set(entry) == {"count", "sum", "min", "max",
                              "p50", "p90", "p99"}
        json.dumps(snap)                      # JSON-ready end to end
        assert DEFAULT_RESERVOIR >= 1024


# --------------------------------------------------- service + metrics
class TestServiceMetrics:
    def test_stats_quote_registry_percentiles(self):
        cells = grid(bounds=(2.5, 6.0, 12.0))
        with SweepService(executor="vector",
                          flush_deadline_s=0.02) as svc:
            for t in svc.submit_many(cells):
                t.result(timeout=60)
            stats = svc.stats()
        assert stats.completed == len(cells)
        assert stats.latency_p50_s is not None
        assert stats.latency_p50_s <= stats.latency_p99_s
        assert stats.latency_p50_s == svc.latency_pct(50)
        d = stats.to_dict()
        assert d["latency_p50_s"] == stats.latency_p50_s
        assert stats.flushed_full + stats.flushed_deadline \
            == stats.buckets

    def test_phase_label_excludes_warmup(self):
        cells = grid()
        with SweepService(executor="vector",
                          flush_deadline_s=0.02) as svc:
            for t in svc.submit_many(cells):
                t.result(timeout=60)
            assert svc.latency_pct(50, phase="steady") is None
            svc.set_phase("steady")
            for t in svc.submit_many(cells):
                t.result(timeout=60)
            h = svc.metrics.histogram("serve_latency_s")
            assert h.count(phase="steady") == len(cells)
            assert h.count() == 2 * len(cells)

    def test_injected_registry_is_used(self):
        reg = MetricsRegistry()
        cells = grid()
        with SweepService(executor="vector", flush_deadline_s=0.02,
                          metrics=reg) as svc:
            for t in svc.submit_many(cells):
                t.result(timeout=60)
        assert reg.counter("serve_completed").total() == len(cells)


# ------------------------------------------------- jax: tracing + jit
class TestJaxTracing:
    def test_compile_once_survives_tracing(self, tracer):
        from repro.backends.jax import HAS_JAX

        if not HAS_JAX:
            pytest.skip("jax not installed")
        cells = grid(bounds=(2.5, 6.0, 12.0))
        with SweepService(executor="jax",
                          flush_deadline_s=0.02) as svc:
            for t in svc.submit_many(cells):
                t.result(timeout=300)
            svc.drain(timeout=60)
            warm = len(svc.profile.buckets)
            for t in svc.submit_many(cells):
                t.result(timeout=300)
            prof = svc.profile
        assert prof.recompiles == 0
        assert prof.compiles_after(warm) == 0
        names = [e["name"] for e in tracer.events() if e["ph"] == "X"]
        assert "pack" in names
        # every jit compile shows up as exactly one "compile" span
        assert names.count("compile") == prof.compiles


# -------------------------------------- regions on the profiler's clock
#: the per-bucket regions of a jax sweep, and the once-per-run ones
BUCKET_REGIONS = ("repro.sweep.solve", "repro.sweep.build",
                  "repro.engine.pack", "repro.engine.dispatch",
                  "repro.engine.wait", "repro.engine.transfer",
                  "repro.engine.results", "repro.sweep.records")
RUN_REGIONS = ("repro.sweep.run", "repro.sweep.plan")


@pytest.fixture(scope="module")
def profiled_sweep(tmp_path_factory):
    """A two-bucket jax sweep under ``jax.profiler``: its ``repro.*``
    host events ``(name, start_ns, end_ns, line, stats)`` and its
    profile."""
    from repro.backends.jax import HAS_JAX

    if not HAS_JAX:
        pytest.skip("jax not installed")
    import glob

    import jax
    from jax.profiler import ProfileData

    cells = grid(bounds=(6.0, 9.0), policies=("equal-share", "oracle"))
    engine = SweepEngine(executor="jax")
    engine.run(cells)                       # compile outside the trace
    out = tmp_path_factory.mktemp("xplane")
    jax.profiler.start_trace(str(out))
    try:
        result = engine.run(cells)
    finally:
        jax.profiler.stop_trace()
    assert not result.failures
    path, = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        for n, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    events.append((ev.name, int(ev.start_ns),
                                   int(ev.end_ns), (plane.name, n),
                                   dict(ev.stats)))
    return events, result.profile


class TestProfilerRegions:
    def test_each_region_once_per_bucket(self, profiled_sweep):
        events, profile = profiled_sweep
        names = [e[0] for e in events]
        buckets = len(profile.buckets)
        assert buckets == 2
        for name in BUCKET_REGIONS:
            assert names.count(name) == buckets, name
        for name in RUN_REGIONS:
            assert names.count(name) == 1, name
        # O(buckets), never O(cells) or O(waves)
        assert len(events) <= 12 * buckets + 4

    def test_regions_nest_under_the_run_and_carry_the_label(
            self, profiled_sweep):
        events, profile = profiled_sweep
        (_, r0, r1, line, args), = [e for e in events
                                    if e[0] == "repro.sweep.run"]
        assert args["scenarios"] == 4
        labels = {b.bucket.replace("#", "%23") for b in profile.buckets}
        for name, a, b, ln, stats in events:
            assert r0 <= a <= b <= r1 and ln == line, name
            if name in BUCKET_REGIONS:
                assert stats["bucket"] in labels, (name, stats)
                assert stats["rows"] == 2
        waves = {e[4]["bucket"]: e[4] for e in events
                 if e[0] == "repro.engine.results"}
        for b in profile.buckets:
            got = waves[b.bucket.replace("#", "%23")]
            assert (got["waves"], got["row_waves"], got["row_slots"]) \
                == (b.waves, b.row_waves, b.row_slots)

    def test_chrome_events_keep_their_names(self, tracer):
        from repro.backends.jax import HAS_JAX

        if not HAS_JAX:
            pytest.skip("jax not installed")
        result = SweepEngine(executor="jax").run(grid())
        assert not result.failures
        xs = [e for e in tracer.events() if e["ph"] == "X"]
        names = {e["name"] for e in xs}
        assert {"plan", "pack", "run", "transfer"} <= names
        assert names & {"compile", "dispatch"}
        assert not names & {"bucket:dispatch", "bucket:fetch"}
        plan, = [e for e in xs if e["name"] == "plan"]
        assert plan["args"] == {"scenarios": 2, "buckets": 1,
                                "leftovers": 0}
        assert plan["cat"] == "sweep" and \
            plan["pid"] == tracer.track_ids()["engine"]
        label = result.profile.buckets[0].bucket
        assert all(e["args"]["bucket"] == label for e in xs
                   if e["name"] in ("pack", "run", "transfer"))

    def test_region_count_is_per_bucket_with_the_profiler_off(
            self, monkeypatch):
        from repro.backends.jax import HAS_JAX

        if not HAS_JAX:
            pytest.skip("jax not installed")
        opened = []
        real = trace.region

        def counting(name, track, *a, **kw):
            opened.append(f"repro.{track}.{name}")
            return real(name, track, *a, **kw)

        monkeypatch.setattr(trace, "region", counting)
        engine = SweepEngine(executor="jax")
        per_cells = []
        for bounds in ((6.0, 9.0), (2.5, 4.0, 6.0, 9.0, 12.0, 15.0)):
            opened.clear()
            result = engine.run(grid(bounds=bounds))
            assert not result.failures and len(result.profile.buckets) == 1
            per_cells.append(sorted(opened))
        assert per_cells[0] == per_cells[1]
        assert sorted(per_cells[0]) == sorted(BUCKET_REGIONS + RUN_REGIONS)


class TestRegionHelper:
    def test_noop_without_jax_or_tracer(self, monkeypatch):
        monkeypatch.setattr(trace, "_annotation", lambda: None)
        assert trace.region("pack", "engine", chrome="pack",
                            rows=2) is trace._NOOP_SPAN
        with trace.region("pack", "engine") as r:
            r.note(rows=3)

    def test_chrome_event_only_where_named(self, tracer, monkeypatch):
        monkeypatch.setattr(trace, "_annotation", lambda: None)
        assert trace.region("results", "engine",
                            bucket="a") is trace._NOOP_SPAN
        with trace.region("plan", "sweep", chrome="plan",
                          chrome_track="engine", scenarios=4) as r:
            r.note(buckets=2)
        ev, = [e for e in tracer.events() if e["ph"] == "X"]
        assert (ev["name"], ev["cat"]) == ("plan", "sweep")
        assert ev["pid"] == tracer.track_ids()["engine"]
        assert ev["args"] == {"scenarios": 4, "buckets": 2}

    def test_annotation_gets_the_profiler_name_and_escaped_args(
            self, monkeypatch):
        entered = []

        class Annotation:
            @staticmethod
            def is_enabled():
                return True

            def __init__(self, name, **args):
                self.seen = (name, args)

            def __enter__(self):
                entered.append(self.seen)

            def __exit__(self, *exc):
                entered.append("exit")

        monkeypatch.setattr(trace, "_annotation", lambda: Annotation)
        with trace.region("pack", "engine", chrome="pack",
                          bucket="jax#0:shared", rows=2):
            pass
        assert entered == [("repro.engine.pack",
                            {"bucket": "jax%230:shared", "rows": 2}),
                           "exit"]

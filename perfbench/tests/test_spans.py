"""The reduction of the program's ``repro.*`` spans (``pb/spans.py``) and
the five readers that wait for it, on recorded chip traces.

    PYTHONPATH=src python -m pytest perfbench/tests -q

``data/spans_small.xplane.pb`` is a traced run of ``npb256-sweep`` cut to
4 ranks on one v5e (``tools/record_trace.py --ranks 4 --seconds 0.05``:
two passes) by a program with the regions; ``data/small.xplane.pb`` one by a program without
them, on which the accepted reduction must read as before and the new
readers report nothing.  ``data/span_metrics.json`` holds the readers'
``BENCHMARK.json`` entries.
"""

import json
from pathlib import Path

import pytest

import _common
import span_report
from pb import harness, spans, tracing

DATA = Path(__file__).resolve().parent / "data"
WITH_SPANS = DATA / "spans_small.xplane.pb"
WITHOUT_SPANS = DATA / "small.xplane.pb"
BUCKET_SPANS = ("repro.sweep.solve", "repro.sweep.build",
                "repro.sweep.records", "repro.engine.pack",
                "repro.engine.dispatch", "repro.engine.wait",
                "repro.engine.transfer", "repro.engine.results")
RUN_SPANS = ("repro.sweep.run", "repro.sweep.plan")
BUCKETS_PER_PASS = 4


@pytest.fixture(scope="module")
def red():
    return span_report.reduce(str(WITH_SPANS))


def read_all(trace):
    return span_report.span_metrics(_common.ROOT, "npb256-sweep", trace)


# ------------------------------------------------------- interval parts
def test_self_times_take_out_direct_children():
    spans_ = [(0, 100, "run"), (10, 40, "build"), (15, 20, "pack"),
              (50, 90, "wait"), (100, 120, "late")]
    got = spans.self_times(spans_, 0, 110)
    assert got == {"run": 100 - 30 - 40, "build": 30 - 5, "pack": 5,
                   "wait": 40, "late": 10}


def test_overlap_of_interval_lists():
    assert spans.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert spans.overlap([(0, 10)], [(10, 20)]) == 0
    assert spans.overlap([], [(0, 1)]) == 0


# ------------------------------------------------ the trace with spans
def test_each_region_once_per_bucket_in_the_window(red):
    count = red["span_count"]
    passes = count["repro.sweep.run"]
    assert passes >= 1 and count["repro.sweep.plan"] == passes
    for name in BUCKET_SPANS:
        assert count[name] == BUCKETS_PER_PASS * passes, name
    assert set(count) == set(BUCKET_SPANS + RUN_SPANS)


def test_self_times_fit_in_the_window(red):
    self_s = red["span_self_s"]
    assert set(self_s) == set(BUCKET_SPANS + RUN_SPANS)
    assert all(v >= 0 for v in self_s.values())
    # the regions of the sweep nest under one thread's run spans
    assert sum(self_s.values()) <= red["window_s"] * (1 + 1e-9)


def test_unattributed_idle_is_part_of_the_idle(red):
    assert set(red["idle_unattributed_s"]) == set(red["busy_s"])
    for dev, busy in red["busy_s"].items():
        idle = red["window_s"] - busy
        assert 0 <= red["idle_unattributed_s"][dev] <= idle * (1 + 1e-9)


def test_gaps_are_labelled_by_the_program(red):
    labels = {label for label, _ in red["idle_gaps"]}
    assert labels <= set(BUCKET_SPANS + RUN_SPANS) | {
        "bench.grid", "bench.sweep_run", "none"}
    assert labels & set(BUCKET_SPANS + RUN_SPANS)
    # the same gaps as the accepted reduction, labelled anew
    assert sorted(s for _, s in red["idle_gaps"]) \
        == sorted(s for _, s in red["bench_idle_gaps"])


def test_waves_of_the_window(red):
    assert red["waves"] > 0
    assert red["waves"] <= red["row_waves"] <= red["row_slots"]


def test_readers_on_the_trace_with_spans(red):
    got = read_all(red)
    window = red["window_s"]
    busy = sum(red["busy_s"].values())
    assert got["build_pct.sweep"] == pytest.approx(
        100 * red["span_self_s"]["repro.sweep.build"] / window)
    assert got["results_pct.sweep"] == pytest.approx(
        100 * red["span_self_s"]["repro.engine.results"] / window)
    assert got["idle_unattributed_pct.sweep"] == pytest.approx(
        100 * sum(red["idle_unattributed_s"].values()) / window)
    assert got["device_ms_per_wave.sweep"] == pytest.approx(
        1000 * busy / red["waves"])
    assert got["lockstep_idle_pct.sweep"] == pytest.approx(
        100 * (1 - red["row_waves"] / red["row_slots"]))
    for name in ("build_pct.sweep", "results_pct.sweep",
                 "idle_unattributed_pct.sweep", "lockstep_idle_pct.sweep"):
        assert 0 <= got[name] <= 100, name
    assert got["device_ms_per_wave.sweep"] > 0


# --------------------------------------------- the trace without spans
def test_accepted_reduction_reads_as_before():
    red = tracing.reduce_trace(str(WITHOUT_SPANS))
    assert set(red) == {"window_s", "busy_s", "top_ops", "idle_gaps"}
    assert red["window_s"] == pytest.approx(0.02260453, abs=1e-12)
    assert red["busy_s"] == {
        "/device:TPU:0": pytest.approx(0.004154656, abs=1e-12)}
    assert red["top_ops"][0] == ["while.133", pytest.approx(0.002172983)]
    assert len(red["top_ops"]) == tracing.TOP
    assert [g[0] for g in red["idle_gaps"]] == ["bench.sweep_run"] * 5
    assert red["idle_gaps"][0][1] == pytest.approx(0.007863079)
    ctx = {"layer": {"scenarios": 8, "window_s": red["window_s"],
                     "pack_s": 0.0}, "trace": red, "chips": 1,
           "busy_s": list(red["busy_s"].values()),
           "setup_compile_s": 1.0}
    cell = harness.Cell(_common.ROOT, "npb256-sweep")
    assert cell.reader("device_idle_pct.sweep").read(ctx) == \
        pytest.approx(100 * (1 - 0.004154656 / 0.02260453))
    assert cell.reader("device_s_per_scen.sweep").read(ctx) == \
        pytest.approx(0.004154656 / 8)


def test_new_readers_report_nothing_without_program_spans():
    red = span_report.reduce(str(WITHOUT_SPANS))
    assert red["span_self_s"] == {} and red["waves"] == 0
    assert set(read_all(red).values()) == {None}
    accepted = tracing.reduce_trace(str(WITHOUT_SPANS))
    assert set(read_all(accepted).values()) == {None}


# ----------------------------------------------------------- entries
def test_entries_name_the_readers_and_the_cell(tmp_path):
    entries = json.loads((DATA / "span_metrics.json").read_text())
    bench = json.loads((_common.ROOT / "BENCHMARK.json").read_text())
    layers = {m["layer"] for m in bench["per_layer"]}
    sources = {m["source"] for m in bench["per_layer"]}
    names = [m["name"] for m in entries["per_layer"]]
    assert names == list(span_report.METRICS)
    for m in entries["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["layer"] in layers and m["source"] in sources
        assert (m["better"], m["moves"], m["workloads"]) == (
            "lower", "sweep_scen_per_s", ["npb256-sweep"])
    root = _common.small_root(tmp_path / "root", 4)
    bench["per_layer"].extend(entries["per_layer"])
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.Cell(root, "npb256-sweep")
    assert set(names) <= {m["name"] for m in cell.per_layer}
    for name in names:
        assert callable(cell.reader(name).read)

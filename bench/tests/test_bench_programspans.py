"""The program-span reduction and the readers of the program's spans and
counters, on synthetic spans and on a short traced CPU run."""
import time
from types import SimpleNamespace

import pytest

import harness
import programspans as ps
import tracereduce as tr
from bench_cells import tiny_cell


def _sp(name, start, end, **meta):
    return ps.ProgramSpan("jasda." + name, float(start), float(end),
                          tuple((k, str(v)) for k, v in meta.items()))


def _rounds():
    # two rounds (ns): round 1 [0, 1000) with bids, settle and, inside
    # settle, a score wait and the clearing; round 2 [1000, 4000), a long
    # settle whose clearing takes most of it
    return [_sp("round", 0, 1000, round=1), _sp("bids", 10, 300),
            _sp("settle", 400, 900), _sp("score.wait", 410, 450),
            _sp("settle.clear", 450, 850),
            _sp("round", 1000, 4000, round=2), _sp("bids", 1010, 1200),
            _sp("settle", 1300, 3900), _sp("settle.clear", 1310, 3800)]


def test_ms_per_round_sums_one_name_over_the_rounds():
    spans = _rounds()
    assert ps.ms_per_round(spans, "settle.clear", 2) == pytest.approx(
        (400 + 2490) / 1e6 / 2)
    # the program has spans, none of this name: the layer took no time
    assert ps.ms_per_round(spans, "settle.feedback", 2) == 0.0
    # a program without spans (or no rounds): nothing to read
    assert ps.ms_per_round([], "settle.clear", 2) is None
    assert ps.ms_per_round(None, "settle.clear", 2) is None
    assert ps.ms_per_round(spans, "settle.clear", 0) is None


def test_idle_goes_to_the_innermost_open_span():
    spans = _rounds()
    assert ps.innermost_at(spans, 420) == "jasda.score.wait"
    assert ps.innermost_at(spans, 600) == "jasda.settle.clear"
    assert ps.innermost_at(spans, 350) == "jasda.round"
    assert ps.innermost_at(spans, 5000) == "(none)"
    events = [tr.DeviceEvent("/device:TPU:0", "k", 0.0, 100.0),
              tr.DeviceEvent("/device:TPU:0", "k", 1100.0, 100.0),
              tr.DeviceEvent("/device:TPU:0", "k", 2000.0, 100.0),
              tr.DeviceEvent("/device:TPU:1", "k", 0.0, 5000.0)]
    by = ps.idle_by_span(spans, events)
    # gaps on the first device: (100, 1100) mid 600 in the clearing;
    # (1200, 2000) mid 1600 in round 2's clearing
    assert by == {"jasda.settle.clear": pytest.approx(1800 / 1e9)}
    assert ps.idle_by_span(spans, []) == {}


def test_longest_rounds_break_down_into_children():
    out = ps.longest_rounds(_rounds(), top=1)
    assert len(out) == 1 and out[0]["round"] == "2"
    assert out[0]["seconds"] == pytest.approx(3000 / 1e9)
    assert out[0]["children_s"] == {
        "jasda.bids": pytest.approx(190 / 1e9),
        "jasda.settle": pytest.approx(2600 / 1e9),
        "jasda.settle.clear": pytest.approx(2490 / 1e9)}


def test_spans_per_round_are_counted():
    counts = ps.per_round_counts(_rounds())
    assert counts["jasda.round"] == 1.0
    assert counts["jasda.score.wait"] == 0.5
    assert counts["all"] == 4.5
    assert ps.per_round_counts([_sp("bids", 0, 1)]) == {}


def test_kernel_events_inside_dispatch_and_wait():
    spans = [_sp("score.dispatch", 0, 100), _sp("score.wait", 500, 900),
             _sp("score.dispatch", 1000, 1100), _sp("score.wait", 1200, 1300)]

    def ev(start, end):
        return tr.DeviceEvent("/device:TPU:0", "k", float(start),
                              float(end - start))

    # inside round 1; inside round 2; ends after round 2's wait; starts
    # before any dispatch
    kernels = [ev(50, 400), ev(1050, 1250), ev(1150, 1400), ev(-10, 20)]
    assert ps.kernel_in_dispatch_wait(spans, kernels) == 0.5
    assert ps.kernel_in_dispatch_wait(spans, []) is None


def _ctx(rows):
    run = SimpleNamespace(round_log=rows, rounds=len(rows))
    return harness.Context(run=run, device={})


def test_counter_readers_average_the_audit_rows():
    rows = [SimpleNamespace(n_passes=2, n_reclears=10, prep="serial"),
            SimpleNamespace(n_passes=4, n_reclears=30, prep="spec_hit"),
            SimpleNamespace(n_passes=0, n_reclears=0, prep="spec_filtered"),
            SimpleNamespace(n_passes=6, n_reclears=0, prep="serial")]
    ctx = _ctx(rows)
    assert harness.metric_reader("settle_passes")(ctx) == 3.0
    assert harness.metric_reader("settle_reclears")(ctx) == 10.0
    assert harness.metric_reader("spec_used_share")(ctx) == 50.0
    # audit rows without the counters (a program that lacks them) or no
    # rows at all: nothing to read, and no error
    bare = _ctx([SimpleNamespace(n_conflicts=1)])
    for name in ("settle_passes", "settle_reclears", "spec_used_share"):
        assert harness.metric_reader(name)(bare) is None
        assert harness.metric_reader(name)(_ctx([])) is None


NEW_METRICS = ("announce_ms", "score_dispatch_ms", "score_wait_ms",
               "clear_ms", "feedback_ms", "settle_passes", "settle_reclears",
               "spec_used_share")


@pytest.mark.parametrize("name", ["pod256-steady", "migdgx-steady",
                                  "pod256-steady+burst"])
def test_traced_run_reports_the_program_metrics(name):
    cell = tiny_cell(name)
    res = harness.measure(cell, 3141592653589, 1.0, True,
                          t_proc0=time.perf_counter(), require_tpu=False)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(NEW_METRICS) <= set(got), sorted(got)
    # the settle's parts lie inside the harness's settle span
    parts = got["score_wait_ms"] + got["clear_ms"] + got["feedback_ms"]
    assert 0 < parts <= got["settle_ms"]
    assert got["settle_reclears"] >= got["settle_passes"] > 0
    assert 0.0 <= got["spec_used_share"] <= 100.0
    spans = ps.load(harness.OUT_DIR / "trace" / name)
    assert ps.per_round_counts(spans)["all"] <= 12

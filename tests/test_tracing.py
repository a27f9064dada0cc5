"""The scheduler's work counters and its ``jasda.*`` trace spans.

* the settle's fixed point counts its resolve passes and the windows it
  clears by WIS, summed over GlobalAssignment's replays on both the serial
  and the lockstep replay path;
* each round's audit row carries those counts and the source of its
  preparation (serial or the pipeline's speculation);
* with the profiler on, a short service run records every span, nested
  round > settle > (score wait, clearing, feedback).
"""
import glob
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import JasdaScheduler, SliceSpec, make_workload
from repro.core.policy import GlobalAssignment, GreedyWIS, Policy
from repro.core.scheduler import SchedulerConfig
from repro.core.trp import fmp_standard
from repro.core.types import Variant, Window
from repro.core.wis import make_round_selector, wis_select

GB = 1 << 30


def _one_conflict_round():
    """Two windows over the same time; J0 wins both with overlapping
    intervals, keeps s0 (the better score) and loses s1 to J1."""
    windows = [Window("s0", 8 * GB, 0.0, 100.0),
               Window("s1", 8 * GB, 0.0, 100.0)]

    def bid(job, sid, vid):
        return Variant(job_id=job, slice_id=sid, t_start=0.0, duration=10.0,
                       fmp=fmp_standard(1 * GB, 2 * GB, 0.1 * GB),
                       local_utility=0.5, declared_features={},
                       payload={"work": 10.0}, variant_id=vid)

    fit = [bid("J0", "s0", "a"), bid("J0", "s1", "b"), bid("J1", "s1", "c"),
           bid("J2", "s0", "d")]
    win_idx = np.array([0, 1, 1, 0])
    scores = np.array([0.9, 0.8, 0.5, 0.4])
    return windows, fit, win_idx, scores


# GreedyWIS: sweep 2 windows, pass 1 bans J0 on s1, re-clear s1, pass 2
# finds nothing.  GlobalAssignment adds one replay per choice of J0's
# window (2 combos), each seeded with the first sweep: a pass that bans,
# one re-clear, a pass that finds nothing -> 2 + 2 + 2 passes, 3 + 1 + 1
# windows.  A batched selector runs GlobalAssignment's replays in lockstep.
# The per-window loop is the plain ``wis_select`` passed explicitly.
@pytest.mark.parametrize("clearing,impl,passes,reclears", [
    (GreedyWIS(), None, 2, 3),
    (GreedyWIS(), "numpy", 2, 3),
    (GlobalAssignment(), None, 6, 5),
    (GlobalAssignment(), "numpy", 6, 5),
], ids=["greedy", "greedy-batched", "global-serial", "global-lockstep"])
def test_settle_counts_fixed_point_work(clearing, impl, passes, reclears):
    windows, fit, win_idx, scores = _one_conflict_round()
    selector = wis_select if impl is None else make_round_selector(impl)
    rr = clearing.settle(windows, fit, win_idx, scores, selector=selector)
    assert rr.n_conflicts == 1
    assert [[v.variant_id for v in r.selected] for r in rr.results] == \
        [["a"], ["c"]]
    assert (rr.n_passes, rr.n_reclears) == (passes, reclears)
    # two-lane windows: every batched-selector clear is a scalar row
    assert rr.n_reclears_vec == 0


def _sched(clearing=GreedyWIS(), n_jobs=18):
    sched = JasdaScheduler(
        [SliceSpec("s20", 20 * GB, n_chips=4),
         SliceSpec("s10", 10 * GB, n_chips=2),
         SliceSpec("s5", 5 * GB)],
        SchedulerConfig(score_impl="ref", policy=Policy(clearing=clearing)))
    for a in make_workload(n_jobs, seed=3, arrival_rate=2.0):
        sched.add_job(a, 0.0)
    return sched


@pytest.mark.parametrize("clearing", [GreedyWIS(), GlobalAssignment()],
                         ids=["greedy", "global"])
def test_audit_row_carries_the_settle_counts(clearing):
    sched = _sched(clearing)
    for t in range(12):
        rr = sched.run_round(float(t))
        row = sched.log[-1]
        assert (row.prep, row.n_spec_discarded) == ("serial", 0)
        if rr is None or not rr.n_bids:
            assert (row.n_passes, row.n_reclears) == (0, 0)
            continue
        assert (row.n_passes, row.n_reclears) == (rr.n_passes, rr.n_reclears)
        assert row.n_reclears_vec == rr.n_reclears_vec <= row.n_reclears
        assert row.n_passes >= 1 and row.n_reclears >= row.n_windows
    assert sum(r.n_conflicts for r in sched.log) > 0


def test_pipelined_rows_record_their_preparation():
    times = [float(t) for t in range(30)]
    serial, piped = _sched(), _sched()
    for t in times:
        serial.run_round(t)
    piped.run_rounds_pipelined(times)
    assert all((r.prep, r.n_spec_discarded) == ("serial", 0)
               for r in serial.log)
    assert {r.prep for r in piped.log} <= {"serial", "spec_hit",
                                           "spec_filtered"}
    # speculation ran (used or discarded) ...
    assert any(r.prep != "serial" or r.n_spec_discarded for r in piped.log)
    # ... and changed no decision, so not the settle's work either
    assert ([(r.n_passes, r.n_reclears) for r in serial.log]
            == [(r.n_passes, r.n_reclears) for r in piped.log])
    # a discarded speculation is followed by a serial preparation
    assert all(r.prep == "serial" for r in piped.log if r.n_spec_discarded)


def test_core_loads_without_jax():
    code = ("import sys, repro.core, repro.core.tracing, repro.service; "
            "print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True,
                         env={**os.environ, "PYTHONPATH": os.pathsep.join(
                             sys.path)})
    assert out.stdout.strip() == "False"


SPANS = {"jasda.round", "jasda.announce", "jasda.bids", "jasda.pack",
         "jasda.score.dispatch", "jasda.settle", "jasda.score.wait",
         "jasda.settle.clear", "jasda.settle.feedback"}


def _trace_service(trace_dir):
    import jax
    from repro.service import JasdaService, PoissonArrivals, ServiceConfig

    slices = [SliceSpec(f"s{i}", 16 * GB, n_chips=2) for i in range(4)]
    arrivals = PoissonArrivals(2.0, seed=5, work_range=(4.0, 20.0),
                               mem_range_gb=(1.0, 8.0))
    svc = JasdaService(JasdaScheduler(slices, SchedulerConfig(
        score_impl="ref")), arrivals, config=ServiceConfig(t_end=12.0))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(str(trace_dir), profiler_options=opts):
        svc.run()
    return svc


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("jasda."):
                        out.append((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    dict(ev.stats)))
    return out


def test_service_run_records_nested_spans(tmp_path):
    svc = _trace_service(tmp_path)
    spans = _host_spans(tmp_path)
    assert {s[0] for s in spans} == SPANS

    def parents(name, parent):
        outer = [s for s in spans if s[0] == parent]
        for s in spans:
            if s[0] == name:
                assert any(p[1] <= s[1] and s[2] <= p[2] for p in outer), s

    for inner in ("jasda.score.wait", "jasda.settle.clear",
                  "jasda.settle.feedback"):
        parents(inner, "jasda.settle")
    parents("jasda.settle", "jasda.round")
    rounds = [s for s in spans if s[0] == "jasda.round"]
    assert len(rounds) == svc.round_count
    assert sorted(int(s[3]["round"]) for s in rounds) == \
        list(range(1, svc.round_count + 1))
    # at most one of each span a round, bar speculative preparation
    assert len(spans) <= 12 * len(rounds)

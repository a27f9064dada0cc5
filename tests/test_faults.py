"""Fault injection, graceful degradation, and crash recovery.

Covers the robustness layer end to end: the retry/backoff helper, typed
kernel dispatch errors + the pallas → ref → numpy degradation ladder,
agent silent/error windows at bid collection, slice revocation with the
full recovery protocol, dead-window epsilon boundaries, calibration
snapshot round-trips, and checkpointed crash recovery (byte-identical
replay, serial AND pipelined).
"""
import json
import pickle

import numpy as np
import pytest

import repro.kernels.common as kcommon
from repro.checkpoint import CheckpointStore
from repro.core import (FaultEvent, FaultInjector, FaultPlan, JasdaScheduler,
                        SchedulerConfig, SimConfig, SliceSpec, simulate,
                        make_workload)
from repro.core.calibration import Calibrator
from repro.core.faults import (AGENT_ERROR, AGENT_SILENT, DEVICE_DISPATCH_FAIL,
                               SCHEDULER_CRASH, SLICE_REVOKED,
                               AgentRespondError, AgentSilentError)
from repro.core.negotiation.messages import LOSS_SLICE_FAILED
from repro.core.types import Variant
from repro.core.windows import DeadWindowRegistry
from repro.kernels.common import (BackendHealth, KernelDispatchError,
                                  check_dispatch_fault, clear_dispatch_faults,
                                  dispatch_faults_snapshot,
                                  inject_dispatch_fault,
                                  restore_dispatch_faults)
from repro.runtime.monitor import retry_with_backoff

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAS_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional dependency
    HAS_HYPOTHESIS = False

GB = 1 << 30


@pytest.fixture(autouse=True)
def _clean_armed_faults():
    clear_dispatch_faults()
    yield
    clear_dispatch_faults()


def _slices(n=3, cap_gb=16):
    return [SliceSpec(f"S{k}", cap_gb * GB, flops_per_s=1.0, hbm_bw=1.0)
            for k in range(n)]


def _sched(impl="numpy"):
    return JasdaScheduler(_slices(), SchedulerConfig(wis_impl=impl))


def _commit_rows(sched):
    return [(r.status, r.job_id, r.slice_id, r.t_start, r.t_end, r.score)
            for r in sched.commit_log]


def _log_rows(sched):
    return [(l.t, l.n_bidders, l.n_bids, l.n_selected, l.total_score,
             l.n_windows, l.n_conflicts, l.n_dropped) for l in sched.log]


# ---------------------------------------------------------------------------
# retry_with_backoff
# ---------------------------------------------------------------------------

def test_backoff_success_first_try_no_sleep():
    sleeps = []
    out = retry_with_backoff(lambda k: ("ok", k), sleep=sleeps.append)
    assert out == ("ok", 0)
    assert sleeps == []


def test_backoff_delay_sequence_and_recovery():
    sleeps, calls = [], []

    def flaky(k):
        calls.append(k)
        if k < 2:
            raise RuntimeError("boom")
        return k

    out = retry_with_backoff(flaky, retries=3, base=0.05, factor=2.0,
                             max_delay=1.0, sleep=sleeps.append)
    assert out == 2
    assert calls == [0, 1, 2]
    assert sleeps == pytest.approx([0.05, 0.10])


def test_backoff_delay_cap():
    sleeps = []

    def always(k):
        raise RuntimeError("boom")

    with pytest.raises(RuntimeError):
        retry_with_backoff(always, retries=5, base=0.1, factor=10.0,
                           max_delay=0.3, sleep=sleeps.append)
    assert sleeps == pytest.approx([0.1, 0.3, 0.3, 0.3, 0.3])


def test_backoff_jitter_deterministic_per_seed():
    def run(seed):
        sleeps = []

        def twice(k):
            if k < 2:
                raise RuntimeError("boom")
            return k

        retry_with_backoff(twice, retries=2, base=0.1, jitter=0.5,
                           rng=np.random.default_rng(seed),
                           sleep=sleeps.append)
        return sleeps

    a, b = run(7), run(7)
    assert a == b  # seeded jitter replays
    assert all(s >= base for s, base in zip(a, [0.1, 0.2]))
    assert run(8) != a  # and actually jitters


def test_backoff_nonretryable_raises_immediately():
    calls = []

    def fail(k):
        calls.append(k)
        raise ValueError("fatal")

    with pytest.raises(ValueError):
        retry_with_backoff(fail, retries=5, sleep=lambda _d: None,
                           retryable=lambda e: not isinstance(e, ValueError))
    assert calls == [0]


def test_backoff_exhaustion_raises_last_error():
    with pytest.raises(RuntimeError, match="attempt 2"):
        retry_with_backoff(
            lambda k: (_ for _ in ()).throw(RuntimeError(f"attempt {k}")),
            retries=2, sleep=lambda _d: None)
    with pytest.raises(ValueError):
        retry_with_backoff(lambda k: k, retries=-1)


# ---------------------------------------------------------------------------
# typed kernel dispatch errors + degradation ladder
# ---------------------------------------------------------------------------

def test_kernel_dispatch_error_carries_backend_and_shape():
    inject_dispatch_fault("ref")
    with pytest.raises(KernelDispatchError) as ei:
        check_dispatch_fault("ref", "score_variants", (256, 32))
    err = ei.value
    assert err.backend == "ref"
    assert err.op == "score_variants"
    assert err.shape == (256, 32)
    assert isinstance(err.cause, RuntimeError)
    # the armed fault is one-shot
    check_dispatch_fault("ref", "score_variants", (256, 32))
    assert dispatch_faults_snapshot() == {}


def test_dispatch_faults_snapshot_roundtrip():
    inject_dispatch_fault("ref", count=2)
    snap = dispatch_faults_snapshot()
    clear_dispatch_faults()
    assert dispatch_faults_snapshot() == {}
    restore_dispatch_faults(snap)
    assert dispatch_faults_snapshot() == {"ref": 2}


def test_backend_health_ladder_and_stickiness():
    h = BackendHealth()
    assert h.resolve("pallas") == "pallas"
    h.mark_failed("pallas", "xla oom")
    assert h.resolve("pallas") == "ref"
    h.mark_failed("ref")
    assert h.resolve("pallas") == "numpy"
    assert h.resolve("ref") == "numpy"
    assert not h.healthy("ref") and h.healthy("numpy")
    # first failure reason is sticky
    h.mark_failed("pallas", "second reason")
    assert h.failed_backends()["pallas"] == "xla oom"
    h2 = BackendHealth()
    h2.restore(h.snapshot())
    assert h2.failed_backends() == h.failed_backends()


def test_settle_batch_raises_typed_error_and_ladder_recovers():
    from repro.core.wis import RoundSelector
    from repro.kernels.wis_dp import ops as wis_ops

    w = np.random.default_rng(0).uniform(1, 2, (4, 8)).astype(np.float32)
    pred = np.zeros((4, 8), np.int32)
    inject_dispatch_fault("ref")
    with pytest.raises(KernelDispatchError) as ei:
        wis_ops.wis_settle_batch(w, pred, impl="ref")
    assert ei.value.backend == "ref" and ei.value.op == "wis_settle_batch"

    # same fault through the selector: degrades to numpy, still selects
    inject_dispatch_fault("ref")
    health = BackendHealth()
    rs = RoundSelector("ref", health=health)
    sel = rs._dispatch(w.astype(np.float64), pred)
    assert sel.shape == w.shape
    assert "ref" in health.failed_backends()
    assert rs._effective_impl() == "numpy"
    # without a health object the typed error propagates
    inject_dispatch_fault("ref")
    with pytest.raises(KernelDispatchError):
        RoundSelector("ref")._dispatch(w.astype(np.float64), pred)


def test_ladder_degradation_preserves_results_and_traces():
    from repro.kernels.wis_dp.ops import trace_counts

    plan = FaultPlan(seed=0, events=(
        FaultEvent(t=5.0, kind=DEVICE_DISPATCH_FAIL, target="ref"),))
    r_fault = simulate(_sched("ref"), make_workload(8, seed=3),
                       SimConfig(t_end=300.0, seed=1), faults=plan)
    assert "ref" in r_fault.scheduler.backend_health.failed_backends()
    assert r_fault.scheduler._wis_selector._effective_impl() == "numpy"
    before = dict(trace_counts())
    r_ref = simulate(_sched("numpy"), make_workload(8, seed=3),
                     SimConfig(t_end=300.0, seed=1))
    # ladder lands on the host backend: results match the numpy reference
    assert _commit_rows(r_fault.scheduler) == _commit_rows(r_ref.scheduler)
    assert r_fault.jct_per_job == r_ref.jct_per_job
    # the degraded run retraced nothing on the dead backend
    assert dict(trace_counts()) == before


# ---------------------------------------------------------------------------
# fault plans + the agent-fault gate
# ---------------------------------------------------------------------------

class _InFlightFailure:
    """A device array whose materialization fails (a device lost in flight)."""

    shape = (256,)

    def __getitem__(self, _):
        return self

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("device lost in flight")


def _raise_dispatch(*_a, **_k):
    raise RuntimeError("XLA compile error")


# a real (non-injected) device error at each place the ladder used to hide
# one: the scoring dispatch, the settle dispatch (fused first pass and
# re-clear), and the in-flight scores at ScoreHandle.result()
@pytest.mark.parametrize("site,score_impl,wis_impl,match", [
    ("score_dispatch", "ref", None, "XLA compile error"),
    ("settle_dispatch", "ref", "ref", "XLA compile error"),
    ("score_result", "ref", None, "device lost in flight"),
])
def test_real_device_error_ends_the_service_run(monkeypatch, site,
                                                score_impl, wis_impl, match):
    from repro.kernels.jasda_score import ops as score_ops
    from repro.kernels.wis_dp import ops as wis_ops
    from repro.service import JasdaService, PoissonArrivals, ServiceConfig

    if site == "score_dispatch":
        monkeypatch.setattr(score_ops, "_score_ref_jit", _raise_dispatch)
    elif site == "settle_dispatch":
        monkeypatch.setattr(wis_ops, "_settle_ref_fused_jit", _raise_dispatch)
        monkeypatch.setattr(wis_ops, "_settle_ref_jit", _raise_dispatch)
    else:
        monkeypatch.setattr(
            score_ops, "_score_ref_jit",
            lambda *a: (_InFlightFailure(),) * 3)
    sched = JasdaScheduler(_slices(), SchedulerConfig(
        score_impl=score_impl, wis_impl=wis_impl))
    svc = JasdaService(sched, PoissonArrivals(2.0, seed=0),
                       config=ServiceConfig(t_end=20.0))
    with pytest.raises(RuntimeError, match=match):
        svc.run()
    # nothing degraded: the ladder is for injected faults only
    assert sched.backend_health.failed_backends() == {}


def test_fault_event_validates_kind():
    with pytest.raises(ValueError):
        FaultEvent(t=1.0, kind="meteor_strike")


def test_fault_plan_sorts_and_generates_deterministically():
    e1 = FaultEvent(t=9.0, kind=SLICE_REVOKED, target="S0")
    e2 = FaultEvent(t=3.0, kind=AGENT_SILENT, target="J000", duration=5.0)
    plan = FaultPlan(seed=0, events=(e1, e2))
    assert [e.t for e in plan.events] == [3.0, 9.0]
    assert plan.for_kind(SLICE_REVOKED) == (e1,)

    kw = dict(t_end=500.0, slice_ids=["S0", "S1"], job_ids=["J0", "J1"],
              revoke_rate=0.01, silent_rate=0.01, error_rate=0.01,
              dispatch_fail_times=[100.0], crash_times=[200.0])
    a, b = FaultPlan.generate(11, **kw), FaultPlan.generate(11, **kw)
    assert a == b
    assert a != FaultPlan.generate(12, **kw)
    assert a.for_kind(SCHEDULER_CRASH)[0].t == 200.0


def test_injector_gate_windows_and_attempts():
    plan = FaultPlan(seed=0, events=(
        FaultEvent(t=10.0, kind=AGENT_SILENT, target="JA", duration=5.0),
        FaultEvent(t=20.0, kind=AGENT_ERROR, target="JB", duration=5.0,
                   attempts=2),
    ))
    gate = FaultInjector(plan)

    class A:
        def __init__(self, jid):
            self.spec = type("S", (), {"job_id": jid})()

    ja, jb = A("JA"), A("JB")
    gate(ja, 9.9, 0)  # before the window: no raise
    with pytest.raises(AgentSilentError):
        gate(ja, 10.0, 0)
    with pytest.raises(AgentSilentError):
        gate(ja, 14.9, 3)  # silence ignores the attempt index
    gate(ja, 15.0, 0)  # window is half-open [t0, t1)

    with pytest.raises(AgentRespondError):
        gate(jb, 21.0, 0)
    with pytest.raises(AgentRespondError):
        gate(jb, 21.0, 1)
    gate(jb, 21.0, 2)  # attempts=2: the third retry succeeds
    # the gate is stateless in time: re-asking an old (t, attempt) replays
    with pytest.raises(AgentRespondError):
        gate(jb, 21.0, 0)
    # slice/device/crash events go through the heap, agent windows do not
    kinds = {e.kind for e in gate.scheduled_events()}
    assert AGENT_SILENT not in kinds and AGENT_ERROR not in kinds
    assert pickle.loads(pickle.dumps(gate)).plan == plan


def test_silent_and_error_agents_do_not_stall_rounds():
    plan = FaultPlan(seed=0, events=(
        FaultEvent(t=0.0, kind=AGENT_SILENT, target="J001", duration=60.0),
        FaultEvent(t=0.0, kind=AGENT_ERROR, target="J002", duration=40.0),
    ))
    results = {}
    for pipeline in (False, True):
        sched = _sched()
        r = simulate(sched, make_workload(8, seed=3),
                     SimConfig(t_end=400.0, seed=1, pipeline=pipeline),
                     faults=plan)
        assert r.iterations > 0
        assert sum(l.n_dropped for l in sched.log) > 0
        results[pipeline] = (_commit_rows(sched), _log_rows(sched),
                             r.jct_per_job, r.calibration)
    # dropped bidders are part of round state: pipelined == serial exactly
    assert results[False] == results[True]


def test_error_agent_recovers_within_retry_budget():
    # fails 2 consecutive attempts; scheduler retries bid_retries=2 times,
    # so the third attempt lands and the agent is NEVER dropped
    plan = FaultPlan(seed=0, events=(
        FaultEvent(t=0.0, kind=AGENT_ERROR, target="J000", duration=1e9,
                   attempts=2),))
    sched = _sched()
    r = simulate(sched, make_workload(4, seed=3),
                 SimConfig(t_end=200.0, seed=1), faults=plan)
    assert sum(l.n_dropped for l in sched.log) == 0
    assert r.n_finished > 0


# ---------------------------------------------------------------------------
# slice revocation
# ---------------------------------------------------------------------------

def test_revoke_slice_full_protocol():
    sched = _sched()
    agents = make_workload(8, seed=3)
    for a in agents:
        sched.add_job(a, 0.0)
    for k in range(12):
        sched.run_round(float(k))
    victims = [c for c in sched.commitments if c.variant.slice_id == "S1"]
    assert victims, "workload never committed to S1; pick another seed"
    starts = [c.variant.t_start for c in victims]
    lost = sched.revoke_slice("S1", now=12.0)
    assert {id(c) for c in lost} == {id(c) for c in victims}
    # commit_log rows flipped to lost
    lost_rows = [r for r in sched.commit_log if r.status == "lost"]
    assert len(lost_rows) == len(victims)
    # revoked windows are retired: an eps-close twin stays suppressed
    for t0 in starts:
        assert sched._dead_windows.suppressed("S1", t0)
        assert sched._dead_windows.suppressed("S1", t0 + 0.5e-6)
    # out-of-round feedback notified every affected agent
    fb = sched.last_feedback
    assert fb is not None and fb.t == 12.0 and fb.windows == ()
    reported = {v.variant_id for ls in fb.losses.values() for v in ls}
    assert reported == {c.variant.variant_id for c in victims}
    assert all(l.reason == LOSS_SLICE_FAILED
               for ls in fb.losses.values() for l in ls)
    assert set(fb.reliability) == set(fb.losses)


def test_revoked_work_is_recleared_and_sim_completes():
    plan = FaultPlan(seed=0, events=(
        FaultEvent(t=40.0, kind=SLICE_REVOKED, target="S1", duration=40.0),
        FaultEvent(t=30.0, kind=AGENT_SILENT, target="J003", duration=20.0),
    ))
    sched = _sched()
    r = simulate(sched, make_workload(8, seed=3),
                 SimConfig(t_end=600.0, seed=1), faults=plan)
    rows = sched.commit_log
    lost_jobs = {row.job_id for row in rows if row.status == "lost"}
    assert lost_jobs, "revocation at t=40 should catch live commitments"
    # every revoked commitment is accounted for in the audit trail AND the
    # job's work was re-cleared afterwards (a later commitment exists)
    for job in lost_jobs:
        t_lost = max(row.t_start for row in rows
                     if row.job_id == job and row.status == "lost")
        later = [row for row in rows if row.job_id == job
                 and row.status != "lost" and row.t_end > t_lost]
        assert later, f"{job} lost its slice but was never re-cleared"
    assert r.n_finished == r.n_jobs  # nothing is stranded by the fault


def test_degraded_slice_inflates_observed_durations():
    plan = FaultPlan(seed=0, events=(
        FaultEvent(t=0.5, kind="slice_degraded", target="S0", magnitude=0.25),
        FaultEvent(t=0.5, kind="slice_degraded", target="S1", magnitude=0.25),
        FaultEvent(t=0.5, kind="slice_degraded", target="S2", magnitude=0.25),
    ))
    r_slow = simulate(_sched(), make_workload(6, seed=3),
                      SimConfig(t_end=2000.0, seed=1), faults=plan)
    r_fast = simulate(_sched(), make_workload(6, seed=3),
                      SimConfig(t_end=2000.0, seed=1))
    assert r_slow.mean_jct > r_fast.mean_jct


# ---------------------------------------------------------------------------
# dead-window epsilon boundaries (revoked twin re-announced within eps)
# ---------------------------------------------------------------------------

def _check_eps_boundary(t_min, frac, eps):
    reg = DeadWindowRegistry(eps=eps)
    reg.add("s", t_min, expiry=100.0)
    inside = t_min + frac * eps
    outside = t_min + (2.0 + frac) * eps
    assert reg.suppressed("s", inside)
    assert not reg.suppressed("s", outside)
    # a twin within eps MERGES (expiry extends) instead of duplicating
    reg.add("s", inside, expiry=200.0)
    assert len(reg) == 1
    reg.prune(150.0)
    assert reg.suppressed("s", t_min), "merged expiry must be the max"
    # a twin beyond eps is a distinct entry
    reg.add("s", outside, expiry=300.0)
    assert len(reg) == 2


if HAS_HYPOTHESIS:

    @settings(max_examples=200, deadline=None)
    @given(
        t_min=st.floats(0.0, 1e6, allow_nan=False, allow_infinity=False),
        # frac ≤ 0.9: t_min + frac*eps rounds to the nearest float, and at
        # frac=1.0 that rounding could push the twin just PAST eps
        frac=st.floats(0.0, 0.9, allow_nan=False),
        eps=st.floats(1e-9, 1e-3, allow_nan=False),
    )
    def test_dead_window_eps_boundary_property(t_min, frac, eps):
        _check_eps_boundary(t_min, frac, eps)

else:  # pragma: no cover - exercised only without hypothesis

    def test_dead_window_eps_boundary_seeded():
        rng = np.random.default_rng(0)
        for _ in range(200):
            _check_eps_boundary(float(rng.uniform(0, 1e6)),
                                float(rng.uniform(0, 0.9)),
                                float(10.0 ** rng.uniform(-9, -3)))


def test_dead_window_eps_boundary_near_limit():
    reg = DeadWindowRegistry(eps=1e-6)
    reg.add("s", 10.0, expiry=50.0)
    assert reg.suppressed("s", 10.0 + 0.999e-6)  # just inside eps
    assert not reg.suppressed("s", 10.0 + 2.1e-6)  # clearly beyond


# ---------------------------------------------------------------------------
# calibration snapshot round-trip (incl. jobs that never re-bid)
# ---------------------------------------------------------------------------

def _run_calibrated():
    sched = _sched()
    r = simulate(sched, make_workload(8, seed=3, misreport_fraction=0.4),
                 SimConfig(t_end=300.0, seed=1))
    assert any(row["errors"] for row in r.calibration.values())
    return sched, r.calibration


def test_calibration_roundtrip_exact_and_json():
    sched, snap = _run_calibrated()
    c2 = Calibrator(sched.calibrator.config).restore(snap)
    assert c2.snapshot() == snap
    # through JSON (the benchmark/CLI checkpoint form)
    c3 = Calibrator(sched.calibrator.config).restore(
        json.loads(json.dumps(snap)))
    assert c3.snapshot() == snap
    # error history order is state (windowed E[ε] reads the tail), and a
    # job that never re-bids must keep it verbatim through restore
    for j, row in snap.items():
        assert c2._jobs[j].errors == row["errors"]


def test_calibration_restore_continues_identically():
    sched, snap = _run_calibrated()
    c2 = Calibrator(sched.calibrator.config).restore(snap)
    jid = max(snap, key=lambda j: len(snap[j]["errors"]))
    v = Variant(job_id=jid, slice_id="S0", t_start=0.0, duration=1.0,
                fmp=None, local_utility=0.9, declared_features={"jct": 0.9})
    e1 = sched.calibrator.verify(v, {"jct": 0.55})
    e2 = c2.verify(v, {"jct": 0.55})
    assert e1 == e2
    assert sched.calibrator.snapshot()[jid] == c2.snapshot()[jid]


# ---------------------------------------------------------------------------
# checkpointed crash recovery
# ---------------------------------------------------------------------------

def test_checkpoint_save_restore_state(tmp_path):
    store = CheckpointStore(str(tmp_path), keep=2)
    store.save_state(0, {"a": np.arange(3), "b": "x"})
    store.save_state(5, {"a": np.arange(4), "b": "y"})
    state, step = store.restore_state()
    assert step == 5 and state["b"] == "y"
    np.testing.assert_array_equal(state["a"], np.arange(4))
    state0, _ = store.restore_state(0)
    assert state0["b"] == "x"
    store.save_state(7, {"b": "z"})
    assert store.steps() == [5, 7]  # gc kept the newest two


_CRASH_BASE = (
    FaultEvent(t=12.0, kind=SLICE_REVOKED, target="S1", duration=40.0),
    FaultEvent(t=30.0, kind=AGENT_SILENT, target="J003", duration=20.0),
)


@pytest.mark.parametrize("pipeline", [False, True])
def test_crash_replay_is_byte_identical(pipeline, tmp_path):
    cfg = SimConfig(t_end=300.0, seed=1, pipeline=pipeline)
    runs = {}
    for tag, extra in (("ref", ()), ("crash", (
            FaultEvent(t=40.5, kind=SCHEDULER_CRASH),
            FaultEvent(t=90.5, kind=SCHEDULER_CRASH)))):
        store = CheckpointStore(str(tmp_path / f"{tag}_{pipeline}"))
        r = simulate(_sched(), make_workload(8, seed=3), cfg,
                     faults=FaultPlan(seed=7, events=_CRASH_BASE + extra),
                     checkpoint=store, checkpoint_every=5)
        runs[tag] = r
    ref, crash = runs["ref"], runs["crash"]
    assert _commit_rows(crash.scheduler) == _commit_rows(ref.scheduler)
    assert _log_rows(crash.scheduler) == _log_rows(ref.scheduler)
    assert crash.jct_per_job == ref.jct_per_job
    assert crash.calibration == ref.calibration
    assert crash.n_finished == ref.n_finished
    assert crash.total_score == ref.total_score


def test_crash_without_checkpoint_is_ignored():
    plan = FaultPlan(seed=0, events=(
        FaultEvent(t=50.5, kind=SCHEDULER_CRASH),))
    r = simulate(_sched(), make_workload(6, seed=3),
                 SimConfig(t_end=300.0, seed=1), faults=plan)
    r_ref = simulate(_sched(), make_workload(6, seed=3),
                     SimConfig(t_end=300.0, seed=1))
    assert r.jct_per_job == r_ref.jct_per_job


def test_scheduler_pickle_preserves_commit_identity():
    sched = _sched()
    agents = make_workload(6, seed=3)
    for a in agents:
        sched.add_job(a, 0.0)
    for k in range(10):
        sched.run_round(float(k))
    assert sched.commitments
    s2 = pickle.loads(pickle.dumps(sched))
    assert _commit_rows(s2) == _commit_rows(sched)
    # the commit index must be re-keyed by the RESTORED variants' ids
    for c in s2.commitments:
        assert id(c.variant) in s2._commit_index
        entry_c, _rec = s2._commit_index[id(c.variant)]
        assert entry_c is c
    # restored scheduler keeps scheduling
    assert s2.run_round(10.0) is not None or True


def test_chaos_seeded_plan_completes(tmp_path):
    """CI chaos matrix entry: a generated FaultPlan for JASDA_CHAOS_SEED.

    Under slice revocations + silent/erroring bidders + a mid-run crash the
    simulation must complete (no stall, no unhandled exception), every
    revoked commitment must be reported in the audit trail, and the
    pipelined run must equal the serial one exactly.
    """
    import os

    seed = int(os.environ.get("JASDA_CHAOS_SEED", "0"))
    t_end = 400.0
    plan = FaultPlan.generate(
        seed, t_end=t_end,
        slice_ids=[s.slice_id for s in _slices()],
        job_ids=[f"J{i:03d}" for i in range(10)],
        revoke_rate=0.004, silent_rate=0.003, error_rate=0.003,
        repair_time=40.0, fault_duration=15.0,
        crash_times=(t_end / 2 + 0.5,))
    results = {}
    for pipeline in (False, True):
        sched = _sched()
        store = CheckpointStore(str(tmp_path / f"chaos_{pipeline}"))
        r = simulate(sched, make_workload(10, seed=seed + 1),
                     SimConfig(t_end=t_end, seed=2, pipeline=pipeline),
                     faults=plan, checkpoint=store, checkpoint_every=20)
        final = r.scheduler  # post-crash-restore instance
        # no stall: the tick train ran the full horizon
        assert r.iterations >= int(t_end) - 1
        # every revocation is accounted for in the audit trail
        n_lost = sum(1 for row in final.commit_log if row.status == "lost")
        statuses = {row.status for row in final.commit_log}
        assert statuses <= {"active", "completed", "failed", "lost"}
        results[pipeline] = (_commit_rows(final), _log_rows(final),
                             r.jct_per_job, r.calibration, n_lost)
    assert results[False] == results[True]


def test_checkpoint_refuses_meshed_scheduler():
    import dataclasses

    sched = _sched()
    object.__setattr__(sched, "config",
                       dataclasses.replace(sched.config, mesh=object()))
    with pytest.raises(ValueError, match="mesh"):
        pickle.dumps(sched)

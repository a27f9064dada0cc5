"""Device-resident settle (PR 5): batched multi-window WIS parity + fusion.

Pins the tentpole's correctness contract:

* the batched multi-window WIS op equals the per-window host ``wis_select``
  AND the O(2^M) brute-force oracle across padding/bucket boundaries, empty
  windows, all-masked lanes and touching half-open intervals (property
  tests use float32-exact interval/weight grids so the float32 device DP
  and the float64 host DP make bit-identical decisions);
* ``fixed_point_settle`` under every ``RoundSelector`` backend (host-batched
  "numpy", which is also the default, device "ref"/"pallas") is
  byte-identical to the per-window host loop (``wis_select``, passed
  explicitly as the independent reference), with and without work budgets,
  serial and pipelined;
* the fused score→clear dispatch (``wis_impl`` device backends consuming
  in-flight device scores) matches the host path, and the batched dispatch
  never retraces after its per-bucket warmup;
* the satellites: vectorized ``RoundFeedback`` assembly equals the object
  walk, and ``AgentConfig.n_start_offsets`` adds mutually-overlapping
  start alternatives while the default stays byte-identical.

Property tests run under hypothesis when available and fall back to seeded
random pools otherwise (hypothesis is not in the baked-in environment).
"""
import numpy as np
import pytest

from repro.core import (AgentConfig, JasdaScheduler, JobAgent, JobSpec,
                        Policy, ScoringPolicy, SimConfig, SliceSpec,
                        make_workload, simulate)
from repro.core.clearing import assign_bids, clear_round, settle_round
from repro.core.negotiation import build_feedback
from repro.core.negotiation.base import chunk_chain_bids
from repro.core.pipeline import pipelined_clear_rounds
from repro.core.policy import FairShare, GlobalAssignment, GreedyWIS
from repro.core.policy.base import _pool_members, fixed_point_settle
from repro.core.scheduler import SchedulerConfig
from repro.core.trp import fmp_standard
from repro.core.types import Variant, Window
from repro.core.wis import (RoundSelector, make_round_selector, wis_brute_force,
                            wis_select, wis_select_batch)

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAS_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional dependency
    HAS_HYPOTHESIS = False

GB = 1 << 30

ALL_IMPLS = ("numpy", "ref", "pallas")


def _variant(job, sid, t0, dur, h, *, work=None, vid=None):
    return Variant(
        job_id=job, slice_id=sid, t_start=t0, duration=dur,
        fmp=fmp_standard(1 * GB, 2 * GB, 0.1 * GB),
        local_utility=h, declared_features={},
        payload={"work": work if work is not None else dur},
        variant_id=vid or f"{job}/{sid}/{t0}")


def _grid_pool(rng, *, n_windows, lanes, masked_frac=0.2):
    """Padded (W, L) layout on a float32-exact grid (halves / 64ths)."""
    starts = rng.integers(0, 64, (n_windows, lanes)).astype(np.float64) / 2
    ends = starts + rng.integers(1, 32, (n_windows, lanes)) / 2
    weights = rng.integers(1, 64, (n_windows, lanes)).astype(np.float64) / 64
    valid = rng.random((n_windows, lanes)) > masked_frac
    return starts, ends, weights, valid


def _check_batch_matches_reference(seed):
    rng = np.random.default_rng(seed)
    n_windows = int(rng.integers(1, 6))
    lanes = int(rng.integers(1, 40))
    starts, ends, weights, valid = _grid_pool(rng, n_windows=n_windows,
                                              lanes=lanes)
    results = {
        impl: wis_select_batch(starts, ends, weights, valid, impl=impl)
        for impl in ALL_IMPLS
    }
    for k in range(n_windows):
        mask = valid[k]
        exp_sel, exp_total = wis_select(starts[k][mask], ends[k][mask],
                                        weights[k][mask])
        exp_set = set(int(i) for i in exp_sel)
        for impl, (sel, totals) in results.items():
            got = set(np.flatnonzero(sel[k][mask]).tolist())
            assert got == exp_set, (seed, impl, k)
            assert abs(totals[k] - exp_total) < 1e-9, (seed, impl, k)
        if mask.sum() and mask.sum() <= 12:
            _, bf_total = wis_brute_force(starts[k][mask], ends[k][mask],
                                          weights[k][mask])
            assert abs(exp_total - bf_total) < 1e-9, (seed, k)


def _check_settle_identity(seed, *, with_budget):
    """fixed_point_settle: every batched backend == the per-window loop."""
    rng = np.random.default_rng(seed)
    n_windows = int(rng.integers(2, 6))
    n_jobs = 6
    windows = [Window(f"s{k}", (4 + 2 * k) * GB, 0.0, 100.0)
               for k in range(n_windows)]
    pool = []
    m = int(rng.integers(10, 70))
    for i in range(m):
        w = windows[int(rng.integers(0, n_windows))]
        # float32-exact grid keeps the f32 device DP decision-identical
        t0 = float(rng.integers(0, 140)) / 2
        dur = float(rng.integers(4, 120)) / 2
        if t0 + dur > w.duration:
            dur = w.duration - t0
        if dur <= 0:
            continue
        pool.append(_variant(f"J{i % n_jobs}", w.slice_id, t0, dur,
                             float(rng.uniform(0.1, 0.9)), vid=f"v{i}"))
    budget = ({f"J{j}": float(rng.integers(60, 200)) for j in range(n_jobs)}
              if with_budget else None)
    fit, win_idx, view = assign_bids(windows, pool)
    # 12-bit grid: every partial DP sum stays float32-exact (see the
    # settle_throughput benchmark note), so f32/f64 decisions provably agree
    scores = rng.integers(1, 1 << 12, len(fit)).astype(np.float64) / (1 << 12)

    def run(selector):
        rr = fixed_point_settle(windows, fit, win_idx, scores,
                                selector=selector, work_budget=budget,
                                view=view)
        return ([tuple(v.variant_id for v in r.selected) for r in rr.results],
                rr.selected_idx, round(rr.total_score, 12), rr.n_conflicts)

    base = run(wis_select)
    for impl in ALL_IMPLS:
        assert run(make_round_selector(impl)) == base, (seed, impl)


# ---------------------------------------------------------------------------
# batched op == wis_select == brute force
# ---------------------------------------------------------------------------


def test_batch_matches_reference_seeded():
    for seed in range(25):
        _check_batch_matches_reference(seed)


def test_settle_identity_seeded():
    for seed in range(12):
        _check_settle_identity(seed, with_budget=bool(seed % 2))


if HAS_HYPOTHESIS:

    @settings(max_examples=40, deadline=None)
    @given(st.integers(0, 10 ** 6))
    def test_batch_matches_reference_property(seed):
        _check_batch_matches_reference(seed)

    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 10 ** 6), st.booleans())
    def test_settle_identity_property(seed, with_budget):
        _check_settle_identity(seed, with_budget=with_budget)


def test_batch_empty_and_fully_masked_windows():
    rng = np.random.default_rng(0)
    starts, ends, weights, valid = _grid_pool(rng, n_windows=4, lanes=16)
    valid[1, :] = False  # all-masked window
    valid[3, :] = False
    sel, totals = wis_select_batch(starts, ends, weights, valid, impl="numpy")
    for impl in ("ref", "pallas"):
        sel_i, totals_i = wis_select_batch(starts, ends, weights, valid,
                                           impl=impl)
        assert (sel_i == sel).all()
    assert not sel[1].any() and not sel[3].any()
    assert totals[1] == 0.0 and totals[3] == 0.0
    # zero windows / zero lanes degenerate shapes
    sel0, tot0 = wis_select_batch(np.zeros((0, 4)), np.zeros((0, 4)),
                                  np.zeros((0, 4)))
    assert sel0.shape == (0, 4) and tot0.shape == (0,)


def test_batch_touching_half_open_intervals():
    """The paper's worked example: (40,47) and (47,50) are both selected."""
    starts = np.array([[40.0, 47.0, 40.0]])
    ends = np.array([[47.0, 50.0, 50.0]])
    weights = np.array([[0.67, 0.64, 0.72]])
    for impl in ALL_IMPLS:
        sel, totals = wis_select_batch(starts, ends, weights, impl=impl)
        assert sel[0].tolist() == [True, True, False], impl
        assert abs(totals[0] - 1.31) < 1e-9


def test_batch_bucket_boundaries():
    """Lane counts straddling the pow2 buckets keep padding self-masking."""
    rng = np.random.default_rng(3)
    for lanes in (31, 32, 33, 63, 64, 65):
        starts, ends, weights, valid = _grid_pool(
            rng, n_windows=3, lanes=lanes, masked_frac=0.1)
        sel_np, _ = wis_select_batch(starts, ends, weights, valid, impl="numpy")
        sel_ref, _ = wis_select_batch(starts, ends, weights, valid, impl="ref")
        assert (sel_np == sel_ref).all(), lanes
        assert not (sel_np & ~valid).any(), lanes


def test_zero_weight_banning_equals_removal():
    """The retained-buffer ban trick: zero-weight lanes are never selected
    and leave the other lanes' DP values untouched."""
    rng = np.random.default_rng(4)
    starts, ends, weights, valid = _grid_pool(rng, n_windows=2, lanes=24,
                                              masked_frac=0.0)
    ban = rng.random((2, 24)) < 0.4
    # (a) remove banned lanes via the valid mask
    sel_removed, tot_removed = wis_select_batch(
        starts, ends, weights, ~ban, impl="numpy")
    # (b) keep them but zero their weights
    w0 = np.where(ban, 0.0, weights)
    sel_zeroed, _ = wis_select_batch(starts, ends, w0, None, impl="numpy")
    assert (sel_zeroed & ban).sum() == 0
    assert (sel_removed == (sel_zeroed & ~ban)).all()


# ---------------------------------------------------------------------------
# scheduler end-to-end: wis_impl backends byte-identical, serial + pipelined
# ---------------------------------------------------------------------------


def _slices():
    return [SliceSpec("s20", 20 * GB, n_chips=4),
            SliceSpec("s10", 10 * GB, n_chips=2),
            SliceSpec("s5", 5 * GB, n_chips=1)]


def _run_sched(wis_impl, *, pipeline=True, clearing=None, score_impl=None,
               selector=None):
    pol = Policy() if clearing is None else Policy(name="x", clearing=clearing)
    cfg = SchedulerConfig.from_policy(pol, wis_impl=wis_impl,
                                      score_impl=score_impl)
    sched = JasdaScheduler(_slices(), cfg)
    if selector is not None:
        sched._wis_selector = selector
    simulate(sched, make_workload(40, seed=3, arrival_rate=0.3),
             SimConfig(t_end=900.0, seed=2, pipeline=pipeline))
    return (
        [(r.t, r.n_selected, round(r.total_score, 9)) for r in sched.log],
        [(c.variant_id, c.slice_id, round(c.t_start, 9), round(c.score, 9))
         for c in sched.commit_log],
    )


def _run_reference(**kw):
    """The scheduler on the plain per-window ``wis_select`` loop: the
    reference every settle backend, the default included, is held to."""
    return _run_sched(None, selector=wis_select, **kw)


@pytest.mark.parametrize("impl", [None, *ALL_IMPLS])
def test_scheduler_byte_identical_under_wis_impl(impl):
    assert _run_sched(impl) == _run_reference()


@pytest.mark.parametrize("impl", [None, "numpy", "ref"])
def test_pipelined_equals_serial_under_device_selector(impl):
    serial = _run_sched(impl, pipeline=False)
    assert _run_sched(impl, pipeline=True) == serial
    assert serial == _run_reference(pipeline=False)


@pytest.mark.parametrize("clearing", [GlobalAssignment(), FairShare()])
def test_backends_identical_under_batched_selector(clearing):
    base = _run_reference(clearing=clearing)
    assert _run_sched("numpy", clearing=clearing) == base
    assert _run_sched(None, clearing=clearing) == base


def test_scheduler_fused_path_byte_identical():
    """Forced device scoring keeps the handle in flight, so the scheduler's
    predispatch (fused score→clear) actually runs — and must not change a
    single commit."""
    base = _run_reference(score_impl="ref")
    assert _run_sched("ref", score_impl="ref") == base
    assert _run_sched("ref", score_impl="ref", pipeline=False) == base


def _conflict_heavy_round():
    """5 windows, 8 jobs each bidding the same interval on two slices."""
    rng = np.random.default_rng(13)
    n_windows = 5
    windows = [Window(f"s{k}", (4 + 2 * k) * GB, 0.0, 100.0)
               for k in range(n_windows)]
    pool = []
    for i in range(90):
        j = i % 8
        t0 = float(rng.integers(0, 120)) / 2
        dur = float(rng.integers(8, 80)) / 2
        dur = min(dur, 100.0 - t0)
        if dur <= 0:
            continue
        for k in rng.choice(n_windows, size=2, replace=False):
            pool.append(_variant(f"J{j}", f"s{k}", t0, dur,
                                 float(rng.uniform(0.1, 0.9)),
                                 vid=f"J{j}/s{k}/v{len(pool)}"))
    return windows, pool


def test_global_assignment_lockstep_equals_serial():
    """Conflict-heavy pool: lockstep config-batch replays == host replays."""
    windows, pool = _conflict_heavy_round()
    ga = GlobalAssignment()
    base = clear_round(windows, pool, ScoringPolicy(), clearing=ga,
                       selector=wis_select)
    assert base.n_conflicts > 0  # the scenario must actually exercise replays
    for impl in ALL_IMPLS:
        rr = clear_round(windows, pool, ScoringPolicy(), clearing=ga,
                         wis_impl=impl)
        assert ([tuple(v.variant_id for v in r.selected) for r in rr.results]
                == [tuple(v.variant_id for v in r.selected)
                    for r in base.results]), impl
        assert abs(rr.total_score - base.total_score) < 1e-9


@pytest.mark.parametrize("clearing", [GreedyWIS(), GlobalAssignment(),
                                      FairShare()],
                         ids=["greedy", "global", "fairshare"])
def test_default_settle_equals_per_window_reference(clearing):
    """The default settle (the batched host selector) clears a
    conflict-heavy round exactly as the per-window ``wis_select`` loop:
    same selections in the same order, same fixed-point work."""
    windows, pool = _conflict_heavy_round()
    fit, win_idx, view = assign_bids(windows, pool)
    scores = np.random.default_rng(5).uniform(0.05, 1.0, len(fit))
    ages = {f"J{j}": j / 8 for j in range(8)}
    kw = dict(view=view, ages=ages)
    ref = clearing.settle(windows, fit, win_idx, scores, selector=wis_select,
                          **kw)
    got = clearing.settle(windows, fit, win_idx, scores, **kw)
    assert ref.n_conflicts > 0
    assert got.selected_idx == ref.selected_idx
    assert got.total_score == ref.total_score
    assert (got.n_passes, got.n_reclears) == (ref.n_passes, ref.n_reclears)
    # the per-window loop runs no batched DP; the default's batched share
    # is a part of its re-clears
    assert ref.n_reclears_vec == 0
    assert 0 <= got.n_reclears_vec <= got.n_reclears
    # the scheduler, clear_round and the pipeline all build it from None
    sel = make_round_selector(None)
    assert isinstance(sel, RoundSelector) and sel.impl == "numpy"
    sched = JasdaScheduler(_slices(), Policy(name="x", clearing=clearing))
    assert isinstance(sched._wis_selector, RoundSelector)
    assert sched.settle_backend == "numpy"


def test_small_row_reclears_take_the_scalar_branch(monkeypatch):
    """Eight windows of six candidates: the ban-free sweep carries enough
    lanes for the lane-major DP; the one conflict dirties a single window,
    whose re-clear runs the scalar DP from the retained pack."""
    windows = [Window(f"s{k}", 8 * GB, 0.0, 100.0) for k in range(8)]
    pool = [_variant(f"J{k}{i}", f"s{k}", 10.0 * i, 10.0, 0.5,
                     vid=f"s{k}/{i}")
            for k in range(8) for i in range(6)]
    # X bids [0, 10) on s0 and s1, beating both incumbents: it keeps s0
    pool += [_variant("X", "s0", 0.0, 10.0, 0.9, vid="x0"),
             _variant("X", "s1", 0.0, 10.0, 0.8, vid="x1")]
    fit, win_idx, view = assign_bids(windows, pool)
    scores = np.array([v.local_utility for v in fit])
    calls = []
    orig = RoundSelector._select_row_scalar

    def spy(packed, k, banned):
        calls.append(k)
        return orig(packed, k, banned)

    monkeypatch.setattr(RoundSelector, "_select_row_scalar",
                        staticmethod(spy))
    rr = GreedyWIS().settle(windows, fit, win_idx, scores, view=view)
    assert rr.n_conflicts == 1
    assert [v.variant_id for v in rr.results[1].selected][0] == "s1/0"
    assert calls == [1]
    assert (rr.n_reclears, rr.n_reclears_vec) == (9, 8)


# ---------------------------------------------------------------------------
# fused score→clear dispatch + zero retraces
# ---------------------------------------------------------------------------


def _stream_rounds(rng, specs):
    rounds = []
    for m, n_windows in specs:
        windows = [Window(f"s{k}", (10 + 2 * (k % 6)) * GB, 300.0 * k, 200.0)
                   for k in range(n_windows)]
        fmp = fmp_standard(1 * GB, 2 * GB, 0.2 * GB)
        pool = []
        for i in range(m):
            w = windows[int(rng.integers(0, n_windows))]
            t0 = w.t_min + float(rng.uniform(0, w.duration * 0.7))
            dur = float(rng.uniform(2.0, w.t_min + w.duration - t0))
            pool.append(Variant(
                job_id=f"J{i % 16}", slice_id=w.slice_id, t_start=t0,
                duration=dur, fmp=fmp,
                local_utility=float(rng.uniform(0.1, 0.9)),
                declared_features={}, payload={"work": dur},
                variant_id=f"J{i % 16}/v{i}"))
        rounds.append((windows, pool))
    return rounds


def test_fused_settle_matches_host_and_never_retraces():
    from repro.kernels.wis_dp import ops as wis_ops

    rng = np.random.default_rng(11)
    policy = ScoringPolicy()
    kw = dict(score_impl="ref", recheck_theta=0.5, grid=16)
    specs = [(400, 6), (520, 4), (380, 6), (450, 5)]
    rounds = _stream_rounds(rng, specs)
    serial = [clear_round(w, p, policy, **kw) for w, p in rounds]
    fused = pipelined_clear_rounds(rounds, policy, wis_impl="ref", **kw)
    assert ([[tuple(v.variant_id for v in r.selected) for r in rr.results]
             for rr in serial]
            == [[tuple(v.variant_id for v in r.selected) for r in rr.results]
                for rr in fused])
    # warm pass done above; a fresh stream over the same shape buckets must
    # hit the jit cache on every dispatch
    rounds2 = _stream_rounds(rng, specs)
    pipelined_clear_rounds(rounds2, policy, wis_impl="ref", **kw)  # warm new buckets if any
    rounds3 = _stream_rounds(rng, specs)
    base = wis_ops.trace_counts()
    pipelined_clear_rounds(rounds3, policy, wis_impl="ref", **kw)
    delta = {k: wis_ops.trace_counts()[k] - base[k] for k in base}
    assert sum(delta.values()) == 0, f"batched settle retraced: {delta}"


def test_transforming_backend_rides_fused_path():
    """FairShare transforms selection scores — since PR 6 the transform is
    threaded into the fused dispatch (``prefetch_transform``), so it DOES
    consume the prefetch, and a transformed prefetch is never handed to a
    raw-score first pass (nor vice versa)."""
    assert GreedyWIS.supports_prefetch
    assert GlobalAssignment.supports_prefetch
    assert FairShare.supports_prefetch
    # transform quantization contract: float32, 1 + age_weight·age
    from repro.core.types import PoolView

    view = PoolView.build(_stream_rounds(np.random.default_rng(0),
                                         [(12, 2)])[0][1])
    tr = FairShare(age_weight=0.5).prefetch_transform(
        view, {j: 0.6 for j in view.job_ids})
    assert tr.dtype == np.float32
    np.testing.assert_allclose(tr, np.float32(1.3))
    assert GreedyWIS().prefetch_transform(view, {}) is None


def test_transformed_prefetch_gating():
    """A transformed prefetch must only seed a transformed first pass:
    FairShare consumes its own prefetch; a raw prefetch handed to FairShare
    (or a transformed one to GreedyWIS) is recomputed, not honored."""
    from repro.core import wis as wis_mod
    from repro.core.wis import predispatch_settle

    rng = np.random.default_rng(13)
    # pool above SMALL_POOL_M so scoring dispatches on device (prefetchable)
    windows, pool = _stream_rounds(rng, [(420, 4)])[0]
    policy = ScoringPolicy()
    ages = {f"J{i}": (i % 5) / 4.0 for i in range(16)}
    calls = []
    orig = wis_mod.SettlePrefetch.materialize

    def spy(self, scores):
        calls.append(self.transformed)
        return orig(self, scores)

    try:
        wis_mod.SettlePrefetch.materialize = spy
        rr = clear_round(windows, pool, policy, ages=ages, wis_impl="ref",
                         clearing=FairShare())
        assert calls == [True]
        calls.clear()
        # cross-wired: transformed prefetch into a raw-score settle is
        # silently ignored (fixed_point_settle recomputes), still identical
        fit, win_idx, view = assign_bids(windows, pool)
        from repro.core.scoring import score_round_async
        selector = make_round_selector("ref")
        handle = score_round_async(fit, windows, win_idx, policy, ages=ages,
                                   view=view)
        wrong = predispatch_settle(selector, FairShare(), len(windows),
                                   win_idx, view, handle, ages=ages)
        base = settle_round(windows, fit, win_idx, handle.result(),
                            selector=selector, view=view, clearing=GreedyWIS())
        crossed = settle_round(windows, fit, win_idx, handle.result(),
                               selector=selector, view=view,
                               clearing=GreedyWIS(), prefetch=wrong)
        assert not calls  # transformed prefetch never materialized raw
        assert ([tuple(v.variant_id for v in r.selected) for r in base.results]
                == [tuple(v.variant_id for v in r.selected)
                    for r in crossed.results])
    finally:
        wis_mod.SettlePrefetch.materialize = orig
    # the honored FairShare round equals the host path byte-for-byte
    host = clear_round(windows, pool, policy, ages=ages,
                       clearing=FairShare())
    assert ([tuple(v.variant_id for v in r.selected) for r in rr.results]
            == [tuple(v.variant_id for v in r.selected)
                for r in host.results])
    assert rr.total_score == host.total_score


def test_custom_backend_signature_unchanged():
    """Backends with the pre-PR-5 settle signature still work through the
    scheduler (prefetch/selector forwarding is capability-gated)."""
    from dataclasses import dataclass

    from repro.core.policy import ClearingPolicy

    @dataclass(frozen=True)
    class OldStyle(ClearingPolicy):
        name = "old_style"

        def settle(self, windows, fit, win_idx, scores, *, selector=wis_select,
                   work_budget=None, view=None, ages=None):
            return fixed_point_settle(windows, fit, win_idx, scores,
                                      selector=selector,
                                      work_budget=work_budget, view=view)

    rng = np.random.default_rng(2)
    rounds = _stream_rounds(rng, [(120, 4)])
    windows, pool = rounds[0]
    rr = clear_round(windows, pool, ScoringPolicy(), clearing=OldStyle(),
                     wis_impl="ref", score_impl="ref")
    base = clear_round(windows, pool, ScoringPolicy(), clearing=GreedyWIS())
    assert ([tuple(v.variant_id for v in r.selected) for r in rr.results]
            == [tuple(v.variant_id for v in r.selected) for r in base.results])


# ---------------------------------------------------------------------------
# satellite: vectorized RoundFeedback assembly == the object walk
# ---------------------------------------------------------------------------


def test_vectorized_feedback_equals_object_walk(monkeypatch):
    import repro.core.negotiation.messages as msgs

    orig = msgs._build_feedback_vectorized
    calls = {"fast": 0}

    def spy(now, windows, agents, bids, rr, calibrator, view, win_idx):
        calls["fast"] += 1
        fast = orig(now, windows, agents, bids, rr, calibrator, view, win_idx)
        legacy = msgs.build_feedback(now, windows, agents, bids, rr,
                                     calibrator)  # no view → object walk
        assert fast == legacy
        return fast

    monkeypatch.setattr(msgs, "_build_feedback_vectorized", spy)
    sched = JasdaScheduler(_slices(), Policy())
    simulate(sched, make_workload(25, seed=5, arrival_rate=0.3),
             SimConfig(t_end=500.0, seed=2))
    assert calls["fast"] > 5  # the fast path actually ran


def test_feedback_falls_back_without_selected_idx():
    """RoundResults from backends that don't report pool indices (custom /
    pre-PR-5) still produce feedback via the object walk."""
    rng = np.random.default_rng(6)
    windows, pool = _stream_rounds(rng, [(40, 3)])[0]
    agents = []
    rr = clear_round(windows, pool, ScoringPolicy())
    import dataclasses

    stripped = dataclasses.replace(rr, selected_idx=())
    fit, win_idx, view = assign_bids(windows, pool)
    fb_stripped = build_feedback(0.0, windows, agents, [], stripped,
                                 view=view, win_idx=win_idx)
    fb_full = build_feedback(0.0, windows, agents, [], rr)
    assert fb_stripped.cutoffs == fb_full.cutoffs


# ---------------------------------------------------------------------------
# satellite: AgentConfig.n_start_offsets in chunk_chain_bids
# ---------------------------------------------------------------------------


def _agent(n_start_offsets=1, work=60.0):
    spec = JobSpec(job_id="J0", arrival_time=0.0, total_work=work,
                   fmp=fmp_standard(0.5 * GB, 2 * GB, 0.1 * GB))
    return JobAgent(spec, AgentConfig(n_start_offsets=n_start_offsets))


def test_start_offsets_default_is_byte_identical():
    w = Window("s0", 8 * GB, 10.0, 40.0)
    base = chunk_chain_bids(_agent(), w, 0.0)
    explicit = chunk_chain_bids(_agent(1), w, 0.0)
    assert [(v.variant_id, v.t_start, v.duration) for v in base] == \
        [(v.variant_id, v.t_start, v.duration) for v in explicit]


def test_start_offsets_add_overlapping_alternatives():
    # work << window span so the carrier chunk leaves room for shifted starts
    w = Window("s0", 8 * GB, 10.0, 40.0)
    base = chunk_chain_bids(_agent(work=15.0), w, 0.0)
    offs = chunk_chain_bids(_agent(3, work=15.0), w, 0.0)
    base_keys = {(v.t_start, v.duration) for v in base}
    extras = [v for v in offs if (v.t_start, v.duration) not in base_keys]
    assert extras, "n_start_offsets=3 must add shifted alternatives"
    for e in extras:
        # every shifted copy overlaps at least one unshifted sibling (WIS
        # keeps at most one per chain position → no double-committed work)
        assert any(e.t_start < b.t_end and b.t_start < e.t_end
                   for b in offs if (b.t_start, b.duration) in base_keys)
    # deterministic ids: regeneration produces the identical bid set
    again = chunk_chain_bids(_agent(3, work=15.0), w, 0.0)
    assert [(v.variant_id, v.t_start, v.duration) for v in offs] == \
        [(v.variant_id, v.t_start, v.duration) for v in again]


def test_start_offsets_flow_through_scheduler():
    """A population with start alternatives still clears consistently
    (serial == pipelined) and never over-commits a job's work."""
    sched_kw = dict(arrival_rate=0.4)

    def run(pipeline):
        sched = JasdaScheduler(_slices(), Policy())
        agents = make_workload(20, seed=7, **sched_kw)
        for a in agents:
            a.cfg = AgentConfig(n_start_offsets=3, strategy=a.cfg.strategy)
        simulate(sched, agents,
                 SimConfig(t_end=500.0, seed=2, pipeline=pipeline))
        return ([(r.t, r.n_selected, round(r.total_score, 9))
                 for r in sched.log],
                [(c.variant_id, round(c.t_start, 9)) for c in sched.commit_log])

    assert run(True) == run(False)

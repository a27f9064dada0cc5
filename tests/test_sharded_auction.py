"""Mesh-sharded auction rounds (PR 6): sharded == single-device, byte-wise.

The tentpole contract: partitioning a round over an auction mesh — the
pooled-bid axis of the scoring dispatch and the (W, L) window axis of the
batched WIS settle, both via ``shard_map`` — changes WHERE the round
computes, never WHAT it selects.  Cross-window conflict resolution stays
host-side and global, so the only device-side cross-shard exchange is the
replicated score gather of the fused settle.

Multi-device tests need virtual devices: run with
``JASDA_FORCE_HOST_DEVICES=8`` (see tests/conftest.py), which maps to
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.  On a single-device
session they skip; the mesh-builder and fallback tests always run.

Property tests run under hypothesis when available and fall back to seeded
random pools otherwise (hypothesis is not in the baked-in environment).
"""
import numpy as np
import pytest

import jax

from repro.core import (JasdaScheduler, Policy, ScoringPolicy, SimConfig,
                        SliceSpec, make_workload, simulate)
from repro.core.clearing import clear_round
from repro.core.pipeline import pipelined_clear_rounds
from repro.core.policy import FairShare, GlobalAssignment, GreedyWIS
from repro.core.scheduler import SchedulerConfig
from repro.core.trp import fmp_standard
from repro.core.types import Variant, Window
from repro.launch.mesh import AUCTION_AXIS, make_auction_mesh, mesh_chips

GB = 1 << 30

multi_device = pytest.mark.skipif(
    jax.local_device_count() < 2,
    reason="needs >1 device (set JASDA_FORCE_HOST_DEVICES=8)")

BACKENDS = [GreedyWIS(), GlobalAssignment(), FairShare(),
            FairShare(age_weight=0.0, spread=0.5)]


def _mk_round(rng, m, n_windows, n_jobs=23):
    """A random round on float32-exact grids (12-bit utilities, half-step
    intervals) so the f32 device DP and f64 host DP decide identically."""
    windows = [Window(f"s{k}", (6 + 2 * (k % 5)) * GB, 0.0, 100.0)
               for k in range(n_windows)]
    fmp = fmp_standard(1 * GB, 2 * GB, 0.1 * GB)
    pool = []
    for i in range(m):
        w = windows[int(rng.integers(0, n_windows))]
        t0 = float(rng.integers(0, 180)) / 2
        dur = float(rng.integers(2, 40)) / 2
        if t0 + dur > 100.0:
            dur = 100.0 - t0
        if dur <= 0:
            continue
        pool.append(Variant(
            job_id=f"J{i % n_jobs}", slice_id=w.slice_id, t_start=t0,
            duration=dur, fmp=fmp,
            local_utility=float(rng.integers(1, 1 << 12)) / (1 << 12),
            declared_features={}, payload={"work": dur}, variant_id=f"v{i}"))
    return windows, pool


def _sig(rr):
    """Byte-level round signature: per-window selections, scores, feedback
    inputs (selected_idx), totals."""
    return ([tuple(v.variant_id for v in r.selected) for r in rr.results],
            tuple(rr.scores), rr.selected_idx, rr.total_score, rr.n_conflicts)


# ---------------------------------------------------------------------------
# mesh builders (always run)
# ---------------------------------------------------------------------------


def test_auction_mesh_shape_and_axis():
    mesh = make_auction_mesh()
    assert mesh.axis_names == (AUCTION_AXIS,)
    n = mesh_chips(mesh)
    assert n & (n - 1) == 0  # power of two
    assert n <= jax.local_device_count()


def test_auction_mesh_clamps_to_pow2_floor():
    avail = jax.local_device_count()
    for req in (1, 2, 3, 5, 7, 8, 100):
        n = mesh_chips(make_auction_mesh(req))
        assert n & (n - 1) == 0
        assert n <= min(req, avail)


def test_production_mesh_degrades_without_raising():
    from repro.launch.mesh import make_production_mesh

    # CI boxes never have 256 chips — the builder must fall back, not raise
    mesh = make_production_mesh()
    assert mesh_chips(mesh) >= 1
    mesh = make_production_mesh(multi_pod=True)
    assert mesh_chips(mesh) >= 1


def test_row_spec_guard_falls_back_unsharded():
    from repro.distributed.sharding import (auction_row_spec, mesh_size,
                                            replicated_spec, spec_sharded)

    mesh = make_auction_mesh()
    n = mesh_size(mesh)
    assert mesh_size(None) == 1
    if n > 1:
        assert spec_sharded(auction_row_spec(mesh, 16 * n))
        # a dim the mesh does not divide degrades to replicated (guard_spec)
        assert not spec_sharded(auction_row_spec(mesh, 16 * n + 1))
    assert not spec_sharded(replicated_spec())


# ---------------------------------------------------------------------------
# sharded == single-device byte-identity (multi-device)
# ---------------------------------------------------------------------------


def _check_round_parity(seed, mesh, *, backend, wis_impl="ref",
                        pipelined=False):
    rng = np.random.default_rng(seed)
    # ragged M spanning: tiny (empty shards after padding), below/above the
    # SMALL_POOL_M device threshold, and window counts that leave some
    # windows empty / all-masked
    m = int(rng.choice([3, 40, 257, 900, 2100]))
    n_windows = int(rng.integers(1, 12))
    ages = {f"J{i}": (i % 7) / 6.0 for i in range(23)}
    policy = ScoringPolicy()
    if pipelined:
        rounds = [_mk_round(rng, m, n_windows) for _ in range(3)]
        serial = [clear_round(w, p, policy, ages=ages, clearing=backend,
                              wis_impl=wis_impl) for w, p in rounds]
        sharded = pipelined_clear_rounds(rounds, policy, ages=ages,
                                         clearing=backend, wis_impl=wis_impl,
                                         mesh=mesh)
        assert [_sig(a) for a in serial] == [_sig(b) for b in sharded]
    else:
        windows, pool = _mk_round(rng, m, n_windows)
        base = clear_round(windows, pool, policy, ages=ages, clearing=backend,
                           wis_impl=wis_impl)
        shard = clear_round(windows, pool, policy, ages=ages,
                            clearing=backend, wis_impl=wis_impl, mesh=mesh)
        assert _sig(base) == _sig(shard)


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAS_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional dependency
    HAS_HYPOTHESIS = False


if HAS_HYPOTHESIS:

    @multi_device
    @pytest.mark.parametrize("backend", BACKENDS, ids=lambda b: b.name)
    @given(seed=st.integers(0, 10_000))
    @settings(max_examples=20, deadline=None)
    def test_sharded_round_byte_identical_prop(backend, seed):
        _check_round_parity(seed, make_auction_mesh(), backend=backend)

else:

    @multi_device
    @pytest.mark.parametrize("backend", BACKENDS, ids=lambda b: b.name)
    def test_sharded_round_byte_identical_seeded(backend):
        mesh = make_auction_mesh()
        for seed in range(8):
            _check_round_parity(seed, mesh, backend=backend)


@multi_device
@pytest.mark.parametrize("backend", [GreedyWIS(), FairShare()],
                         ids=lambda b: b.name)
def test_sharded_pipelined_equals_serial_unsharded(backend):
    mesh = make_auction_mesh()
    for seed in (5, 17):
        _check_round_parity(seed, mesh, backend=backend, pipelined=True)


@multi_device
def test_sharded_empty_and_all_masked_windows():
    """Rounds where some shards see only padding and some windows clear
    empty must match unsharded exactly (including the empty results)."""
    mesh = make_auction_mesh()
    rng = np.random.default_rng(0)
    policy = ScoringPolicy()
    # 2 bids across 9 windows: most windows all-masked, most shards empty
    windows, pool = _mk_round(rng, 2, 9)
    base = clear_round(windows, pool, policy, wis_impl="ref")
    shard = clear_round(windows, pool, policy, wis_impl="ref", mesh=mesh)
    assert _sig(base) == _sig(shard)
    assert len(base.results) == 9


@multi_device
def test_odd_mesh_falls_back_identically():
    """A hand-built non-pow2 mesh cannot divide pow2 buckets — the guard
    degrades every dispatch to unsharded, with identical results."""
    if jax.local_device_count() < 3:
        pytest.skip("needs 3 devices")
    odd = jax.make_mesh((3,), (AUCTION_AXIS,), devices=jax.devices()[:3])
    rng = np.random.default_rng(4)
    windows, pool = _mk_round(rng, 700, 5)
    base = clear_round(windows, pool, ScoringPolicy(), wis_impl="ref")
    shard = clear_round(windows, pool, ScoringPolicy(), wis_impl="ref",
                        mesh=odd)
    assert _sig(base) == _sig(shard)


@multi_device
def test_scheduler_mesh_knob_byte_identical():
    """SchedulerConfig.mesh: full simulated auction (pipelined) sharded ==
    single-device, across logs and commit logs."""

    def run(mesh):
        cfg = SchedulerConfig.from_policy(
            Policy(), wis_impl="ref", score_impl="ref")
        import dataclasses

        cfg = dataclasses.replace(cfg, mesh=mesh)
        sched = JasdaScheduler(
            [SliceSpec("s20", 20 * GB, n_chips=4),
             SliceSpec("s10", 10 * GB, n_chips=2)], cfg)
        simulate(sched, make_workload(30, seed=3, arrival_rate=0.3),
                 SimConfig(t_end=600.0, seed=2, pipeline=True))
        return ([(r.t, r.n_selected, round(r.total_score, 9))
                 for r in sched.log],
                [(c.variant_id, c.slice_id, round(c.t_start, 9),
                  round(c.score, 9)) for c in sched.commit_log])

    assert run(None) == run(make_auction_mesh())


@multi_device
def test_large_round_sharded_equivalence_and_zero_retrace():
    """The headline contract at scale: an 8-way (or what the session has)
    sharded round at M ≥ 1e5 is byte-identical to single-device, and a
    second same-bucket round retraces NOTHING (one executable per pow2
    bucket per mesh shape)."""
    from repro.kernels.jasda_score import ops as score_ops
    from repro.kernels.wis_dp import ops as wis_ops

    mesh = make_auction_mesh(8)
    rng = np.random.default_rng(100)
    policy = ScoringPolicy()
    windows, pool = _mk_round(rng, 1 << 17, 24, n_jobs=101)
    assert len(pool) >= 100_000
    base = clear_round(windows, pool, policy, wis_impl="ref")
    shard = clear_round(windows, pool, policy, wis_impl="ref", mesh=mesh)
    assert _sig(base) == _sig(shard)

    # same pow2 bucket, different M / different data → zero retraces
    windows2, pool2 = _mk_round(rng, (1 << 17) - 4097, 24, n_jobs=101)
    before = (score_ops.trace_counts(), wis_ops.trace_counts())
    base2 = clear_round(windows2, pool2, policy, wis_impl="ref")
    shard2 = clear_round(windows2, pool2, policy, wis_impl="ref", mesh=mesh)
    assert _sig(base2) == _sig(shard2)
    after = (score_ops.trace_counts(), wis_ops.trace_counts())
    assert after == before, f"retraced: {before} -> {after}"


@multi_device
@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_sharded_scores_trim_to_an_unaligned_pool(impl):
    """trim=True slices a sharded score array back to a pool size no shard
    boundary matches; the auction mesh's Auto axes keep that a plain slice."""
    from repro.kernels.jasda_score.ops import score_variants

    rng = np.random.default_rng(5)
    m, t = 1000, 8
    args = (rng.uniform(0, 1, (m, 1)), rng.uniform(0, 1, (m, 4)),
            np.ones(1), np.full(4, 0.2), rng.uniform(1, 3, (m, t)),
            rng.uniform(0.1, 0.5, (m, t)))
    kw = dict(lam=0.5, capacity=2.5, theta=0.05, impl=impl)
    one = score_variants(*args, **kw)
    shard = score_variants(*args, mesh=make_auction_mesh(8), **kw)
    assert np.asarray(shard[0]).shape == (m,)
    for a, b in zip(one, shard):
        if a is not None:
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()

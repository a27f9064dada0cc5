#!/usr/bin/env python3
"""Chip smoke test: the JASDA auction loop on one TPU v5e, on its kernels.

Drives ``JasdaService`` — the engine behind ``repro.launch.serve_auction``
— through announce → bid → score → settle → commit on a v5e-256 pod
inventory (256 chips cut into the published v5e slice shapes of 1, 4, 8,
16, 32 and 64 chips, 16 GiB of HBM per chip) under seeded open-loop
Poisson arrivals, with a backlog that pools ≥ 2¹⁵ bids over ≥ 64 windows
a round.  Configurations, same seed each:

  (a) the default SchedulerConfig (device scoring, batched host settle);
  (b) wis_impl="pallas": fused score → settle, both Pallas kernels; its
      awards must equal (a)'s;
  (c) the host reference, score_impl="numpy" with wis_impl="numpy".

It then checks one full-size packed round: Pallas scores against the
float64 host scorer (``score_variants_numpy``), and Pallas settles at
row widths on both sides of the SMEM/VMEM layout switch against the host
DP.  Any failed check exits non-zero; so does a run where JAX finds no
TPU.  The last line of stdout is the JSON result and nothing else.

    python chip_smoke.py [--seed 0] [--rounds 20]
    python chip_smoke.py --chips 4   # only the mesh-sharded round, 4 chips

The ``--chips 4`` phase scores and settles one round of 2²⁰ bids sharded
over a 4-chip auction mesh and compares it byte for byte with the same
round on one device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

GIB = 1 << 30
HBM_PER_CHIP = 16 * GIB  # TPU v5e: 16 GiB of HBM per chip (Cloud TPU docs)
#: (chips per slice, slices): a v5e-256 pod in published slice shapes
POD_SLICES = ((64, 1), (32, 1), (16, 2), (8, 4), (4, 8), (1, 64))
WARMUP_ROUNDS = 4
#: job work units: small enough that one award per window per round books
#: a 1-chip slice well short of the 1000 s announce horizon over the run
WORK_RANGE = (4.0, 40.0)
MIN_BIDS, MIN_WINDOWS = 1 << 15, 64
#: Poisson arrivals per round, admitted by a token bucket at about one award
#: per window per round (so the backlog stays level) with a 128-job burst
ARRIVAL_RATE, ADMIT_RATE, ADMIT_BURST = 200.0, 80.0, 128.0
#: largest pow2 score bucket: a round pools up to ~44K bids
MAX_BUCKET_M = 1 << 16
#: float32 kernel vs float64 host scorer: the scores are ≤ 1 sums of a few
#: products, so float32 rounding stays far below this
SCORE_ATOL = 1e-5
#: eligibility is compared only where float64 p_exceed is this far from θ
THETA_MARGIN = 1e-4


class CheckFailed(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    print(f"  ok: {what}", flush=True)


def pod_inventory():
    from repro.core import SliceSpec

    return [SliceSpec(f"v5e-{chips}x{i}", chips * HBM_PER_CHIP, n_chips=chips)
            for chips, count in POD_SLICES for i in range(count)]


def warm_seconds(fn, n=5):
    """Median wall seconds of ``n`` calls after a first (compiling) call,
    each ending in ``block_until_ready`` — the host clock around the
    dispatch, not a device trace."""
    import jax

    jax.block_until_ready(fn())
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return sorted(times)[n // 2]


class CompileCounter:
    """Compile seconds and persistent-cache hits/misses, from jax.monitoring."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def run_service(name, sched_cfg, args, counter):
    """One configuration through JasdaService; returns its summary dict."""
    from repro.core import JasdaScheduler
    from repro.service import (JasdaService, PoissonArrivals, ServiceConfig,
                               TokenBucket)

    sched = JasdaScheduler(pod_inventory(), config=sched_cfg)
    arrivals = PoissonArrivals(ARRIVAL_RATE, seed=args.seed,
                               work_range=WORK_RANGE)
    # ticks at t = 0, 1, ..., n: the first arrivals land in (0, 1], so the
    # t = 0 tick announces to nobody and rounds 1..n carry bids
    cfg = ServiceConfig(round_dt=1.0, t_end=float(WARMUP_ROUNDS + args.rounds),
                        seed=args.seed, max_bucket_m=MAX_BUCKET_M)
    svc = JasdaService(sched, arrivals, config=cfg,
                       admission=TokenBucket(ADMIT_RATE, burst=ADMIT_BURST))
    tick_wall = []
    on_tick = svc._on_tick

    def timed_tick(now, horizon, pipe):
        t0 = time.perf_counter()
        on_tick(now, horizon, pipe)
        tick_wall.append(time.perf_counter() - t0)

    svc._on_tick = timed_tick
    c0 = counter.seconds
    t0 = time.perf_counter()
    stats = svc.run()
    wall = time.perf_counter() - t0
    rows = [r for r in sched.log if r.n_bids]
    steady = rows[WARMUP_ROUNDS:]
    steady_wall = tick_wall[1 + WARMUP_ROUNDS:]
    backends = sorted({(r.score_backend, r.settle_backend) for r in rows})
    out = {
        "config": name,
        "rounds": len(rows),
        "bids_min": min(r.n_bids for r in steady) if steady else 0,
        "bids_max": max(r.n_bids for r in steady) if steady else 0,
        "windows_min": min(r.n_windows for r in steady) if steady else 0,
        "awards": stats.n_awards,
        "round_s_mean": sum(steady_wall) / max(1, len(steady_wall)),
        "round_s_max": max(steady_wall, default=0.0),
        "wall_s": wall,
        "compile_s": counter.seconds - c0,
        "backends": backends,
        "failed_backends": sched.backend_health.failed_backends(),
    }
    print(f"[{name}] " + json.dumps(out), flush=True)
    award_log = [(a.round, a.variant_id, a.slice_id) for a in svc.award_log]
    return out, award_log, sched


def packed_round(sched, now):
    """One full-size pooled round of the live scheduler, packed with grids."""
    import numpy as np

    from repro.core.clearing import assign_bids
    from repro.core.negotiation import WindowAnnouncement
    from repro.core.windows import announce_windows
    from repro.kernels.jasda_score.ops import pool_to_arrays_round

    windows = announce_windows(sched.slices, now, sched.policy.window)
    chips = {sid: tl.spec.n_chips for sid, tl in sched.slices.items()}
    ann = WindowAnnouncement(now=now, windows=tuple(windows), chips=chips)
    pool = [v for a in sched.agents.values()
            for group in a.respond(ann).by_window for v in group]
    fit, win_idx, view = assign_bids(windows, pool)
    h = np.asarray([v.local_utility for v in fit])
    thetas = np.asarray([v.theta for v in fit])
    return pool_to_arrays_round(fit, windows, win_idx, sched.policy.scoring,
                                h=h, pack_grids=True, theta=thetas, view=view)


def score_parity(packed, lam, seed):
    """Pallas scores vs the float64 host scorer on one packed round.

    The round's own capacities (≥ 16 GiB) clear every job (2–12 GiB), so
    capacities are redrawn from the seed over 2–14 GiB: many bids then
    fail θ and the safety reduction is exercised.
    """
    import numpy as np

    from repro.kernels.jasda_score.ops import (score_variants,
                                               score_variants_numpy)

    m = packed.fj.shape[0]
    caps = np.random.default_rng(seed).uniform(2.0, 14.0, m) * GIB
    kw = dict(lam=lam, capacity=caps, theta=packed.thetas)
    args = (packed.fj, packed.fs, packed.alphas, packed.betas, packed.mu,
            packed.sg)
    s_p, e_p, _ = score_variants(*args, impl="pallas", **kw)
    s_p, e_p = np.asarray(s_p, np.float64), np.asarray(e_p)
    dispatch_s = warm_seconds(
        lambda: score_variants(*args, impl="pallas", **kw)[0])
    s_n, e_n, p_n = score_variants_numpy(*args, **kw)
    away = np.abs(p_n - packed.thetas) > THETA_MARGIN
    err = float(np.max(np.abs(s_p - s_n)[away])) if away.any() else 0.0
    op_bytes = sum(np.asarray(a).size * 4 for a in args) + 3 * m * 4
    print(f"  score parity: M={m} T={packed.mu.shape[1]} "
          f"operand bytes={op_bytes} eligible={int(e_n.sum())} "
          f"away_from_theta={int(away.sum())} max|pallas-numpy|={err:.3e} "
          f"dispatch+wait={dispatch_s * 1e3:.3f} ms", flush=True)
    check(bool(np.array_equal(e_p[away], e_n[away])),
          "pallas eligibility == numpy eligibility away from theta")
    check(err <= SCORE_ATOL, f"pallas scores within {SCORE_ATOL} of numpy")
    check(0 < int(e_n.sum()) < m, "the round has eligible and ineligible bids")


def settle_parity(seed):
    """Pallas settle vs the host DP on both sides of the SMEM/VMEM switch."""
    import numpy as np

    from repro.core.wis import _batch_dp_backtrack_numpy
    from repro.kernels.wis_dp.kernel import SMEM_MAX_LANES
    from repro.kernels.wis_dp.ops import wis_settle_batch

    rng = np.random.default_rng(seed)
    for rows, lanes in ((128, 1024), (256, 4096), (2, 2 * SMEM_MAX_LANES)):
        # integer grid times and weights in 64ths: every DP sum (< 2^18 at
        # these sizes) stays exact in float32, so the float32 device DP and
        # the float64 host DP decide identically
        ends = np.sort(rng.integers(1, 4 * lanes, (rows, lanes)), axis=1)
        starts = ends - rng.integers(1, 64, (rows, lanes))
        w = rng.integers(0, 64, (rows, lanes)) / 64.0
        pred = np.stack([np.searchsorted(ends[k], starts[k], side="right")
                         for k in range(rows)]).astype(np.int32)
        w32 = w.astype(np.float32)
        sel, _ = wis_settle_batch(w32, pred, impl="pallas")
        ref = _batch_dp_backtrack_numpy(w, pred)
        run_s = warm_seconds(lambda: wis_settle_batch(w32, pred, impl="pallas"))
        check(bool(np.array_equal(np.asarray(sel), ref)),
              f"pallas settle == host DP at (W, L) = ({rows}, {lanes}); "
              f"dispatch+wait {run_s * 1e3:.3f} ms")


def one_chip(args, counter):
    from repro.core.scheduler import SchedulerConfig

    runs = {}
    for name, cfg in (
            ("a_default", SchedulerConfig()),
            ("b_pallas", SchedulerConfig(wis_impl="pallas")),
            ("c_host", SchedulerConfig(score_impl="numpy", wis_impl="numpy"))):
        runs[name] = run_service(name, cfg, args, counter)
    for name, (out, awards, _) in runs.items():
        check(out["awards"] > 0, f"{name}: awards > 0")
        check(not out["failed_backends"], f"{name}: no backend marked failed")
        check(out["rounds"] >= WARMUP_ROUNDS + args.rounds,
              f"{name}: {out['rounds']} rounds")
        check(out["bids_min"] >= MIN_BIDS and out["windows_min"] >= MIN_WINDOWS,
              f"{name}: >= {MIN_BIDS} bids over >= {MIN_WINDOWS} windows "
              f"every round after warm-up")
    check(runs["b_pallas"][0]["backends"] == [("pallas", "pallas")],
          "(b) scored and settled on pallas in every round")
    check(runs["a_default"][0]["backends"] == [("pallas", "numpy")],
          "(a) scored on pallas, settled by the batched host settle")
    check(runs["c_host"][0]["backends"] == [("numpy", "numpy")],
          "(c) scored and settled on the host")
    check(runs["b_pallas"][1] == runs["a_default"][1],
          "(b) awards == (a)'s, settled on the host")

    sched = runs["b_pallas"][2]
    packed = packed_round(sched, sched.log[-1].t + 1.0)
    score_parity(packed, sched.policy.scoring.lam, args.seed)
    settle_parity(args.seed)


#: the sharded round: 2²⁰ bids over 256 windows of 4096 lanes, T = 32
MESH_ROUND = dict(m=1 << 20, rows=256, lanes=4096, t=32)


def four_chips(args):
    """Mesh-sharded round at M = 2²⁰ vs the same round on one device."""
    import jax
    import numpy as np

    from repro.kernels.jasda_score.ops import score_variants
    from repro.kernels.wis_dp.ops import wis_settle_fused
    from repro.launch.mesh import make_auction_mesh

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices")
    mesh = make_auction_mesh(4)
    rng = np.random.default_rng(args.seed)
    m, rows, lanes, t = (MESH_ROUND[k] for k in ("m", "rows", "lanes", "t"))
    f32 = np.float32
    score_args = (rng.uniform(0, 1, (m, 1)).astype(f32),
                  rng.uniform(0, 1, (m, 4)).astype(f32),
                  np.ones(1, f32), np.array([.4, .2, .1, .2], f32),
                  rng.uniform(2, 12, (m, t)).astype(f32),
                  rng.uniform(0.01, 1.0, (m, t)).astype(f32))
    kw = dict(lam=0.5, capacity=rng.uniform(6, 16, m).astype(f32),
              theta=np.full(m, 0.05, f32), impl="pallas", trim=False)
    # every bid in exactly one window lane: a random permutation of the pool
    idx = rng.permutation(m).reshape(rows, lanes).astype(np.int32)
    ends = np.sort(rng.integers(1, 4 * lanes, (rows, lanes)), axis=1)
    starts = ends - rng.integers(1, 64, (rows, lanes))
    pred = np.stack([np.searchsorted(ends[k], starts[k], side="right")
                     for k in range(rows)]).astype(np.int32)
    mask = np.ones((rows, lanes), bool)

    def round_(mesh_):
        t0 = time.perf_counter()
        scores, elig, _ = score_variants(*score_args, mesh=mesh_, **kw)
        sel, tot = wis_settle_fused(scores, idx, mask, pred, impl="pallas",
                                    mesh=mesh_)
        jax.block_until_ready((scores, sel, tot))
        return (scores, elig, sel, tot), time.perf_counter() - t0

    single, t_single = round_(None)
    sharded, t_sharded = round_(mesh)
    _, t_single2 = round_(None)
    _, t_sharded2 = round_(mesh)
    print(f"  M={m} W={rows} L={lanes}: one device {t_single2:.4f}s "
          f"(first {t_single:.3f}s), 4-chip mesh {t_sharded2:.4f}s "
          f"(first {t_sharded:.3f}s)", flush=True)
    names = ("scores", "eligible", "selection", "totals")
    for name, a, b in zip(names, single, sharded):
        check(np.asarray(a).tobytes() == np.asarray(b).tobytes(),
              f"sharded {name} byte-identical to one device")
    for name, b in zip(names, sharded):
        devs = {s.device for s in b.addressable_shards}
        check(len(devs) == 4, f"sharded {name} placed on 4 devices")
    check(int(np.asarray(sharded[2]).sum()) > 0, "the sharded round selects")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rounds", type=int, default=20,
                    help="measured rounds after the warm-up rounds")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    import jax

    from repro.kernels.common import use_interpret
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"error: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    print(f"device: {dev.device_kind} x{len(devices)}  jax {jax.__version__}"
          f"  compile cache: {cache_dir}", flush=True)
    counter = CompileCounter()
    try:
        check(not use_interpret(), "Pallas kernels compiled for the chip, "
              "not interpreted")
        if args.chips == 4:
            four_chips(args)
        else:
            one_chip(args, counter)
    except CheckFailed as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(f"compile: {counter.seconds:.2f}s  persistent cache hits="
          f"{counter.hits} misses={counter.misses}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

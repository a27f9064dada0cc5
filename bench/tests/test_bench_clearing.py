"""The reference's clearing equals the program's on conflict-heavy pools.

Synthetic rounds: jobs bid overlapping chunks on several slices, so the
first WIS pass leaves many cross-window conflicts and the work budgets
bind; the program's GreedyWIS and GlobalAssignment settle them on the host
loop, the reference clears the same scores, and every window's selection
must match.  Sizes cover both of GlobalAssignment's searches: all
combinations (<= 64) and the Hungarian seed with coordinate descent.
"""
import numpy as np
import pytest

import reference
from repro.core.policy import GlobalAssignment, GreedyWIS
from repro.core.trp import fmp_static
from repro.core.types import Variant, Window

FMP = fmp_static(1.0)


def _round(seed, n_jobs, n_slices):
    rng = np.random.default_rng(seed)
    windows = [Window(f"s{k}", 1.0, 0.0, 40.0) for k in range(n_slices)]
    fit, win_idx = [], []
    for j in range(n_jobs):
        for k in rng.choice(n_slices, size=min(n_slices, 3), replace=False):
            t = float(rng.integers(0, 30))
            for c in range(int(rng.integers(1, 4))):
                d = float(rng.integers(2, 8))
                if t + d > 40:
                    break
                fit.append(Variant(job_id=f"j{j}", slice_id=f"s{k}",
                                   t_start=t, duration=d, fmp=FMP,
                                   local_utility=0.5,
                                   payload={"work": d},
                                   variant_id=f"j{j}/s{k}#{c}"))
                win_idx.append(int(k))
                t += d
    scores = rng.uniform(0.05, 1.0, len(fit)).round(3)
    budget = {f"j{j}": float(rng.integers(4, 20)) for j in range(n_jobs)}
    return windows, fit, np.asarray(win_idx), scores, budget


def _record(windows, fit, win_idx, scores, budget, rr):
    jobs = {v.job_id for v in fit}
    return {"now": 0.0, "windows": windows, "fit": fit, "win_idx": win_idx,
            "budget": budget, "trust": {j: (1.0, 0.5) for j in jobs},
            "last_selected": {j: 0.0 for j in jobs}, "scores": scores,
            "result": rr, "n_bids": len(fit)}


CASES = [(seed, n_jobs, n_slices) for seed in range(6)
         for n_jobs, n_slices in ((3, 3), (6, 4), (25, 8))]


@pytest.mark.parametrize("seed,n_jobs,n_slices", CASES)
@pytest.mark.parametrize("kind", ["greedy_wis", "global_assignment"])
def test_reference_clearing_matches_program(kind, seed, n_jobs, n_slices):
    windows, fit, win_idx, scores, budget = _round(seed, n_jobs, n_slices)
    policy = GreedyWIS() if kind == "greedy_wis" else GlobalAssignment()
    rr = policy.settle(windows, fit, win_idx, scores, work_budget=budget)
    config = {"clearing": kind, "global_assignment": {
        "max_configs": 64, "descent_passes": 2, "max_evals": 200}}
    r = reference.Round.from_record(
        _record(windows, fit, win_idx, scores, budget, rr))
    c = reference.clear(r, scores, config)
    for k, res in enumerate(rr.results):
        assert {v.variant_id for v in res.selected} == \
            {r.vid[i] for i in c.selected[k]}
    assert rr.n_conflicts > 0 or n_jobs == 3

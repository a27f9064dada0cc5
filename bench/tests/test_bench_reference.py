"""The plain reference: small-size agreement with the program's host path,
WIS against brute force, and a bfloat16 control that fails the check."""
import itertools
import time

import numpy as np
import pytest

import harness
import reference
from bench_cells import tiny_cell


def _wis_brute(s, e, w):
    best, best_set = 0.0, ()
    for r in range(len(s) + 1):
        for c in itertools.combinations(range(len(s)), r):
            if all(not (s[i] < e[j] and s[j] < e[i])
                   for i, j in itertools.combinations(c, 2)):
                tot = sum(w[i] for i in c)
                if tot > best + 1e-12:
                    best, best_set = tot, c
    return best


@pytest.mark.parametrize("seed", range(20))
def test_wis_is_optimal(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 10))
    s = rng.integers(0, 20, n).astype(float)
    e = s + rng.integers(1, 8, n)
    w = rng.uniform(0, 1, n)
    sel = reference.wis(s, e, w)
    assert all(not (s[i] < e[j] and s[j] < e[i])
               for i, j in itertools.combinations(sel, 2))
    assert sum(w[i] for i in sel) == pytest.approx(_wis_brute(s, e, w))


def test_memory_profile_grid_and_safety():
    mix = {"jobs": {"fmp": {"base": 0.3, "burst": 0.1, "rel_sigma": 0.03,
                            "warmup_frac": 0.1, "burst_frac": 0.05}}}
    gib = float(1 << 30)
    job = reference.JobMemory.from_draw(8 * gib, mix)
    mu, sd = job.grid(20)
    # cell midpoints 0.025, 0.075 ramp; 0.975 is the spike
    assert mu[0] == pytest.approx((0.3 + 0.025 / 0.1 * 0.7) * 8 * gib)
    assert mu[10] == pytest.approx(8 * gib)
    assert mu[-1] == pytest.approx(8.8 * gib)
    assert sd[-1] == pytest.approx(0.03 * 8.8 * gib)
    assert job.p_exceed(16 * gib) < 1e-12
    assert job.p_exceed(8.5 * gib) > 0.5


@pytest.mark.parametrize("name", ["migdgx-steady", "pod256-steady",
                                  "pod256-steady+burst"])
def test_reference_agrees_and_control_fails(name):
    cell = tiny_cell(name)
    run = harness.drive(cell, 20240917, 1.0, trace=False,
                        t_proc0=time.perf_counter(), require_tpu=False)
    assert run.sampled, "no round was sampled"
    got = harness.check_outputs(run)
    assert all(c["value"] <= c["limit"] for c in got.values()), got
    assert got["score_gap"]["value"] < 1e-6
    ctl = harness.check_outputs(run, control=True)
    assert ctl["score_gap"]["value"] > ctl["score_gap"]["limit"], ctl


def test_no_sampled_round_is_not_correct():
    got = reference.check_rounds([], {"clearing": "greedy_wis"}, {})
    assert got["score_gap"]["value"] > got["score_gap"]["limit"]

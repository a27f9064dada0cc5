"""The traffic generator: the same seed gives the same stream, and every
seed gets the same jobs and gaps in another order."""
import copy

import numpy as np
import pytest

import harness
import traffic

BLOCK = 16


def _mix(**arrivals):
    mix = copy.deepcopy(harness.resolve_cell("pod256-steady").mix)
    mix["stream"]["block"] = BLOCK
    mix["arrivals"].update(arrivals)
    return mix


def _pull(mix, seed, t_end):
    stream = traffic.JobStream(mix, np.random.default_rng(seed))
    events = stream.take_until(t_end)
    return stream, events


def test_same_seed_gives_the_same_stream():
    mix = _mix()
    a, ev_a = _pull(mix, 7, 20.0)
    b, ev_b = _pull(mix, 7, 20.0)
    assert [type(e).__name__ for e in ev_a] == [type(e).__name__ for e in ev_b]
    assert a.jobs == b.jobs and len(a.jobs) > BLOCK


def test_seeds_permute_one_set_of_jobs_and_gaps():
    mix = _mix()
    n = 4 * BLOCK
    draws = {}
    for seed in (1, 2):
        stream = traffic.JobStream(mix, np.random.default_rng(seed))
        while stream.n_emitted < n + 1:
            stream.take_until(stream._last_t + 50.0)
        jobs = list(stream.jobs.values())[:n]
        gaps = np.diff([0.0] + [j.t for j in jobs])
        draws[seed] = (jobs, gaps)
    (j1, g1), (j2, g2) = draws[1], draws[2]
    traits = [sorted((j.work, j.steady_bytes, j.deadline is None)
                     for j in js) for js in (j1, j2)]
    assert traits[0] == traits[1]
    assert sorted(g1) == pytest.approx(sorted(g2))
    assert [j.work for j in j1] != [j.work for j in j2]


def test_the_stream_does_not_depend_on_how_often_it_is_pulled():
    """One pull to t = 40 and forty pulls of one round give the same jobs at
    the same simulated times, so a faster system sees the same stream."""
    mix = _mix()
    one, ev_one = _pull(mix, 11, 40.0)
    many = traffic.JobStream(mix, np.random.default_rng(11))
    ev_many = []
    for t in range(1, 41):
        ev_many += many.take_until(float(t))
    assert one.jobs == many.jobs and len(one.jobs) > BLOCK
    assert [(type(e).__name__, e.t) for e in ev_one] == \
        [(type(e).__name__, e.t) for e in ev_many]


def test_arrivals_come_at_the_mix_rate():
    mix = _mix(rate=4.0)
    stream, _ = _pull(mix, 5, 500.0)
    assert len(stream.jobs) / 500.0 == pytest.approx(4.0, rel=0.1)

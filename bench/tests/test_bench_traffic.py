"""The traffic generator: the same seed gives the same stream, and every
seed gets the same jobs and gaps in another order."""
import copy

import numpy as np
import pytest

import harness
import traffic
from bench_cells import burst_cell

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


def _burst_mix():
    return burst_cell("pod256-steady").mix


def _arrival_times(mix, seed, t_end):
    """The point process alone: arrival times up to ``t_end`` (no jobs), and
    every switch time it drew, in order (quiet -> burst first).  Each switch
    lies one dwell after the last, so the switches are the running sum of
    the first switch and the dwells drawn after it."""
    stream = traffic.JobStream(mix, np.random.default_rng(seed))
    proc = stream.arrivals
    switches, dwell = [proc.switch_t], proc._dwell

    def keep():
        d = dwell()
        switches.append(switches[-1] + d)
        return d

    proc._dwell = keep
    times, t = [], 0.0
    while True:
        t = stream._next_arrival(t)
        if t > t_end:
            assert switches[-1] == proc.switch_t
            return switches, times
        times.append(t)


def test_bursts_come_at_the_same_times_on_every_seed():
    a, times_a = _arrival_times(_burst_mix(), 1, 2000.0)
    b, times_b = _arrival_times(_burst_mix(), 3141592653589793, 2000.0)
    n = min(len(a), len(b))
    assert n > 20 and a[:n] == b[:n]
    assert times_a != times_b


def test_burst_rate_and_share_of_time_in_bursts():
    mix = _burst_mix()
    arr = mix["arrivals"]
    t_end = 40000.0
    switches, times = _arrival_times(mix, 7, t_end)
    dq, db = arr["mean_dwell_quiet"], arr["mean_dwell_burst"]
    mean_rate = (arr["rate_quiet"] * dq + arr["rate_burst"] * db) / (dq + db)
    assert len(times) / t_end == pytest.approx(mean_rate, rel=0.05)
    # switches alternate quiet -> burst -> quiet ...; time in bursts is
    # every second interval, cut at t_end
    edges = [0.0] + [min(s, t_end) for s in switches]
    in_burst = sum(edges[i + 1] - edges[i]
                   for i in range(1, len(edges) - 1, 2))
    assert in_burst / t_end == pytest.approx(db / (dq + db), rel=0.10)


def test_arrivals_within_a_burst_come_at_the_burst_rate():
    mix = _burst_mix()
    switches, times = _arrival_times(mix, 11, 20000.0)
    sw = np.asarray(switches)
    # a time is in a burst when an odd number of switches lie before it
    burst = np.searchsorted(sw, np.asarray(times)) % 2 == 1
    edges = np.concatenate([[0.0], np.minimum(sw, 20000.0)])
    t_burst = float(np.sum(np.diff(edges)[1::2]))
    assert burst.sum() / t_burst == pytest.approx(
        mix["arrivals"]["rate_burst"], rel=0.05)


def test_an_arrival_key_the_process_lacks_raises():
    mix = _burst_mix()
    mix["arrivals"]["rate"] = 6.6
    with pytest.raises(TypeError, match="rate"):
        traffic.JobStream(mix, np.random.default_rng(1))


def test_burst_dwells_are_the_mix_seeds_third_stream():
    """The gaps and traits are the mix's first two child streams, whatever
    the process; the burst process spawns the third for its dwells."""
    mix = _burst_mix()
    stream = traffic.JobStream(mix, np.random.default_rng(5))
    third = np.random.SeedSequence(mix["stream"]["base_seed"]).spawn(3)[2]
    first = np.random.default_rng(third).standard_exponential()
    assert stream.arrivals.switch_t == \
        mix["arrivals"]["mean_dwell_quiet"] * first

"""The one traffic generator: a seeded job stream read from a mix's file.

A traffic mix is a JSON file under ``bench/traffic/``; this module is the
only code that reads one.  The stream is a copy of the program's arrival
processes and job synthesis (``repro.service.arrivals``), kept here so
that a change to the program cannot change the benchmark's inputs:

* ``arrivals.process`` names the point process (:data:`PROCESSES`):
  ``poisson`` at ``rate`` jobs per unit of simulated time (each gap a unit
  exponential over the rate), or ``burst``, the program's two-state
  Markov-modulated Poisson process (``BurstArrivals``): ``rate_quiet`` and
  ``rate_burst``, exponential dwells of mean ``mean_dwell_quiet`` and
  ``mean_dwell_burst``, starting quiet;
* each job draws log-uniform work, uniform steady memory, the
  warm-up/steady/burst memory profile of ``fmp_standard`` and, with
  probability ``qos_fraction``, a deadline at work × U(deadline_slack)
  after its arrival (a ``DeadlineExpired`` event then follows it).

Every seed gets the same jobs and the same gaps in another order: both are
drawn from ``stream.base_seed``, in blocks of ``stream.block``, and the
run's seed permutes each block.  So every stretch of whole blocks offers
the same work whatever the seed, and runs of different seeds differ in
the order of arrivals and the scheduler's decisions, not in the load.
The burst process's dwell times come from ``stream.base_seed`` too, on a
stream of their own that no seed permutes: every seed sees the same
bursts at the same simulated times.

The run's generator is ``numpy.random.default_rng`` seeded from the run's
seed alone, so the same seed gives the same jobs at the same simulated
times, whatever the speed of the system under test.  The stream speaks the
service's ``ArrivalProcess.take_until`` interface; ``on_pull`` is called
with the simulated time at the start of every pull, which the service
makes once at the start of every round.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

GIB = float(1 << 30)


@dataclass(frozen=True)
class JobDraw:
    """What the generator decided about one job (the reference reads this)."""

    job_id: str
    t: float
    work: float
    steady_bytes: float
    deadline: Optional[float]


def load_mix(path) -> dict:
    with open(path) as f:
        mix = json.load(f)
    for key in ("round_dt", "warmup_rounds", "arrivals", "admission", "jobs",
                "stream"):
        if key not in mix:
            raise ValueError(f"{path}: traffic mix lacks {key!r}")
    return mix


class Shuffled:
    """Draws of a fixed base generator, handed out block by block, each
    block in an order that the run's generator picks."""

    def __init__(self, draw: Callable[[np.random.Generator], object],
                 base: np.random.Generator, rng: np.random.Generator,
                 block: int):
        if block < 1:
            raise ValueError("stream.block must be >= 1")
        self.draw, self.base, self.rng, self.block = draw, base, rng, block
        self._left: list = []

    def next(self):
        if not self._left:
            drawn = [self.draw(self.base) for _ in range(self.block)]
            self._left = [drawn[i] for i in self.rng.permutation(self.block)]
        return self._left.pop()


class PoissonProcess:
    """Arrivals at a fixed ``rate``: each gap a unit exponential over it.
    It draws nothing but the gaps, so it spawns nothing from ``seeds``."""

    def __init__(self, gaps: Shuffled, seeds: np.random.SeedSequence, *,
                 rate: float):
        if rate <= 0:
            raise ValueError("arrivals.rate must be > 0")
        self.gaps, self.rate = gaps, float(rate)

    def next(self, prev: float) -> float:
        return prev + self.gaps.next() / self.rate


class BurstProcess:
    """The program's two-state MMPP (``BurstArrivals._next_arrival``): the
    chain starts quiet; a draw that crosses the switch point is dropped and
    drawn again from the switch under the new state's rate.  The unit gaps
    are the stream's shuffled ones; the dwells come from a stream of their
    own, spawned from the mix's ``seeds``, which no run's seed touches."""

    def __init__(self, gaps: Shuffled, seeds: np.random.SeedSequence, *,
                 rate_quiet: float, rate_burst: float,
                 mean_dwell_quiet: float, mean_dwell_burst: float):
        if min(rate_quiet, rate_burst) <= 0:
            raise ValueError("arrivals.rate_quiet and rate_burst must be > 0")
        if min(mean_dwell_quiet, mean_dwell_burst) <= 0:
            raise ValueError("arrivals.mean_dwell_* must be > 0")
        self.gaps = gaps
        self.dwells = np.random.default_rng(seeds.spawn(1)[0])
        self.rate = {False: float(rate_quiet), True: float(rate_burst)}
        self.mean_dwell = {False: float(mean_dwell_quiet),
                           True: float(mean_dwell_burst)}
        self.burst = False
        self.switch_t = self._dwell()

    def _dwell(self) -> float:
        return self.mean_dwell[self.burst] * self.dwells.standard_exponential()

    def next(self, prev: float) -> float:
        t = prev
        while True:
            candidate = t + self.gaps.next() / self.rate[self.burst]
            if candidate <= self.switch_t:
                return candidate
            t = self.switch_t
            self.burst = not self.burst
            self.switch_t = t + self._dwell()


#: arrival processes by the name a mix gives in ``arrivals.process``
PROCESSES = {"poisson": PoissonProcess, "burst": BurstProcess}


class JobStream:
    """Seeded lazy stream of the program's arrival events (see module doc)."""

    def __init__(self, mix: dict, rng: np.random.Generator,
                 on_pull: Optional[Callable[[float], None]] = None):
        from repro.core.trp import fmp_standard
        from repro.core.types import JobSpec
        from repro.service.arrivals import DeadlineExpired, JobArrival

        self._fmp_standard = fmp_standard
        self._JobSpec = JobSpec
        self._JobArrival = JobArrival
        self._DeadlineExpired = DeadlineExpired
        self.mix = mix
        self.on_pull = on_pull
        params = dict(mix["arrivals"])
        kind = params.pop("process", "poisson")
        if kind not in PROCESSES:
            raise ValueError(f"unknown arrival process {kind!r} "
                             f"(have {sorted(PROCESSES)})")
        self.round_dt = float(mix["round_dt"])
        order = mix["stream"]
        base_seed, block = int(order["base_seed"]), int(order["block"])
        # the gaps and traits are the mix's first two child streams; a
        # process that draws more (the burst dwells) spawns the next ones,
        # so a mix's gaps and traits do not depend on its process
        seeds = np.random.SeedSequence(base_seed)
        base = seeds.spawn(2)
        gaps = Shuffled(lambda g: float(g.standard_exponential()),
                        np.random.default_rng(base[0]), rng, block)
        self._traits = Shuffled(self._draw_traits,
                                np.random.default_rng(base[1]), rng, block)
        self.arrivals = PROCESSES[kind](gaps, seeds, **params)
        self.jobs: Dict[str, JobDraw] = {}
        self._n = 0
        self._seq = 0
        self._staged: List[Tuple[float, int, object]] = []
        self._last_t = 0.0
        self._next_t: Optional[float] = None

    # -- the point process -------------------------------------------------
    def _next_arrival(self, prev: float) -> float:
        return self.arrivals.next(prev)

    # -- job synthesis (the program's generator family) --------------------
    def _draw_traits(self, g: np.random.Generator):
        """(work, steady bytes, deadline slack or None) of one job."""
        jobs = self.mix["jobs"]
        lo, hi = jobs["work"]
        work = float(np.exp(g.uniform(np.log(lo), np.log(hi))))
        steady = float(g.uniform(*jobs["mem_gib"])) * GIB
        slack = float(g.uniform(*jobs["deadline_slack"]))
        has_deadline = g.uniform() < jobs["qos_fraction"]
        return work, steady, slack if has_deadline else None

    def _draw_job(self, t: float) -> None:
        jobs = self.mix["jobs"]
        work, steady, slack = self._traits.next()
        deadline = None if slack is None else t + work * slack
        fmp_p = jobs["fmp"]
        fmp = self._fmp_standard(
            fmp_p["base"] * steady, steady, fmp_p["burst"] * steady,
            warmup_frac=fmp_p["warmup_frac"],
            burst_frac=fmp_p["burst_frac"], rel_sigma=fmp_p["rel_sigma"])
        job_id = f"J{self._n:06d}"
        self._n += 1
        self.jobs[job_id] = JobDraw(job_id, t, work, steady, deadline)
        spec = self._JobSpec(job_id=job_id, arrival_time=t, total_work=work,
                             fmp=fmp, qos_deadline=deadline)
        self._stage(t, self._JobArrival(t, spec))
        if deadline is not None:
            self._stage(deadline, self._DeadlineExpired(deadline, job_id))

    def _stage(self, t: float, ev) -> None:
        self._staged.append((t, self._seq, ev))
        self._seq += 1

    # -- the service's ArrivalProcess interface ----------------------------
    def take_until(self, t: float) -> list:
        """Every event with timestamp <= t, by (time, draw order)."""
        if self.on_pull is not None:
            self.on_pull(t - self.round_dt)
        while True:
            if self._next_t is None:
                self._next_t = self._next_arrival(self._last_t)
            if self._next_t > t:
                break
            self._last_t, self._next_t = self._next_t, None
            self._draw_job(self._last_t)
        due = sorted((e for e in self._staged if e[0] <= t),
                     key=lambda e: (e[0], e[1]))
        self._staged = [e for e in self._staged if e[0] > t]
        return [ev for _, _, ev in due]

    @property
    def n_emitted(self) -> int:
        return self._n


def mix_path(root: Path, name: str) -> Path:
    return root / "traffic" / f"{name}.json"

"""The plain reference a round is checked against: scoring, WIS, clearing.

Written from the paper's equations and the configuration file alone; it
imports nothing of the program.  For one auction round it takes what
crossed the auction's boundary — the announced windows, the bids (interval,
job, declared job utility h̃, work) and the per-job state the round scores
with (trust ρ and HistAvg, the time the job last won) — and recomputes:

* every bid's eligibility: the bid lies inside its window and its job's
  memory profile satisfies Pr(max RAM > c_k) <= θ (paper §4.1 (a)), on a
  64-point grid of the profile, in float64;
* every bid's score (Eqs. 2-5 and §4.3):
  ĥ = ρ h̃ + (1 − ρ) HistAvg,
  f = Σ β_j ψ_j over utilization, slack, memory headroom and age,
  Score = λ ĥ + (1 − λ) f, all in float64 (or in bfloat16 for the control);
* each window's selection: maximum-weight non-overlapping bids by the
  textbook dynamic programme over bids sorted by end time (ties: the set
  without the later bid), run on the scores the program selected on;
* the policy's conflict resolution: a job keeps its best-scored wins, a
  win overlapping a kept win on another slice or past the job's remaining
  work is revoked and its window re-cleared, until nothing changes
  (``greedy_wis``); ``global_assignment`` also searches which win each
  conflicted job keeps (all combinations up to 64, else a Hungarian seed
  and two passes of coordinate descent, at most 200 replays) and keeps the
  replay with the largest total.
"""
from __future__ import annotations

import itertools
import math
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np
from scipy.special import log_ndtr

#: grid of the safety check (condition (a)) and of the headroom feature
SAFETY_GRID = 64
HEADROOM_GRID = 32
#: two intervals that overlap by less than this are disjoint
OVERLAP_EPS = 1e-12
#: a bid may protrude past its window by this much
CONTAIN_EPS = 1e-9
#: a bid's work may exceed its job's remaining work by this much
BUDGET_EPS = 1e-9
#: a replay must beat the best total by more than this to replace it
TOTAL_EPS = 1e-12

#: the limit of each number the check compares, and where it came from:
#: see PERF.md, "How correct is decided"
LIMITS = {
    "score_gap": 1e-4,
    "unsafe_bids": 0,
    "window_selection_diff": 0,
    "award_diff": 0,
}


# ---------------------------------------------------------------------------
# Job memory profiles (TRP/FMP, paper §3.2)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class JobMemory:
    """A job's memory profile: a linear ramp from ``base`` to ``steady``
    over the first ``warmup_frac`` of its run, ``steady`` until the last
    ``burst_frac``, then ``steady + burst``; Gaussian with a standard
    deviation of ``rel_sigma`` times the phase's level."""

    base: float
    steady: float
    burst: float
    warmup_frac: float
    burst_frac: float
    rel_sigma: float

    @classmethod
    def from_draw(cls, steady: float, mix: dict) -> "JobMemory":
        f = mix["jobs"]["fmp"]
        return cls(f["base"] * steady, steady, f["burst"] * steady,
                   f["warmup_frac"], f["burst_frac"], f["rel_sigma"])

    def grid(self, n: int) -> Tuple[np.ndarray, np.ndarray]:
        """Mean and standard deviation at the midpoints of n equal cells."""
        t = (np.arange(n) + 0.5) / n
        w, b = self.warmup_frac, self.burst_frac
        ramp = self.base + (t / w) * (self.steady - self.base)
        peak = self.steady + self.burst
        mu = np.where(t < w, ramp, np.where(t < 1.0 - b, self.steady, peak))
        sd = self.rel_sigma * np.where(t < 1.0 - b, self.steady, peak)
        return mu, sd

    def p_exceed(self, capacity: float) -> float:
        """Pr(max over the grid of RAM > capacity), grid points independent."""
        mu, sd = self.grid(SAFETY_GRID)
        return float(-np.expm1(np.sum(log_ndtr((capacity - mu) / sd))))

    def mean(self) -> float:
        return float(np.mean(self.grid(HEADROOM_GRID)[0]))


# ---------------------------------------------------------------------------
# One round's inputs as plain arrays
# ---------------------------------------------------------------------------


@dataclass
class Round:
    now: float
    # windows
    w_slice: List[str]
    w_tmin: np.ndarray
    w_dur: np.ndarray
    w_cap: np.ndarray
    # bids
    vid: List[str]
    job: List[str]
    slice_: List[str]
    ts: np.ndarray
    dur: np.ndarray
    h_declared: np.ndarray
    work: np.ndarray
    win: np.ndarray
    # per-job state
    rho: Dict[str, float]
    hist: Dict[str, float]
    last: Dict[str, float]
    budget: Dict[str, float]

    @property
    def te(self) -> np.ndarray:
        return self.ts + self.dur

    @classmethod
    def from_record(cls, rec: dict) -> "Round":
        ws, fit = rec["windows"], rec["fit"]
        return cls(
            now=float(rec["now"]),
            w_slice=[w.slice_id for w in ws],
            w_tmin=np.array([w.t_min for w in ws], np.float64),
            w_dur=np.array([w.duration for w in ws], np.float64),
            w_cap=np.array([w.capacity for w in ws], np.float64),
            vid=[v.variant_id for v in fit],
            job=[v.job_id for v in fit],
            slice_=[v.slice_id for v in fit],
            ts=np.array([v.t_start for v in fit], np.float64),
            dur=np.array([v.duration for v in fit], np.float64),
            h_declared=np.array([v.local_utility for v in fit], np.float64),
            work=np.array([float(v.payload["work"]) for v in fit], np.float64),
            win=np.asarray(rec["win_idx"], np.int64),
            rho={j: t[0] for j, t in rec["trust"].items()},
            hist={j: t[1] for j, t in rec["trust"].items()},
            last=dict(rec["last_selected"]),
            budget=dict(rec["budget"]))


# ---------------------------------------------------------------------------
# Eligibility and scores
# ---------------------------------------------------------------------------


def unsafe_bids(r: Round, jobs: Dict[str, JobMemory], theta: float) -> int:
    """Bids outside their window, on another slice, or unsafe (cond. (a))."""
    bad = 0
    p_cache: Dict[Tuple[str, float], float] = {}
    for i in range(len(r.vid)):
        k = int(r.win[i])
        inside = (r.ts[i] >= r.w_tmin[k] - CONTAIN_EPS
                  and r.ts[i] + r.dur[i] <= r.w_tmin[k] + r.w_dur[k]
                  + CONTAIN_EPS and r.slice_[i] == r.w_slice[k])
        key = (r.job[i], float(r.w_cap[k]))
        p = p_cache.get(key)
        if p is None:
            p = p_cache[key] = jobs[r.job[i]].p_exceed(r.w_cap[k])
        if not inside or p > theta:
            bad += 1
    return bad


def scores(r: Round, jobs: Dict[str, JobMemory], config: dict,
           dtype=np.float64) -> np.ndarray:
    """Eq. 4 for every bid of the round, computed in ``dtype``."""
    sc = config["scoring"]
    lam = float(sc["lam"])
    beta = sc["betas"]
    tau = float(config["age_tau"])

    def a(x):
        return np.asarray(x, np.float64).astype(dtype)

    def clip(x):
        return np.clip(x, dtype(0.0), dtype(1.0)).astype(dtype)

    rho = a([r.rho[j] for j in r.job])
    hist = a([r.hist[j] for j in r.job])
    h = clip(rho * clip(a(r.h_declared)) + (a(1.0) - rho) * hist)
    wdur = a(np.maximum(r.w_dur[r.win], 1e-9))
    cap = r.w_cap[r.win]
    util = clip(a(r.dur) / wdur)
    slack = clip(a(1.0) - (a(r.ts) - a(r.w_tmin[r.win])) / wdur)
    mean_mu = np.array([jobs[j].mean() for j in r.job], np.float64)
    headroom = np.where(cap > 0, 1.0 - mean_mu / np.where(cap > 0, cap, 1.0),
                        0.0)
    headroom = clip(a(headroom))
    wait = np.maximum(0.0, r.now - np.array([r.last[j] for j in r.job]))
    age = clip(a(1.0 - np.exp(-wait / tau)))
    f = clip(a(beta.get("utilization", 0.0)) * util
             + a(beta.get("slack", 0.0)) * slack
             + a(beta.get("mem_headroom", 0.0)) * headroom
             + a(beta.get("age", 0.0)) * age)
    return (a(lam) * h + a(1.0 - lam) * f).astype(np.float64)


# ---------------------------------------------------------------------------
# Selection and conflict resolution
# ---------------------------------------------------------------------------


def wis(starts: np.ndarray, ends: np.ndarray, w: np.ndarray) -> List[int]:
    """Maximum-weight set of non-overlapping intervals (half-open).

    Sort by end (stable), dp[j] = max(dp[j-1], w_j + dp[p(j)]) with p(j) the
    number of intervals ending at or before start_j; a bid is taken only if
    it strictly improves the optimum.  Returns local indices, by end time.
    """
    order = np.argsort(ends, kind="stable")
    s, e, ww = starts[order], ends[order], w[order]
    p = np.searchsorted(e, s, side="right")
    m = len(order)
    dp = [0.0] * (m + 1)
    take = [False] * m
    for j in range(m):
        with_j = float(ww[j]) + dp[p[j]]
        if with_j > dp[j]:
            dp[j + 1] = with_j
            take[j] = True
        else:
            dp[j + 1] = dp[j]
    out = []
    j = m
    while j > 0:
        if take[j - 1]:
            out.append(int(order[j - 1]))
            j = int(p[j - 1])
        else:
            j -= 1
    return out[::-1]


class Clearing:
    """Per-window WIS plus iterated conflict resolution for one round."""

    def __init__(self, r: Round, s: np.ndarray, prefer=None):
        self.r, self.s = r, s
        self.prefer = prefer or {}
        self.members = [[] for _ in r.w_slice]
        for i, k in enumerate(r.win):
            self.members[int(k)].append(i)
        self.banned = np.zeros(len(r.vid), bool)
        self.selected: List[List[int]] = [[] for _ in r.w_slice]
        self.dirty = set(range(len(r.w_slice)))
        self.n_conflicts = 0

    def seed(self, first_pass: List[List[int]]) -> None:
        self.selected = [list(x) for x in first_pass]
        self.dirty = set()

    def reclear(self) -> None:
        r, te = self.r, self.r.te
        for k in sorted(self.dirty):
            idx = [i for i in self.members[k] if not self.banned[i]]
            if not idx:
                self.selected[k] = []
                continue
            ia = np.array(idx)
            self.selected[k] = [idx[j] for j in wis(r.ts[ia], te[ia],
                                                    self.s[ia])]
        self.dirty = set()

    def resolve(self) -> bool:
        r, s, te = self.r, self.s, self.r.te
        wins: Dict[str, List[int]] = {}
        for sel in self.selected:
            for i in sel:
                wins.setdefault(r.job[i], []).append(i)
        banned_any = False
        for job, ws in wins.items():
            pins = self.prefer.get(job, ())
            ws = sorted(ws, key=lambda i: (0 if i in pins else 1, -s[i],
                                           r.ts[i], r.win[i]))
            kept: List[int] = []
            used = 0.0
            budget = r.budget.get(job)
            for i in ws:
                drop = any(r.ts[i] < te[j] - OVERLAP_EPS
                           and r.ts[j] < te[i] - OVERLAP_EPS
                           and r.win[i] != r.win[j] for j in kept)
                if not drop and budget is not None:
                    if used + r.work[i] > budget + BUDGET_EPS:
                        drop = True
                    else:
                        used += r.work[i]
                if drop:
                    self.banned[i] = True
                    banned_any = True
                    self.n_conflicts += 1
                    self.dirty.add(int(r.win[i]))
                else:
                    kept.append(i)
        return banned_any

    def run(self) -> "Clearing":
        while True:
            self.reclear()
            if not self.resolve():
                return self

    def total(self) -> float:
        return float(sum(float(self.s[i]) for sel in self.selected
                         for i in sorted(sel, key=lambda i: self.r.ts[i])))


def greedy(r: Round, s: np.ndarray) -> Tuple[Clearing, List[List[int]]]:
    """The fixed point with no pins, and its ban-free first pass."""
    c = Clearing(r, s)
    c.reclear()
    first = [list(x) for x in c.selected]
    while c.resolve():
        c.reclear()
    return c, first


def conflict_clusters(r: Round, first: List[List[int]]):
    """Per job, the groups of its first-pass wins that overlap across
    windows (connected components, size >= 2), jobs in sorted order."""
    te = r.te
    wins: Dict[str, List[int]] = {}
    for sel in first:
        for i in sel:
            wins.setdefault(r.job[i], []).append(i)
    out = []
    for job in sorted(wins):
        ws = sorted(wins[job])
        if len(ws) < 2:
            continue
        comp = {i: {i} for i in ws}
        for a, b in itertools.combinations(ws, 2):
            if (r.win[a] != r.win[b] and r.ts[a] < te[b] - OVERLAP_EPS
                    and r.ts[b] < te[a] - OVERLAP_EPS
                    and comp[a] is not comp[b]):
                merged = comp[a] | comp[b]
                for i in merged:
                    comp[i] = merged
        seen = []
        for i in ws:
            if all(comp[i] is not g for g in seen):
                seen.append(comp[i])
        out.extend((job, sorted(g)) for g in seen if len(g) >= 2)
    return out


def global_assignment(r: Round, s: np.ndarray, params: dict) -> Clearing:
    base, first = greedy(r, s)
    if base.n_conflicts == 0:
        return base
    clusters = conflict_clusters(r, first)
    if not clusters:
        return base
    max_configs = int(params["max_configs"])
    max_evals = int(params["max_evals"])
    best, best_total = base, base.total()
    evals = 0

    def replay(choice) -> Clearing:
        prefer: Dict[str, tuple] = {}
        for (job, _), i in zip(clusters, choice):
            prefer[job] = prefer.get(job, ()) + (i,)
        c = Clearing(r, s, prefer)
        c.seed(first)
        return c.run()

    def evaluate(choice) -> bool:
        nonlocal evals, best, best_total
        if evals >= max_evals:
            return False
        evals += 1
        c = replay(choice)
        t = c.total()
        if t > best_total + TOTAL_EPS:
            best, best_total = c, t
        return True

    n_joint = math.prod(len(w) for _, w in clusters)
    if n_joint <= max_configs:
        for choice in itertools.product(*(w for _, w in clusters)):
            if not evaluate(choice):
                break
        return best

    # a Hungarian assignment of clusters to windows seeds the search
    from scipy.optimize import linear_sum_assignment

    current = [max(w, key=lambda i: (s[i], -i)) for _, w in clusters]
    wset = sorted({int(r.win[i]) for _, w in clusters for i in w})
    col = {k: c for c, k in enumerate(wset)}
    profit = np.full((len(clusters), len(wset)), -1e9)
    arg: Dict[Tuple[int, int], int] = {}
    for row, (_, w) in enumerate(clusters):
        for i in w:
            c = col[int(r.win[i])]
            if s[i] > profit[row, c]:
                profit[row, c] = s[i]
                arg[(row, c)] = i
    for row, c in zip(*linear_sum_assignment(profit, maximize=True)):
        if profit[row, c] > -1e8:
            current[row] = arg[(row, c)]
    evaluate(current)
    mark = best_total
    for _ in range(int(params["descent_passes"])):
        improved = False
        for c, (_, w) in enumerate(clusters):
            for i in w:
                if current[c] == i:
                    continue
                trial = list(current)
                trial[c] = i
                if not evaluate(trial):
                    return best
                if best_total > mark + TOTAL_EPS:
                    mark, current, improved = best_total, trial, True
        if not improved:
            break
    return best


def clear(r: Round, s: np.ndarray, config: dict) -> Clearing:
    kind = config["clearing"]
    if kind == "greedy_wis":
        return greedy(r, s)[0]
    if kind == "global_assignment":
        return global_assignment(r, s, config["global_assignment"])
    raise ValueError(f"the reference has no clearing {kind!r}")


# ---------------------------------------------------------------------------
# The comparison
# ---------------------------------------------------------------------------


def compare_round(rec: dict, config: dict, jobs: Dict[str, JobMemory],
                  control: bool = False) -> Dict[str, float]:
    """The numbers for one sampled round (see ``check_rounds``).

    With ``control`` the program's scores are replaced by the reference's
    own computed in bfloat16, the precision below the float32 the scorer
    states: its score gap is the control's reading.
    """
    r = Round.from_record(rec)
    ref = scores(r, jobs, config)
    prog = (scores(r, jobs, config, dtype=_bfloat16()) if control
            else np.asarray(rec["scores"], np.float64))
    if prog.shape != ref.shape:
        return {"score_gap": math.inf, "unsafe_bids": 0,
                "window_selection_diff": len(r.w_slice),
                "award_diff": len(r.vid)}
    gap = float(np.max(np.abs(prog - ref))) if len(ref) else 0.0
    c = clear(r, prog, config)
    rr = rec["result"]
    results = list(getattr(rr, "results", ()) or ())
    win_diff = 0
    for k in range(len(r.w_slice)):
        mine = {r.vid[i] for i in c.selected[k]}
        theirs = ({v.variant_id for v in results[k].selected}
                  if k < len(results)
                  and results[k].window.slice_id == r.w_slice[k]
                  and results[k].window.t_min == r.w_tmin[k] else None)
        win_diff += int(theirs != mine)
    mine_awards = {r.vid[i] for sel in c.selected for i in sel}
    their_awards = {v.variant_id for v in getattr(rr, "selected", ()) or ()}
    return {"score_gap": gap,
            "unsafe_bids": unsafe_bids(r, jobs, float(config["theta"])),
            "window_selection_diff": win_diff,
            "award_diff": len(mine_awards ^ their_awards)}


def _bfloat16():
    import ml_dtypes

    return ml_dtypes.bfloat16


def check_rounds(records: Sequence[dict], config: dict,
                 jobs: Dict[str, JobMemory], *, control: bool = False
                 ) -> Dict[str, dict]:
    """Worst reading of each number over the sampled rounds, with limits.

    * ``score_gap``: the widest gap between a bid's score from the
      program's scoring path and the reference's float64 score;
    * ``unsafe_bids``: bids in the scored pool that lie outside their
      window or break the safety bound θ;
    * ``window_selection_diff``: windows whose final selection differs from
      the reference's clearing of the same scores;
    * ``award_diff``: bids committed by one and not the other.

    No sampled round at all reads as an infinite score gap.
    """
    worst = {k: (math.inf if k == "score_gap" else 0) for k in LIMITS}
    if records:
        worst["score_gap"] = 0.0
    for rec in records:
        got = compare_round(rec, config, jobs, control=control)
        for k, v in got.items():
            worst[k] = max(worst[k], v)
    return {k: {"value": worst[k], "limit": LIMITS[k]} for k in LIMITS}

"""Weighted Interval Scheduling (paper §4.4, `SelectBestCompatibleVariants`).

The per-window clearing step: given M candidate variants, each an interval
[t_start, t_end] with weight Score(v) ≥ 0, select the maximum-total-score
subset of pairwise non-overlapping intervals.

Classical DP after sorting by end time — O(M log M):

    p(j) = largest i < j with end_i <= start_j        (binary search)
    dp[j] = max(dp[j-1], w_j + dp[p(j)])

Three per-window implementations:

* :func:`wis_select`       — numpy host path: the plain per-window reference
                             (used when passed explicitly as ``selector``).
* :func:`wis_select_jax`   — jit-able JAX path (sort + searchsorted +
                             ``lax.scan`` DP + ``lax.while_loop`` backtrack);
                             mirrored by the Pallas kernel ``kernels/wis_dp``.
* :func:`wis_brute_force`  — O(2^M) oracle for property tests.

Plus the BATCHED multi-window machinery behind the device-resident round
settle (the clearing-side twin of the PR-2 scoring engine):

* :class:`RoundSelector` packs every window's candidate set into a padded
  ``(W, L)`` sorted-lane layout once per round (:meth:`RoundSelector.pack`)
  and clears any subset of windows in ONE dispatch
  (:meth:`RoundSelector.select`), with three backends mirroring
  ``jasda_score``'s contract — host ``numpy`` (float64, byte-identical to
  the per-window loop by construction), jnp ``ref`` and the ``pallas``
  kernel (``kernels/wis_dp``).  Shapes are pow2-bucketed on both dims so
  drifting (W, M) rounds never retrace.
* :meth:`RoundSelector.predispatch` fuses selection behind the round's
  in-flight scoring dispatch (scores never round-trip through the host);
  the returned :class:`SettlePrefetch` materializes at settle time.
* :func:`make_round_selector` maps the ``SchedulerConfig.wis_impl`` knob to
  a selector (None → the batched host settle, ``RoundSelector("numpy")``).

Banned lanes are excluded by ZEROING their weights rather than re-packing:
under the strict ``>`` tie rule a zero-weight lane is never taken and its
presence shifts dp indices without changing any dp value, so zero-weight
banning is exactly equivalent to removing the lane (the conflict
resolution loop re-clears dirty windows from the retained buffers).

Intervals are treated as half-open [start, end): touching intervals
(end_i == start_j) are compatible, matching the paper's worked example where
(40,47) and (47,50) are both selected.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from .types import OVERLAP_EPS

__all__ = [
    "wis_select",
    "wis_select_jax",
    "wis_brute_force",
    "total_weight",
    "RoundSelector",
    "SettlePrefetch",
    "PackedSettle",
    "make_round_selector",
    "HOST_SELECTOR",
    "predispatch_settle",
    "wis_select_batch",
]


def _validate(starts, ends, weights):
    starts = np.asarray(starts, dtype=np.float64)
    ends = np.asarray(ends, dtype=np.float64)
    weights = np.asarray(weights, dtype=np.float64)
    if not (starts.shape == ends.shape == weights.shape):
        raise ValueError("starts/ends/weights must have identical shapes")
    if np.any(ends < starts):
        raise ValueError("interval with end < start")
    if np.any(weights < -1e-12):
        raise ValueError("WIS optimality requires non-negative weights")
    return starts, ends, weights


def wis_select(
    starts: Sequence[float],
    ends: Sequence[float],
    weights: Sequence[float],
) -> Tuple[np.ndarray, float]:
    """Optimal WIS. Returns (selected original indices asc by end, total).

    O(M log M): numpy argsort + searchsorted + a single DP pass.
    """
    starts, ends, weights = _validate(starts, ends, weights)
    m = starts.shape[0]
    if m == 0:
        return np.zeros((0,), dtype=np.int64), 0.0

    order = np.argsort(ends, kind="stable")
    s, e, w = starts[order], ends[order], weights[order]

    # p[j]: number of intervals (in sorted order) ending <= s[j]; dp is
    # 1-indexed with dp[0] = 0 so p[j] indexes dp directly.
    p = np.searchsorted(e, s, side="right")

    dp = np.zeros(m + 1, dtype=np.float64)
    take = np.zeros(m, dtype=bool)
    for j in range(m):
        with_j = w[j] + dp[p[j]]
        if with_j > dp[j]:  # strict: prefer fewer intervals on ties
            dp[j + 1] = with_j
            take[j] = True
        else:
            dp[j + 1] = dp[j]

    # Backtrack.
    sel: List[int] = []
    j = m
    while j > 0:
        if take[j - 1]:
            sel.append(j - 1)
            j = p[j - 1]
        else:
            j -= 1
    sel_sorted = np.array(sel[::-1], dtype=np.int64)
    return order[sel_sorted], float(dp[m])


def wis_brute_force(
    starts: Sequence[float],
    ends: Sequence[float],
    weights: Sequence[float],
) -> Tuple[np.ndarray, float]:
    """Exhaustive oracle (use only for small M in tests)."""
    starts, ends, weights = _validate(starts, ends, weights)
    m = starts.shape[0]
    if m > 22:
        raise ValueError("brute force limited to M <= 22")
    best_mask, best_val = 0, 0.0
    for mask in range(1 << m):
        idx = [i for i in range(m) if mask >> i & 1]
        ok = True
        for a in range(len(idx)):
            for b in range(a + 1, len(idx)):
                i, j = idx[a], idx[b]
                if (starts[i] < ends[j] - OVERLAP_EPS
                        and starts[j] < ends[i] - OVERLAP_EPS):
                    ok = False
                    break
            if not ok:
                break
        if ok:
            val = float(sum(weights[i] for i in idx))
            if val > best_val + 1e-15:
                best_val, best_mask = val, mask
    sel = np.array([i for i in range(m) if best_mask >> i & 1], dtype=np.int64)
    return sel, best_val


def total_weight(weights: Sequence[float], selected: Sequence[int]) -> float:
    w = np.asarray(weights, dtype=np.float64)
    return float(w[np.asarray(selected, dtype=np.int64)].sum()) if len(selected) else 0.0


# ---------------------------------------------------------------------------
# JAX path — jit-able, fixed-size, mask-based (device-resident clearing)
# ---------------------------------------------------------------------------


def wis_select_jax(starts, ends, weights, valid=None):
    """Jit-able WIS over a fixed-size padded pool.

    Args:
      starts, ends, weights: (M,) float arrays (padded entries arbitrary).
      valid: optional (M,) bool mask; invalid entries are excluded.

    Returns:
      (selected_mask (M,) bool in ORIGINAL order, total_score scalar).

    The DP is a ``lax.scan`` over sorted intervals; backtracking is a
    ``lax.while_loop``.  Padded/invalid entries get weight 0 and a
    point-interval at +inf so they never affect the optimum.
    """
    import jax
    import jax.numpy as jnp

    starts = jnp.asarray(starts, dtype=jnp.float32)
    ends = jnp.asarray(ends, dtype=jnp.float32)
    weights = jnp.asarray(weights, dtype=jnp.float32)
    m = starts.shape[0]
    if valid is None:
        valid = jnp.ones((m,), dtype=bool)
    else:
        valid = jnp.asarray(valid, dtype=bool)

    big = jnp.float32(3.0e38)
    s = jnp.where(valid, starts, big)
    e = jnp.where(valid, ends, big)
    w = jnp.where(valid, weights, 0.0)

    order = jnp.argsort(e, stable=True)
    s_o, e_o, w_o = s[order], e[order], w[order]
    p = jnp.searchsorted(e_o, s_o, side="right")  # (M,) into dp[0..M]

    def dp_step(dp, j):
        with_j = w_o[j] + dp[p[j]]
        without_j = dp[j]
        take = with_j > without_j
        dp = dp.at[j + 1].set(jnp.where(take, with_j, without_j))
        return dp, take

    dp0 = jnp.zeros((m + 1,), dtype=jnp.float32)
    dp, take = jax.lax.scan(dp_step, dp0, jnp.arange(m))

    def backtrack(state):
        j, sel = state
        t = take[j - 1]
        sel = sel.at[j - 1].set(t)
        j = jnp.where(t, p[j - 1], j - 1)
        return j, sel

    def cond(state):
        return state[0] > 0

    sel_sorted = jnp.zeros((m,), dtype=bool)
    _, sel_sorted = jax.lax.while_loop(cond, backtrack, (jnp.int32(m), sel_sorted))

    sel_mask = jnp.zeros((m,), dtype=bool).at[order].set(sel_sorted)
    return sel_mask & valid, dp[m]


# ---------------------------------------------------------------------------
# Batched multi-window settle (device-resident clearing, paper §4.4 batched)
# ---------------------------------------------------------------------------

#: smallest jit-shape buckets for the batched dispatch: the window dim and
#: the lane dim both pad to powers of two (one executable per bucket pair)
MIN_ROW_BUCKET = 8
MIN_LANE_BUCKET = 32


def _bucket(n: int, lo: int) -> int:
    return max(lo, 1 << int(np.ceil(np.log2(max(n, 1)))))


class PackedSettle:
    """Retained padded buffers for one round's batched WIS dispatches.

    ``idx_sorted[k, j]`` is the pool index of window k's j-th candidate in
    ascending-end order (−1 on padded lanes); ``pred`` the predecessor
    table over that order; ``wmat`` the float64 selection weights in the
    same layout (0 on pads).  Sort order and predecessors are computed ONCE
    (float64, stable — identical to the per-window host path); banning only
    zeroes weights, so conflict-resolution re-clears re-dispatch straight
    from these buffers.
    """

    __slots__ = ("members", "idx_sorted", "pred", "wmat", "n_windows",
                 "lanes", "row_len", "_rows")

    def __init__(self, members, idx_sorted, pred, wmat):
        self.members = members
        self.idx_sorted = idx_sorted
        self.pred = pred
        self.wmat = wmat
        self.n_windows = idx_sorted.shape[0]
        self.lanes = idx_sorted.shape[1]
        self.row_len = np.fromiter((len(m) for m in members), np.intp,
                                   count=len(members))
        # lazily materialized per-row python lists (per-row scalar DP)
        self._rows: list = [None] * self.n_windows

    def row(self, k: int) -> tuple:
        """Window k's real lanes as ``(pool indices, weights, preds)``
        python lists plus the pool indices as an array (for ban gathers),
        built once per pack: a re-clear of a small window then costs no
        numpy call that scales with the pool."""
        row = self._rows[k]
        if row is None:
            n = int(self.row_len[k])
            idx = self.idx_sorted[k, :n]
            row = self._rows[k] = (idx.tolist(), self.wmat[k, :n].tolist(),
                                   self.pred[k, :n].tolist(), idx)
        return row

    def fill_weights(self, sel_scores: np.ndarray) -> None:
        """Gather the (sorted-lane) weight matrix from per-pool scores."""
        sel_scores = np.asarray(sel_scores, np.float64)
        self._rows = [None] * self.n_windows
        if sel_scores.size == 0:
            self.wmat = np.zeros(self.idx_sorted.shape, np.float64)
            return
        safe = np.clip(self.idx_sorted, 0, None)
        self.wmat = np.where(self.idx_sorted >= 0, sel_scores[safe], 0.0)


class SettlePrefetch:
    """An in-flight fused score→clear first pass (see RoundSelector).

    Holds the retained :class:`PackedSettle` plus the device selection mask
    the fused dispatch is computing; :meth:`materialize` blocks at the host
    boundary and returns (first_pass selections, packed buffers) for the
    fixed-point settle to continue from.

    ``transformed`` records whether the dispatch multiplied the gathered
    scores by the policy's selection transform
    (``ClearingPolicy.prefetch_transform``): a transformed prefetch is only
    valid for a settle that SELECTS on the matching transformed scores, and
    vice versa — ``fixed_point_settle`` checks the flag before adopting the
    first pass.
    """

    def __init__(self, packed: PackedSettle, raw_sel, transformed: bool = False):
        self.packed = packed
        self._raw = raw_sel
        self.transformed = transformed

    def materialize(self, scores: np.ndarray):
        packed = self.packed
        # blocks on the device; an error raised in flight propagates
        sel = np.asarray(self._raw)[: packed.n_windows]
        first_pass = [
            [int(i) for i in packed.idx_sorted[k][np.flatnonzero(sel[k])]]
            for k in range(packed.n_windows)
        ]
        if packed.wmat is None:
            packed.fill_weights(scores)
        return first_pass, packed


def _batch_dp_backtrack_numpy(w: np.ndarray, pred: np.ndarray) -> np.ndarray:
    """Float64 batched DP + backtrack, vectorized across windows.

    Per-row arithmetic is EXACTLY :func:`wis_select`'s DP (same float64
    add and strict ``>``; ``max(dp[j], with_j)`` equals the reference's
    conditional copy bit-for-bit, ties included), so selections are
    byte-identical to the per-window host loop.  The python loop runs once
    per LANE for all windows instead of once per candidate per window, in
    lane-major (transposed) layout with preallocated outputs and flat-index
    gathers so each step is a handful of contiguous (W,)-sized kernels.
    """
    r, m = w.shape
    w_t = np.ascontiguousarray(w.T)  # (m, r): lane-major rows
    dp = np.zeros((m + 1, r), np.float64)
    dp1d = dp.reshape(-1)
    # flat offsets of dp[pred[j, row], row] in lane-major dp
    pf_t = np.ascontiguousarray(pred.T.astype(np.intp) * r
                                + np.arange(r, dtype=np.intp)[None, :])
    take_t = np.empty((m, r), bool)
    with_j = np.empty(r, np.float64)
    for j in range(m):
        np.add(w_t[j], dp1d[pf_t[j]], out=with_j)
        np.greater(with_j, dp[j], out=take_t[j])
        np.maximum(with_j, dp[j], out=dp[j + 1])
    # Backtrack with a skip table: prev_take[row, j] = largest position
    # j' ≤ j whose lane j'−1 was taken (0 if none).  The reference walk
    # decrements the cursor through non-taken stretches before selecting —
    # prev_take collapses each stretch into one gather, so every vectorized
    # iteration lands EXACTLY one selection per active row and the loop
    # runs max-selections-per-row times instead of max-lanes times.
    jj = np.arange(1, m + 1, dtype=np.intp)
    prev_take = np.zeros((r, m + 1), np.intp)
    np.maximum.accumulate(np.where(take_t.T, jj[None, :], 0), axis=1,
                          out=prev_take[:, 1:])
    sel = np.zeros((r, m), bool)
    rows = np.arange(r)
    cur = np.full(r, m, np.intp)
    while True:
        j = prev_take[rows, cur]
        act = j > 0
        if not act.any():
            break
        jm1 = np.maximum(j - 1, 0)
        sel[rows[act], jm1[act]] = True
        cur = np.where(act, pred[rows, jm1], 0)
    return sel


class RoundSelector:
    """Batched multi-window WIS selector (the device-resident settle).

    One instance per scheduler (``SchedulerConfig.wis_impl``); stateless
    apart from the backend choice, so it is shared freely across rounds and
    replays.  Also callable with the classic per-window ``(starts, ends,
    weights)`` signature (delegating to :func:`wis_select`) so code written
    against the scalar selector protocol keeps working.
    """

    batched = True

    def __init__(self, impl: str = "numpy", mesh=None, health=None):
        if impl not in ("numpy", "ref", "pallas"):
            raise ValueError(
                f"wis_impl must be one of 'numpy' | 'ref' | 'pallas', got {impl!r}")
        self.impl = impl
        # auction mesh (launch.mesh.make_auction_mesh): shards the window
        # rows of every batched dispatch; host backend has nothing to shard
        self.mesh = mesh if impl in ("ref", "pallas") else None
        # sticky per-backend health (kernels.common.BackendHealth), shared
        # with the scheduler's scoring dispatches: an injected fault on a
        # device backend degrades every future settle down the ladder
        self.health = health

    def _effective_impl(self) -> str:
        return self.health.resolve(self.impl) if self.health is not None \
            else self.impl

    @property
    def device(self) -> bool:
        return self._effective_impl() in ("ref", "pallas")

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        if self.mesh is not None:
            return f"RoundSelector({self.impl!r}, mesh={dict(self.mesh.shape)})"
        return f"RoundSelector({self.impl!r})"

    def __call__(self, starts, ends, weights):
        return wis_select(starts, ends, weights)

    # -- packing ---------------------------------------------------------------
    def pack(self, members, view, sel_scores: Optional[np.ndarray] = None) -> PackedSettle:
        """Pad every window's candidates into the (W, L) sorted-lane layout.

        ``members[k]`` lists window k's pool indices in pool order (the
        same order the per-window host path sees).  Device backends bucket
        lanes to a power of two so drifting per-window pool sizes reuse
        one executable; the host backend packs exactly (no jit cache to
        protect, shorter DP loop).
        """
        w = len(members)
        lens = np.fromiter((len(m) for m in members), np.intp, count=w)
        max_len = int(lens.max()) if w else 1
        lanes = (max(1, max_len) if self.impl == "numpy"
                 else _bucket(max_len, MIN_LANE_BUCKET))
        idx = np.full((w, lanes), -1, np.intp)
        total = int(lens.sum())
        if total:
            import itertools

            flat = np.fromiter(
                itertools.chain.from_iterable(members), np.intp, count=total)
            rows = np.repeat(np.arange(w, dtype=np.intp), lens)
            cum0 = np.concatenate([[0], np.cumsum(lens)[:-1]])
            lane = np.arange(total, dtype=np.intp) - np.repeat(cum0, lens)
            idx[rows, lane] = flat
        valid = idx >= 0
        if total and len(view):
            safe = np.clip(idx, 0, None)
            s = np.where(valid, view.t_start[safe], np.inf)
            e = np.where(valid, view.t_end[safe], np.inf)
        else:  # empty pool: all lanes padded (gathering would index-error)
            s = np.full((w, lanes), np.inf)
            e = np.full((w, lanes), np.inf)
        order = np.argsort(e, axis=1, kind="stable")
        e_s = np.take_along_axis(e, order, axis=1)
        s_s = np.take_along_axis(s, order, axis=1)
        pred = np.empty((w, lanes), np.int32)
        for k in range(w):
            pred[k] = np.searchsorted(e_s[k], s_s[k], side="right")
        idx_sorted = np.take_along_axis(idx, order, axis=1)
        packed = PackedSettle(members, idx_sorted, pred, None)
        if sel_scores is not None:
            packed.fill_weights(sel_scores)
        return packed

    # -- batched selection -----------------------------------------------------
    def select(self, packed: PackedSettle, rows, banned=None) -> List[List[int]]:
        """Clear the given windows in one dispatch → pool indices per row
        (ascending end time, matching :func:`wis_select`'s return order)."""
        return self.select_rows(packed, [(k, banned) for k in rows])

    #: the vectorized host DP pays off once the batch carries at least this
    #: many windows-worth of real lanes per lane step (below it, per-row
    #: scalar DP straight from the packed buffers is cheaper — no pow2 pad
    #: work, no per-step numpy kernel overhead)
    _VECTOR_MIN_ROWS = 6.0

    def vectorizes(self, packed: PackedSettle, rows) -> bool:
        """True when :meth:`select_rows` clears these rows in one batched
        DP (the lane-major host DP, or the device kernel); False when each
        row runs the scalar DP from the retained buffers."""
        if self.impl != "numpy":
            return True
        return (int(packed.row_len[rows].sum())
                >= self._VECTOR_MIN_ROWS * packed.lanes)

    def select_rows(self, packed: PackedSettle, requests) -> List[List[int]]:
        """Like :meth:`select` but with a per-row banned mask — the form the
        GlobalAssignment lockstep replays use (rows from different candidate
        configurations share the packed buffers but not their bans)."""
        if not requests:
            return []
        rows = [k for k, _ in requests]
        if not self.vectorizes(packed, rows):
            # small batch (conflict re-clears, narrow rounds): scalar DP
            # per row from the retained sort/pred — identical selections
            return [self._select_row_scalar(packed, k, banned)
                    for k, banned in requests]
        idx_rows = packed.idx_sorted[rows]
        w = packed.wmat[rows]  # fancy indexing copies — safe to mutate
        valid = idx_rows >= 0
        safe = np.where(valid, idx_rows, 0)
        # one masking per run of rows sharing a ban state (a replay's dirty
        # windows are consecutive), over those rows' own lanes only
        start = 0
        for r in range(1, len(requests) + 1):
            if r < len(requests) and requests[r][1] is requests[start][1]:
                continue
            banned = requests[start][1]
            if banned is not None:
                ws = w[start:r]
                ws[valid[start:r] & banned[safe[start:r]]] = 0.0
            start = r
        sel = self._dispatch(w, packed.pred[rows])
        # single nonzero + row bounds instead of W flatnonzero calls
        sel_rows, sel_lanes = np.nonzero(sel)
        flat = idx_rows[sel_rows, sel_lanes].tolist()
        ends = np.cumsum(np.bincount(sel_rows, minlength=len(rows))).tolist()
        return [flat[a:b] for a, b in zip([0] + ends[:-1], ends)]

    @staticmethod
    def _select_row_scalar(packed: PackedSettle, k: int, banned) -> List[int]:
        """One window's WIS from the retained buffers, scalar python DP.

        Skips the re-sort the per-window host path pays on every re-clear
        (order and predecessors were fixed at pack time); python floats ARE
        IEEE float64, so the arithmetic is bit-identical to ``wis_select``.
        Bans are gathered for the row's own lanes only.
        """
        idx, w, p, ia = packed.row(k)
        n = len(idx)
        if n == 0:
            return []
        if banned is not None:
            w = [0.0 if b else x for b, x in zip(banned[ia].tolist(), w)]
        dp = [0.0] * (n + 1)
        take = [False] * n
        for j in range(n):
            with_j = w[j] + dp[p[j]]
            if with_j > dp[j]:
                dp[j + 1] = with_j
                take[j] = True
            else:
                dp[j + 1] = dp[j]
        sel: List[int] = []
        j = n
        while j > 0:
            if take[j - 1]:
                sel.append(idx[j - 1])
                j = p[j - 1]
            else:
                j -= 1
        sel.reverse()
        return sel

    def _dispatch(self, w: np.ndarray, pred: np.ndarray) -> np.ndarray:
        impl = self._effective_impl()
        if impl == "numpy":
            return _batch_dp_backtrack_numpy(w, pred)
        # device path: pad the row dim to its pow2 bucket (zero rows clear
        # empty) so the jit cache is keyed on bucketed shapes only
        from ..kernels.common import KernelDispatchError
        from ..kernels.wis_dp import ops as wis_ops

        r = w.shape[0]
        rb = _bucket(r, MIN_ROW_BUCKET)
        wp, pp = w, pred
        if rb != r:
            wp = np.concatenate([w, np.zeros((rb - r, w.shape[1]), w.dtype)])
            pp = np.concatenate(
                [pred, np.zeros((rb - r, pred.shape[1]), pred.dtype)])
        # degradation ladder (injected faults only): the faulted backend is
        # marked sick (sticky) and the dispatch retries one rung down,
        # ending at the host float64 DP, which cannot fail
        while impl != "numpy":
            try:
                sel, _ = wis_ops.wis_settle_batch(
                    wp.astype(np.float32), pp, impl=impl, mesh=self.mesh)
                return np.asarray(sel)[:r]
            except KernelDispatchError as exc:
                if self.health is None:
                    raise
                self.health.mark_failed(impl, str(exc))
                impl = self.health.resolve(impl)
        return _batch_dp_backtrack_numpy(w, pred)

    # -- fused score→clear dispatch (device backends only) ---------------------
    def predispatch(self, n_windows: int, win_idx, view, handle,
                    transform=None) -> Optional["SettlePrefetch"]:
        """Dispatch the ban-free first-pass WIS against IN-FLIGHT scores.

        Called right after ``score_round_async`` while the scoring dispatch
        is still on the device stream: the selection weights are gathered
        from the device scores array, so the round's scores flow into
        clearing without a host round-trip, and the whole score→clear chain
        overlaps the next round's host preparation.  Host-only backends
        return None (nothing to fuse).

        ``transform`` (optional (M,) float32, aligned with the pool) is the
        clearing policy's selection-weight multiplier — gathered scores are
        multiplied in-dispatch, which is what lets score-transforming
        backends (FairShare's age boost) consume the fused path.
        """
        if not self.device:
            return None
        from .policy.base import _pool_members  # lazy: avoids import cycle

        members = _pool_members(n_windows, win_idx)
        packed = self.pack(members, view, None)
        rb = _bucket(n_windows, MIN_ROW_BUCKET)
        idx = packed.idx_sorted
        pred = packed.pred
        if rb != n_windows:
            pad = np.full((rb - n_windows, packed.lanes), -1, idx.dtype)
            idx = np.concatenate([idx, pad])
            pred = np.concatenate(
                [pred, np.zeros((rb - n_windows, packed.lanes), pred.dtype)])
        from ..kernels.wis_dp import ops as wis_ops

        tr = None
        if transform is not None:
            # pad to the bucket-padded device scores (padded rows are
            # masked lanes; 1.0 keeps the gather shape-stable)
            tr = np.ones(int(handle.device_scores.shape[0]), np.float32)
            tr[: len(transform)] = np.asarray(transform, np.float32)
        from ..kernels.common import KernelDispatchError

        try:
            sel, _ = wis_ops.wis_settle_fused(
                handle.device_scores, idx.astype(np.int32), idx >= 0, pred,
                impl=self._effective_impl(), mesh=self.mesh, transform=tr)
        except KernelDispatchError as exc:
            # an injected fault: speculation is optional, so mark the
            # backend sick and settle without fusion (the settle half
            # re-clears from host scores)
            if self.health is None:
                raise
            self.health.mark_failed(exc.backend, str(exc))
            return None
        return SettlePrefetch(packed, sel, transformed=transform is not None)


def predispatch_settle(selector, backend, n_windows: int, win_idx, view,
                       handle, ages=None) -> Optional[SettlePrefetch]:
    """Dispatch the fused first-pass WIS iff every fusion condition holds.

    The ONE eligibility rule shared by every entry point (clear_round, the
    pipelined round stream, the scheduler's prepare half): the selector is
    a device-backed RoundSelector, the scoring dispatch is still in flight,
    and the clearing backend declares ``supports_prefetch``.  Backends that
    SELECT on transformed scores publish the transform through
    ``prefetch_transform(view, ages)`` (None = identity) and it is applied
    in-dispatch, so the fused first pass matches their selection weights.
    Returns None when any condition fails — callers settle without fusion,
    identically.
    """
    if (isinstance(selector, RoundSelector) and selector.device
            and handle is not None and handle.in_flight
            and getattr(backend, "supports_prefetch", False)):
        get_tr = getattr(backend, "prefetch_transform", None)
        transform = get_tr(view, ages) if get_tr is not None else None
        return selector.predispatch(n_windows, win_idx, view, handle,
                                    transform=transform)
    return None


def make_round_selector(impl: Optional[str], mesh=None, health=None):
    """Map the ``wis_impl`` knob (plus an optional auction mesh) to a selector.

    None or "numpy" → the batched host settle, the float64 host backend
    (byte-identical to the per-window :func:`wis_select` loop, which stays
    the plain reference a caller passes explicitly as ``selector``: the
    round is sorted and packed once, re-clears zero banned weights and run
    from the retained pack); "ref" / "pallas" → the device backends in
    ``kernels/wis_dp`` (float32 DP, fused score→clear dispatch).  ``mesh``
    shards the device backends' window rows
    (``launch.mesh.make_auction_mesh``); host paths ignore it.
    """
    return RoundSelector(impl or "numpy", mesh=mesh, health=health)


#: the default ``selector`` of every settle entry point (the policies'
#: ``settle``, ``fixed_point_settle``, ``clear_round``, ``settle_round``):
#: the batched host settle, shared (a host RoundSelector holds no state)
HOST_SELECTOR = make_round_selector(None)


def wis_select_batch(starts, ends, weights, valid=None, *, impl: str = "numpy"):
    """Batched multi-window WIS over padded (W, L) arrays (test/bench API).

    Returns ``(sel_mask (W, L) bool in ORIGINAL lane order, totals (W,))``.
    Semantically ``wis_select`` applied per row over the valid lanes;
    ``impl`` picks the host float64 path or a device backend.  Totals are
    recomputed on the host in float64 for all impls so they are directly
    comparable against the per-window reference.
    """
    starts = np.asarray(starts, np.float64)
    ends = np.asarray(ends, np.float64)
    weights = np.asarray(weights, np.float64)
    w, lanes = starts.shape
    if valid is None:
        valid = np.ones((w, lanes), bool)
    valid = np.asarray(valid, bool)

    sel = np.zeros((w, lanes), bool)
    if lanes == 0 or w == 0:
        return sel, np.zeros(w, np.float64)
    s = np.where(valid, starts, np.inf)
    e = np.where(valid, ends, np.inf)
    wt = np.where(valid, weights, 0.0)
    order = np.argsort(e, axis=1, kind="stable")
    e_s = np.take_along_axis(e, order, axis=1)
    s_s = np.take_along_axis(s, order, axis=1)
    w_s = np.take_along_axis(wt, order, axis=1)
    pred = np.empty((w, lanes), np.int32)
    for k in range(w):
        pred[k] = np.searchsorted(e_s[k], s_s[k], side="right")
    if impl == "numpy":
        sel_sorted = _batch_dp_backtrack_numpy(w_s, pred)
    else:
        from ..kernels.wis_dp import ops as wis_ops

        dev_sel, _ = wis_ops.wis_settle_batch(
            w_s.astype(np.float32), pred, impl=impl)
        sel_sorted = np.asarray(dev_sel)
    rows = np.repeat(np.arange(w), lanes).reshape(w, lanes)
    sel[rows, order] = sel_sorted
    sel &= valid
    totals = np.where(sel, weights, 0.0).sum(axis=1)
    return sel, totals

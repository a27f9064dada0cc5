"""Jit'd wrappers: on-device WIS clearing (sort → DP kernel → backtrack).

``wis_clear`` has the same contract as ``core.wis.wis_select`` (returns
selected ORIGINAL indices sorted ascending by end time + total weight), so
it can be plugged into ``clearing.clear_window(selector=...)`` directly.

``wis_settle_batch`` / ``wis_settle_fused`` are the batched multi-window
forms behind the device-resident round settle (core/wis.py
``RoundSelector``): one dispatch clears EVERY window of an auction round.
They follow the ``jasda_score`` zero-recompile contract — weights and
predecessor tables are runtime operands, shapes are pow2-bucketed by the
caller, and ``trace_counts`` exposes jit cache misses so benchmarks can
assert the cache is never missed across drifting (W, M) rounds.  The fused
form gathers its weights from the IN-FLIGHT device scores of the round's
``jasda_score`` dispatch, so scores flow into selection without a host
round-trip.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common import check_dispatch_fault, use_interpret
from .kernel import wis_batch_pallas, wis_dp_pallas
from .ref import wis_batch_reference, wis_dp_reference

__all__ = [
    "wis_clear",
    "wis_dp",
    "wis_settle_batch",
    "wis_settle_fused",
    "trace_counts",
]

# Incremented when a batched-settle jit wrapper RETRACES (python body runs
# only on a jit cache miss) — the settle_throughput benchmark asserts these
# stay flat across rounds with drifting (W, M, scores).
TRACE_COUNT = {"settle_ref": 0, "settle_pallas": 0}


def trace_counts() -> dict:
    """Cumulative retrace counters for the batched settle dispatches."""
    return dict(TRACE_COUNT)


@jax.jit
def _settle_ref_jit(weights, pred):
    TRACE_COUNT["settle_ref"] += 1
    return wis_batch_reference(weights, pred)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _settle_pallas_jit(weights, pred, interpret):
    TRACE_COUNT["settle_pallas"] += 1
    return wis_batch_pallas(weights, pred, interpret=interpret)


def _fused_weights(scores, idx, mask, transform):
    """Gather selection weights from in-flight scores, one shared recipe.

    ``transform`` (same length as ``scores``, or None) is a per-pool-index
    selection-weight multiplier — the policy's score transform (FairShare's
    age boost) quantized to float32, applied IN-DISPATCH so transforming
    backends can consume the fused first pass too.  The product of two
    float32 operands rounded to float32 matches the host path's quantized
    transform by construction.
    """
    safe = jnp.clip(idx, 0, scores.shape[0] - 1)
    w = scores[safe].astype(jnp.float32)
    if transform is not None:
        w = w * transform[safe].astype(jnp.float32)
    return jnp.where(mask, w, 0.0)


@jax.jit
def _settle_ref_fused_jit(scores, idx, mask, pred):
    TRACE_COUNT["settle_ref"] += 1
    return wis_batch_reference(_fused_weights(scores, idx, mask, None), pred)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _settle_pallas_fused_jit(scores, idx, mask, pred, interpret):
    TRACE_COUNT["settle_pallas"] += 1
    return wis_batch_pallas(_fused_weights(scores, idx, mask, None), pred,
                            interpret=interpret)


@jax.jit
def _settle_ref_fused_tr_jit(scores, transform, idx, mask, pred):
    TRACE_COUNT["settle_ref"] += 1
    return wis_batch_reference(
        _fused_weights(scores, idx, mask, transform), pred)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _settle_pallas_fused_tr_jit(scores, transform, idx, mask, pred, interpret):
    TRACE_COUNT["settle_pallas"] += 1
    return wis_batch_pallas(
        _fused_weights(scores, idx, mask, transform), pred,
        interpret=interpret)


# ---------------------------------------------------------------------------
# Mesh-sharded settle: partition the window (row) axis over an auction mesh
# ---------------------------------------------------------------------------

# (mesh, impl, interpret, fused, transformed) -> jitted shard_map wrapper;
# one executable per mesh shape, keeping the zero-retrace contract (the
# inner jit cache stays keyed on bucketed (W, L) shapes only).
_SHARDED_SETTLE_CACHE: dict = {}


def _sharded_settle_fn(mesh, impl: str, interpret: bool, fused: bool,
                       transformed: bool):
    key = (mesh, impl, interpret, fused, transformed)
    fn = _SHARDED_SETTLE_CACHE.get(key)
    if fn is not None:
        return fn
    from jax.sharding import PartitionSpec as PS

    row = PS(tuple(mesh.axis_names))
    rep = PS()
    kernel = wis_batch_reference if impl == "ref" else \
        functools.partial(wis_batch_pallas, interpret=interpret)

    if fused:
        # scores (and transform) stay REPLICATED: lanes of any window may
        # index any pool row, so the gather needs the whole scores array —
        # this all-gather of the (M_pad,) score vector is the only
        # cross-shard exchange on the device side of a round
        def body(scores, transform, idx, mask, pred):
            return kernel(_fused_weights(scores, idx, mask, transform), pred)

        if transformed:
            sharded = jax.shard_map(
                body, mesh=mesh,
                in_specs=(rep, rep, row, row, row),
                out_specs=(row, row), check_vma=False)

            @jax.jit
            def call(scores, transform, idx, mask, pred):
                TRACE_COUNT["settle_ref" if impl == "ref" else "settle_pallas"] += 1
                return sharded(scores, transform, idx, mask, pred)
        else:
            sharded = jax.shard_map(
                lambda scores, idx, mask, pred: body(scores, None, idx, mask, pred),
                mesh=mesh, in_specs=(rep, row, row, row),
                out_specs=(row, row), check_vma=False)

            @jax.jit
            def call(scores, idx, mask, pred):
                TRACE_COUNT["settle_ref" if impl == "ref" else "settle_pallas"] += 1
                return sharded(scores, idx, mask, pred)
    else:
        sharded = jax.shard_map(
            kernel, mesh=mesh, in_specs=(row, row),
            out_specs=(row, row), check_vma=False)

        @jax.jit
        def call(weights, pred):
            TRACE_COUNT["settle_ref" if impl == "ref" else "settle_pallas"] += 1
            return sharded(weights, pred)

    _SHARDED_SETTLE_CACHE[key] = call
    return call


def _settle_shards(mesh, rows: int) -> int:
    """Shard count for a (rows, L) settle under ``mesh`` (1 = unsharded)."""
    if mesh is None:
        return 1
    from ...distributed.sharding import auction_row_spec, mesh_size, spec_sharded

    n = mesh_size(mesh)
    if n <= 1 or not spec_sharded(auction_row_spec(mesh, rows)):
        return 1
    return n


def wis_settle_batch(weights, pred, *, impl: Optional[str] = None, mesh=None):
    """Batched multi-window WIS: (W, L) sorted weights/pred → (sel, totals).

    Rows are windows, lanes candidates sorted ascending by end time (the
    host pack in core/wis.py produces the layout); padded / banned lanes
    carry weight 0 and are provably never selected under the strict ``>``
    tie rule.  Returns jax arrays (left in flight — np.asarray to block).

    ``mesh`` shards the window (row) axis via ``shard_map``: each shard
    clears its rows independently (the per-row DP never crosses rows), so
    the sharded dispatch is byte-identical to the single-device one.
    Non-dividing or single-device meshes fall back to unsharded.
    """
    weights = jnp.asarray(weights, jnp.float32)
    pred = jnp.asarray(pred, jnp.int32)
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    check_dispatch_fault(impl, "wis_settle_batch", weights.shape)
    if _settle_shards(mesh, weights.shape[0]) > 1:
        return _sharded_settle_fn(mesh, impl, use_interpret(), False, False)(
            weights, pred)
    if impl == "ref":
        return _settle_ref_jit(weights, pred)
    return _settle_pallas_jit(weights, pred, use_interpret())


def wis_settle_fused(scores, idx, mask, pred, *, impl: Optional[str] = None,
                     mesh=None, transform=None):
    """Fused score→clear dispatch: gather weights from IN-FLIGHT scores.

    ``scores`` is the (M_pad,) device array of a ``jasda_score`` round
    dispatch (still in flight); ``idx``/``mask``/``pred`` are the host-built
    (W, L) sorted-lane layout (pool index per lane, validity, predecessor
    counts).  The gather chains on the scoring computation on the async
    stream, so the round's selection never waits on a device→host→device
    round-trip.  Returns the in-flight (sel, totals) pair.

    ``transform`` (optional (M_pad,) float32) multiplies each gathered
    score in-dispatch — the clearing policy's selection transform
    (``ClearingPolicy.prefetch_transform``), which is what lets
    score-transforming backends (FairShare) ride the fused path.  ``mesh``
    shards the window rows; scores/transform stay replicated (any lane may
    gather any pool row).
    """
    scores = jnp.asarray(scores)
    idx = jnp.asarray(idx, jnp.int32)
    mask = jnp.asarray(mask, bool)
    pred = jnp.asarray(pred, jnp.int32)
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if transform is not None:
        transform = jnp.asarray(transform, jnp.float32)
    check_dispatch_fault(impl, "wis_settle_fused", idx.shape)
    if _settle_shards(mesh, idx.shape[0]) > 1:
        fn = _sharded_settle_fn(mesh, impl, use_interpret(), True,
                                transform is not None)
        if transform is not None:
            return fn(scores, transform, idx, mask, pred)
        return fn(scores, idx, mask, pred)
    if transform is not None:
        if impl == "ref":
            return _settle_ref_fused_tr_jit(scores, transform, idx, mask, pred)
        return _settle_pallas_fused_tr_jit(scores, transform, idx, mask, pred,
                                           use_interpret())
    if impl == "ref":
        return _settle_ref_fused_jit(scores, idx, mask, pred)
    return _settle_pallas_fused_jit(scores, idx, mask, pred, use_interpret())


def wis_dp(weights, pred, *, impl: Optional[str] = None):
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl == "ref":
        return wis_dp_reference(jnp.asarray(weights), jnp.asarray(pred))
    return wis_dp_pallas(
        jnp.asarray(weights), jnp.asarray(pred), interpret=use_interpret()
    )


def wis_clear(starts, ends, weights, *, impl: Optional[str] = None) -> Tuple[np.ndarray, float]:
    """Drop-in optimal WIS selector backed by the device DP."""
    starts = np.asarray(starts, np.float64)
    ends = np.asarray(ends, np.float64)
    weights = np.asarray(weights, np.float64)
    m = starts.shape[0]
    if m == 0:
        return np.zeros((0,), np.int64), 0.0

    order = np.argsort(ends, kind="stable")
    s, e, w = starts[order], ends[order], weights[order]
    pred = np.searchsorted(e, s, side="right").astype(np.int32)

    dp, take = wis_dp(w.astype(np.float32), pred, impl=impl)
    dp = np.asarray(dp)
    take = np.asarray(take)

    sel = []
    j = m
    while j > 0:
        if take[j - 1]:
            sel.append(j - 1)
            j = pred[j - 1]
        else:
            j -= 1
    sel = np.array(sel[::-1], dtype=np.int64)
    return order[sel], float(dp[-1]) if m else 0.0

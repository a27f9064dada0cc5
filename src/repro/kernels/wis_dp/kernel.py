"""WIS clearing DP as a Pallas TPU kernel (paper §4.4).

Rationale: when the variant pool is produced on-device by ``jasda_score``,
clearing on-device avoids a host round-trip per scheduling iteration — at
high iteration rates (the paper's "fine-grained, high-frequency scheduling
regimes") the PCIe hop would dominate.  A GPU port of this DP is a
single-threaded loop in one thread block; on TPU it runs on the scalar
unit, one grid program per window.

Layout.  The recurrence reads ``dp[p_j]`` at a data-dependent index.
Rows live in HBM as ``(W, *row)`` arrays and each grid program copies its
window's weight and predecessor rows in by DMA, runs the forward DP into a
``dp`` table, backtracks, and copies the selection out.  ``take[j]`` is
never stored: under the strict ``>`` rule it equals ``dp[j+1] > dp[j]``,
and the backtrack writes the selection into the weight buffer (weights are
dead by then), so a window costs 12 bytes per lane.  Two row layouts,
chosen from the lane count L:

* L ≤ :data:`SMEM_MAX_LANES`: ``(1, L)`` rows in scalar memory (SMEM),
  read and written one scalar at a time — the fast path; 2¹⁶ lanes fill
  most of the 1 MiB SMEM of a v5e core.
* larger L: ``(L/1024, 8, 128)`` tables in VMEM (Mosaic has no scalar
  access to VMEM, so a lane is read by a masked reduction of its tile and
  written by a masked tile store), up to :data:`VMEM_MAX_LANES`.  L must
  be a multiple of 1024, which the settle pack's pow2 lane buckets are.

The O(M log M) sort + predecessor search stays OUTSIDE the kernel (host
pack / ops.py); the kernel is the O(M) data-dependent part XLA cannot fuse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

__all__ = ["wis_dp_pallas", "wis_batch_pallas", "SMEM_MAX_LANES",
           "VMEM_MAX_LANES"]

#: widest lane row one program holds in SMEM (w + pred + dp, 12 B a lane)
SMEM_MAX_LANES = 1 << 16
#: widest row of the VMEM table layout, under a 96 MiB VMEM budget
VMEM_MAX_LANES = 1 << 23
_TILE = 8 * 128  # lanes in one (8, 128) VMEM tile


class _SmemRow:
    """A (1, L) SMEM buffer addressed by lane."""

    def __init__(self, ref):
        self.ref = ref

    def __getitem__(self, j):
        return self.ref[0, j]

    def __setitem__(self, j, value):
        self.ref[0, j] = value

    def fill(self, value, m: int) -> None:
        def body(j, carry):
            self.ref[0, j] = value
            return carry

        jax.lax.fori_loop(0, m, body, 0)


class _VmemTable:
    """An (R, 8, 128) VMEM table addressed by lane, for rows past SMEM.

    Lane j sits in tile j // 1024.  A read loads the tile and max-reduces
    it with every other lane masked to the dtype's minimum (exact, and
    blind to whatever the unwritten lanes hold); a write rewrites the tile
    with the lane replaced.
    """

    def __init__(self, ref):
        self.ref = ref

    @staticmethod
    def _onehot(j):
        sub = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
        return sub * 128 + lane == j % _TILE

    def __getitem__(self, j):
        tile = self.ref[j // _TILE]
        low = (-jnp.inf if jnp.issubdtype(tile.dtype, jnp.floating)
               else jnp.iinfo(tile.dtype).min)
        return jnp.max(jnp.where(self._onehot(j), tile,
                                 jnp.full_like(tile, low)))

    def __setitem__(self, j, value):
        r = j // _TILE
        tile = self.ref[r]
        self.ref[r] = jnp.where(self._onehot(j),
                                jnp.asarray(value, tile.dtype), tile)

    def fill(self, value, m: int) -> None:
        self.ref[...] = jnp.full(self.ref.shape, value, self.ref.dtype)


def _layout(m: int):
    """(row shape, table class, scratch, compiler params) for m lanes."""
    if m <= SMEM_MAX_LANES:
        return ((1, m), _SmemRow,
                [pltpu.SMEM((1, m), jnp.float32), pltpu.SMEM((1, m), jnp.int32),
                 pltpu.SMEM((1, m + 1), jnp.float32)], None)
    if m > VMEM_MAX_LANES or m % _TILE:
        raise ValueError(
            f"{m} lanes fit neither the SMEM row layout (<= {SMEM_MAX_LANES}) "
            f"nor the VMEM table layout (multiples of {_TILE} up to "
            f"{VMEM_MAX_LANES})")
    r = m // _TILE
    return ((r, 8, 128), _VmemTable,
            [pltpu.VMEM((r, 8, 128), jnp.float32),
             pltpu.VMEM((r, 8, 128), jnp.int32),
             pltpu.VMEM((r + 1, 8, 128), jnp.float32)],
            pltpu.CompilerParams(vmem_limit_bytes=12 * m + (8 << 20)))


def _load_rows(w_hbm, p_hbm, w_buf, p_buf, sem, row):
    cw = pltpu.make_async_copy(w_hbm.at[row], w_buf, sem.at[0])
    cp = pltpu.make_async_copy(p_hbm.at[row], p_buf, sem.at[1])
    cw.start()
    cp.start()
    cw.wait()
    cp.wait()


def _store_row(buf, out_hbm, sem, row):
    cp = pltpu.make_async_copy(buf, out_hbm.at[row], sem.at[0])
    cp.start()
    cp.wait()


def _forward(w, p, dp, m: int) -> None:
    """dp[0] = 0; dp[j+1] = max(dp[j], w[j] + dp[p[j]]).

    The table is not zeroed, so ``p[j]`` is clamped to j: a padded lane
    (weight 0, predecessor count L) then reads dp[j] instead of a stale
    entry and, as in the reference, is not taken.  Real lanes have
    p[j] <= j already.
    """
    dp[0] = jnp.float32(0.0)

    def body(j, carry):
        dp[j + 1] = jnp.maximum(dp[j], w[j] + dp[jnp.minimum(p[j], j)])
        return carry

    jax.lax.fori_loop(0, m, body, 0)


def _dp_kernel(w_hbm, p_hbm, dp_hbm, w_buf, p_buf, dp_buf, sem, *, m: int,
               table):
    _load_rows(w_hbm, p_hbm, w_buf, p_buf, sem, 0)
    w, dp = table(w_buf), table(dp_buf)
    _forward(w, table(p_buf), dp, m)

    def out(j, carry):
        w[j] = dp[j + 1]
        return carry

    jax.lax.fori_loop(0, m, out, 0)
    _store_row(w_buf, dp_hbm, sem, 0)


def _call(kernel, weights, pred, out_specs, out_shape, *, grid, interpret):
    """Shared pallas_call: (W, L) rows in HBM, one window per program."""
    w, m = weights.shape
    row, table, scratch, params = _layout(m)
    return pl.pallas_call(
        functools.partial(kernel, m=m, table=table),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=out_specs,
        out_shape=out_shape(row),
        scratch_shapes=scratch + [pltpu.SemaphoreType.DMA((2,))],
        compiler_params=params,
        interpret=interpret,
    )(weights.astype(jnp.float32).reshape((w,) + row),
      pred.astype(jnp.int32).reshape((w,) + row))


@functools.partial(jax.jit, static_argnames=("interpret",))
def wis_dp_pallas(weights: jnp.ndarray, pred: jnp.ndarray, *, interpret: bool = False):
    """(M,) sorted-by-end weights + predecessor table → (dp, take)."""
    m = weights.shape[0]
    dp = _call(_dp_kernel, weights[None, :], pred[None, :],
               pl.BlockSpec(memory_space=pl.ANY),
               lambda row: jax.ShapeDtypeStruct((1,) + row, jnp.float32),
               grid=(), interpret=interpret).reshape(m)
    prev = jnp.concatenate([jnp.zeros((1,), jnp.float32), dp[:-1]])
    return dp, dp > prev


# ---------------------------------------------------------------------------
# Batched multi-window DP + backtrack (device-resident settle, one dispatch)
# ---------------------------------------------------------------------------


def _batch_kernel(w_hbm, p_hbm, sel_hbm, total_ref, w_buf, p_buf, dp_buf, sem,
                  *, m: int, table):
    """One grid program = one window: forward DP, then in-kernel backtrack.

    The backward pass is the classical data-dependent walk (j = pred[j-1]
    when lane j-1 was taken, else j-1) as a ``while_loop`` on the cursor;
    it writes 1.0 into the (zeroed) weight buffer at each taken lane.
    """
    row = pl.program_id(0)
    _load_rows(w_hbm, p_hbm, w_buf, p_buf, sem, row)
    w, p, dp = table(w_buf), table(p_buf), table(dp_buf)
    _forward(w, p, dp, m)
    total_ref[0] = dp[m]
    w.fill(0.0, m)

    def bwd(j):
        taken = dp[j] > dp[j - 1]
        w[j - 1] = jnp.where(taken, 1.0, 0.0).astype(jnp.float32)
        return jnp.where(taken, p[j - 1], j - 1)

    jax.lax.while_loop(lambda j: j > 0, bwd, jnp.int32(m))
    _store_row(w_buf, sel_hbm, sem, row)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wis_batch_pallas(weights: jnp.ndarray, pred: jnp.ndarray, *, interpret: bool = False):
    """Batched WIS: (W, L) sorted weights + predecessors → (sel, totals).

    Same per-row contract as ``wis_batch_reference`` (ref.py): rows are
    windows, lanes are candidates sorted ascending by end time, padded /
    banned lanes carry weight 0 (never taken under the strict ``>`` rule).
    Returns the selection mask in sorted lane order plus per-window optimal
    totals — the whole round's clearing in ONE dispatch.
    """
    w, m = weights.shape
    sel, total = _call(
        _batch_kernel, weights, pred,
        [pl.BlockSpec(memory_space=pl.ANY),
         pl.BlockSpec((None, None, 1), lambda i: (i, 0, 0),
                      memory_space=pltpu.SMEM)],
        lambda row: [jax.ShapeDtypeStruct((w,) + row, jnp.float32),
                     jax.ShapeDtypeStruct((w, 1, 1), jnp.float32)],
        grid=(w,), interpret=interpret)
    return sel.reshape(w, m) > 0, total.reshape(w)

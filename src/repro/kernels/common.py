"""Shared kernel utilities: dispatch policy, fault injection + numerics.

Besides the numerics helpers this module owns the kernel packages' fault
surface.  :func:`inject_dispatch_fault` is the deterministic fault-
injection hook for tests and the simulator's ``device_dispatch_fail``
event: it arms ONE failure for a named backend, and the next dispatch on
that backend raises :class:`KernelDispatchError` (backend + bucketed
operand shape attached) before touching the device.  :class:`BackendHealth`
is the sticky per-backend ladder state the scheduler uses to walk pallas →
ref → host numpy on such an injected fault, never re-trying a backend that
failed once (so the zero-retrace contract holds per HEALTHY backend).

Only injected faults walk the ladder.  A real XLA / Mosaic error from a
dispatch, a compile or an in-flight result propagates as raised and ends
the run: a silent step down the ladder would hide that the device path
never ran.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "use_interpret",
    "log_ndtr",
    "NEG_INF",
    "KernelDispatchError",
    "BackendHealth",
    "DEGRADATION_LADDER",
    "inject_dispatch_fault",
    "clear_dispatch_faults",
    "check_dispatch_fault",
    "dispatch_faults_snapshot",
    "restore_dispatch_faults",
]

NEG_INF = -1e30  # large-negative for masking (avoids inf-inf NaNs in bf16)

#: backend order the scheduler walks on an injected dispatch fault; "numpy"
#: is the host float64 reference and never raises KernelDispatchError.
DEGRADATION_LADDER = ("pallas", "ref", "numpy")


class KernelDispatchError(RuntimeError):
    """An injected dispatch fault; carries backend + bucketed operand shape.

    Raised only by :func:`check_dispatch_fault`, so catching this type
    (the degradation ladder does) can never swallow a real device error.
    """

    def __init__(self, backend: str, op: str,
                 shape: Tuple[int, ...] = (),
                 cause: Optional[BaseException] = None):
        self.backend = backend
        self.op = op
        self.shape = tuple(int(s) for s in shape)
        self.cause = cause
        detail = f" <- {type(cause).__name__}: {cause}" if cause else ""
        super().__init__(
            f"{op}[{backend}] dispatch failed at bucket shape "
            f"{self.shape}{detail}")


class BackendHealth:
    """Sticky per-backend health: once a backend fails it stays failed.

    Filled only by injected faults (:class:`KernelDispatchError`), so an
    empty :meth:`failed_backends` means every dispatch ran where it was
    asked to.  One instance is shared by a scheduler's scoring AND settle
    dispatches so a pallas fault observed while scoring also steers the
    round's WIS settle away from pallas.  ``resolve(preferred)`` walks the
    degradation ladder from the preferred backend to the first healthy
    one ("numpy" is always healthy — the host reference path has no
    device to lose).  Stickiness is what makes fault landing
    deterministic across serial and pipelined runs: after the first
    failure the chosen backend no longer depends on WHEN subsequent
    dispatches happen.
    """

    def __init__(self) -> None:
        self._failed: Dict[str, str] = {}

    def mark_failed(self, backend: str, reason: str = "") -> None:
        self._failed.setdefault(backend, reason)

    def healthy(self, backend: str) -> bool:
        return backend not in self._failed

    def resolve(self, preferred: str) -> str:
        """First healthy backend at or below ``preferred`` on the ladder."""
        if preferred not in DEGRADATION_LADDER:
            return preferred if self.healthy(preferred) else "numpy"
        start = DEGRADATION_LADDER.index(preferred)
        for backend in DEGRADATION_LADDER[start:]:
            if self.healthy(backend):
                return backend
        return "numpy"

    def failed_backends(self) -> Dict[str, str]:
        return dict(self._failed)

    # snapshot/restore hooks used by checkpointed crash recovery ---------
    def snapshot(self) -> Dict[str, str]:
        return dict(self._failed)

    def restore(self, snap: Dict[str, str]) -> None:
        self._failed = dict(snap)


# Armed one-shot dispatch faults: backend -> remaining failure count.
# Module-level (not per-scheduler) because the dispatch functions in the
# kernel packages are free functions; determinism comes from the FAULT PLAN
# arming them at seeded times, and stickiness of BackendHealth means at most
# the FIRST dispatch after arming observes the fault.
_ARMED_FAULTS: Dict[str, int] = {}


def inject_dispatch_fault(backend: str, count: int = 1) -> None:
    """Arm ``count`` dispatch failures for ``backend`` (test/sim hook)."""
    _ARMED_FAULTS[backend] = _ARMED_FAULTS.get(backend, 0) + int(count)


def clear_dispatch_faults() -> None:
    _ARMED_FAULTS.clear()


def dispatch_faults_snapshot() -> Dict[str, int]:
    """Armed-but-unfired faults (checkpointed so crash restore replays a
    fault armed between the checkpoint and the crash exactly once)."""
    return dict(_ARMED_FAULTS)


def restore_dispatch_faults(snap: Dict[str, int]) -> None:
    _ARMED_FAULTS.clear()
    _ARMED_FAULTS.update({k: int(v) for k, v in snap.items()})


def check_dispatch_fault(backend: str, op: str,
                         shape: Tuple[int, ...] = ()) -> None:
    """Raise KernelDispatchError if a fault is armed for ``backend``.

    The only place a KernelDispatchError is raised.
    """
    n = _ARMED_FAULTS.get(backend, 0)
    if n > 0:
        if n == 1:
            _ARMED_FAULTS.pop(backend, None)
        else:
            _ARMED_FAULTS[backend] = n - 1
        raise KernelDispatchError(
            backend, op, shape,
            cause=RuntimeError("injected dispatch fault"))


@functools.cache
def use_interpret() -> bool:
    """Pallas TPU kernels run in interpret mode on non-TPU backends.

    interpret=True executes the kernel body with jnp on CPU — bit-identical
    control flow to the TPU lowering, used for CI validation against ref.py.
    """
    return jax.default_backend() != "tpu"


# Numerical Recipes' Chebyshev fit of erfc: for y >= 0,
#   erfc(y) = t * exp(-y^2 + P(t)),  t = 1 / (1 + y/2),
# with fractional error below 1.2e-7 everywhere (Press et al., "Numerical
# Recipes", 2nd ed., §6.2).  t * exp(P(t)) is erfcx(y), so log erfc(y) is
# formed in log space and never underflows.  Coefficients low order first.
_ERFC_P = (-1.26551223, 1.00002368, 0.37409196, 0.09678418, -0.18628806,
           0.27886807, -1.13520398, 1.48851587, -0.82215223, 0.17087277)


def log_ndtr(z: jnp.ndarray) -> jnp.ndarray:
    """log Φ(z) from exp / log / log1p and a polynomial only.

    Mosaic lowers none of erf, erfc or expm1, so the kernel body uses the
    rational erfc above.  Left tail (z < 0): log Φ(z) = log erfc(|z|/√2) −
    log 2, exact in log space down to any z.  Right side: log1p(−erfc/2).
    Agrees with ``scipy.special.log_ndtr`` to a relative 2e-5 over
    z ∈ [−40, 10] in float32.
    """
    z = jnp.asarray(z)
    y = jnp.abs(z) * 0.7071067811865476
    t = 1.0 / (1.0 + 0.5 * y)
    poly = _ERFC_P[-1]
    for c in _ERFC_P[-2::-1]:
        poly = poly * t + c
    log_erfc = jnp.log(t) + poly - y * y
    left = log_erfc - 0.6931471805599453
    right = jnp.log1p(-0.5 * jnp.exp(log_erfc))
    return jnp.where(z < 0, left, right)


def expm1(x: jnp.ndarray) -> jnp.ndarray:
    """exp(x) − 1 accurate near 0, from exp and log only (Kahan's form).

    ``jnp.expm1`` has no Mosaic lowering.  With u = exp(x):
    (u − 1) · x / log(u) cancels the rounding of u; u == 1 returns x, and
    u == 0 (x = −inf) returns −1.
    """
    u = jnp.exp(x)
    um1 = u - 1.0
    safe = jnp.logical_and(u != 1.0, um1 != -1.0)
    scaled = um1 * x / jnp.log(jnp.where(safe, u, 2.0))
    return jnp.where(u == 1.0, x, jnp.where(um1 == -1.0, -1.0, scaled))

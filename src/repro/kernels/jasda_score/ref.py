"""Pure-jnp oracle for batched variant scoring + safety (paper §4.1–§4.2).

Given M variants with job-side features X_j (M, Fj), system-side features
X_s (M, Fs), and per-variant FMP grids (mu, sigma) over T points:

    h̃        = clip(X_j @ α, 0, 1)
    f̃_sys    = clip(X_s @ β, 0, 1)
    score     = λ·h̃ + (1−λ)·f̃_sys                      (Eq. 4)
    log_surv  = Σ_t log Φ((c_i − μ_t)/σ_t)               (grid safety)
    p_exceed  = 1 − exp(log_surv)
    eligible  = p_exceed ≤ θ_i                            (condition (a))

``lam``, ``capacity`` and ``theta`` are runtime values — scalars broadcast
over the pool (the legacy overload), or per-variant ``(M,)``/``(M, 1)``
vectors so each bid is verified against the capacity and risk bound of the
window it targets (heterogeneous slices, one dispatch).  The Pallas kernel
(kernel.py) and the host numpy path (ops.score_variants_numpy) implement
identical semantics.

Scores of ineligible variants are zeroed (they never enter clearing).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import expm1, log_ndtr

__all__ = ["score_variants_reference"]


def _per_variant(x, m: int) -> jnp.ndarray:
    """Normalize a scalar / (M,) / (M,1) runtime parameter to (M,) f32."""
    x = jnp.asarray(x, jnp.float32)
    if x.ndim == 0:
        return jnp.broadcast_to(x, (m,))
    return x.reshape(m)


def score_variants_reference(
    feat_job: jnp.ndarray,  # (M, Fj)
    feat_sys: jnp.ndarray,  # (M, Fs)
    alphas: jnp.ndarray,  # (Fj,)
    betas: jnp.ndarray,  # (Fs,)
    mu: jnp.ndarray,  # (M, T)
    sigma: jnp.ndarray,  # (M, T)
    *,
    lam,  # scalar or per-variant (M,)
    capacity,  # scalar or per-variant (M,)
    theta,  # scalar or per-variant (M,)
):
    m = feat_job.shape[0]
    lam_v = _per_variant(lam, m)
    cap_v = _per_variant(capacity, m)[:, None]  # broadcast over T
    th_v = _per_variant(theta, m)

    h = jnp.clip(feat_job @ alphas, 0.0, 1.0)
    f = jnp.clip(feat_sys @ betas, 0.0, 1.0)
    score = lam_v * h + (1.0 - lam_v) * f

    z = (cap_v - mu) / jnp.maximum(sigma, 1e-30)
    z = jnp.where(sigma > 0, z, jnp.where(mu <= cap_v, jnp.inf, -jnp.inf))
    logphi = jnp.where(jnp.isposinf(z), 0.0, log_ndtr(z))
    log_surv = jnp.sum(logphi, axis=-1)
    p_exceed = -expm1(log_surv)
    eligible = p_exceed <= th_v
    return jnp.where(eligible, score, 0.0), eligible, p_exceed

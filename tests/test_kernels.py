"""Per-kernel shape/dtype sweeps: pallas (interpret) vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import mha_pallas
from repro.kernels.flash_attention.ref import mha_reference
from repro.kernels.jasda_score.kernel import score_variants_pallas
from repro.kernels.jasda_score.ref import score_variants_reference
from repro.kernels.linear_scan.kernel import linear_scan_pallas
from repro.kernels.linear_scan.ref import (linear_scan_associative,
                                           linear_scan_reference)
from repro.kernels.wis_dp.kernel import wis_dp_pallas
from repro.kernels.wis_dp.ref import wis_dp_reference


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,sq,sk,d", [
    (2, 4, 2, 256, 256, 64),
    (1, 8, 1, 128, 384, 64),     # MQA + decode-style longer k
    (1, 4, 4, 256, 256, 128),    # MHA, wide head
    (2, 2, 2, 512, 512, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, hq, hkv, sq, sk, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, hq, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, sk, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, sk, d), dtype)
    out = mha_pallas(q, k, v, causal=True, q_offset=sk - sq, interpret=True)
    ref = mha_reference(q, k, v, causal=True, q_offset=sk - sq)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [64, 128, 256])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 1, 256, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 1, 256, 64), jnp.float32)
    out = mha_pallas(q, k, v, causal=True, window=window, interpret=True)
    ref = mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 128, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 128, 64), jnp.float32)
    out = mha_pallas(q, k, v, causal=False, interpret=True)
    ref = mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# linear scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,t,d,bt,bd", [
    (2, 512, 256, 128, 128),
    (1, 1024, 512, 256, 512),
    (3, 256, 128, 256, 128),
])
def test_linear_scan_sweep(b, t, d, bt, bd):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    a = jax.random.uniform(ks[0], (b, t, d), jnp.float32, 0.8, 0.999)
    bb = jax.random.normal(ks[1], (b, t, d), jnp.float32) * 0.1
    h0 = jax.random.normal(ks[2], (b, d), jnp.float32)
    o, hT = linear_scan_pallas(a, bb, h0, block_t=bt, block_d=bd, interpret=True)
    r, rT = linear_scan_reference(a, bb, h0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(rT), atol=1e-4)


def test_associative_scan_matches_sequential():
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    a = jax.random.uniform(ks[0], (2, 300, 64), jnp.float32, 0.5, 1.0)
    b = jax.random.normal(ks[1], (2, 300, 64), jnp.float32)
    h0 = jax.random.normal(ks[2], (2, 64), jnp.float32)
    o1, t1 = linear_scan_associative(a, b, h0)
    o2, t2 = linear_scan_reference(a, b, h0)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4)


# ---------------------------------------------------------------------------
# jasda_score
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,t", [(8, 16), (64, 32), (256, 64), (300, 48)])
def test_jasda_score_sweep(m, t):
    rng = np.random.default_rng(m * 1000 + t)
    fj = rng.uniform(0, 1, (m, 3)).astype(np.float32)
    fs = rng.uniform(0, 1, (m, 3)).astype(np.float32)
    al = np.array([.5, .3, .2], np.float32)
    be = np.array([.4, .2, .2], np.float32)
    mu = rng.uniform(5, 21, (m, t)).astype(np.float32)
    sg = rng.uniform(0.0, 0.8, (m, t)).astype(np.float32)
    sg[rng.uniform(size=(m, t)) < 0.1] = 0.0
    from repro.kernels.jasda_score.ops import score_variants
    s_k, e_k, _ = score_variants(fj, fs, al, be, mu, sg, lam=0.6,
                                 capacity=20.0, theta=0.05, impl="pallas")
    s_r, e_r, _ = score_variants_reference(
        jnp.array(fj), jnp.array(fs), jnp.array(al), jnp.array(be),
        jnp.array(mu), jnp.array(sg), lam=0.6, capacity=20.0, theta=0.05)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), atol=3e-5)
    np.testing.assert_array_equal(np.asarray(e_k), np.asarray(e_r))


def test_jasda_score_safety_matches_trp():
    # kernel's log-space safety must agree with the host evaluator
    from repro.core.trp import prob_exceed_grid
    rng = np.random.default_rng(11)
    mu = rng.uniform(5, 19, (16, 64))
    sg = rng.uniform(0.01, 1.0, (16, 64))
    _, elig, p = score_variants_reference(
        jnp.zeros((16, 3)), jnp.zeros((16, 3)),
        jnp.zeros(3), jnp.zeros(3),
        jnp.array(mu, jnp.float32), jnp.array(sg, jnp.float32),
        lam=0.5, capacity=20.0, theta=0.05)
    for i in range(16):
        p_host = prob_exceed_grid(mu[i], sg[i], 20.0)
        assert float(p[i]) == pytest.approx(p_host, abs=1e-4)


# ---------------------------------------------------------------------------
# wis_dp
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [1, 7, 64, 300])
def test_wis_dp_kernel_matches_ref(m):
    rng = np.random.default_rng(m)
    w = rng.uniform(0, 1, m).astype(np.float32)
    ends = np.sort(rng.uniform(0, 100, m))
    starts = ends - rng.uniform(0.5, 20, m)
    pred = np.searchsorted(ends, starts, side="right").astype(np.int32)
    dp_k, take_k = wis_dp_pallas(jnp.array(w), jnp.array(pred), interpret=True)
    dp_r, take_r = wis_dp_reference(jnp.array(w), jnp.array(pred))
    np.testing.assert_allclose(np.asarray(dp_k), np.asarray(dp_r), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(take_k), np.asarray(take_r))


# rows as the host settle pack lays them out: sorted by end, zero-weight
# padded lanes at the tail with predecessor count L.  "vmem" lowers the
# SMEM row bound so the lanes take the VMEM table layout; each layout gets
# its own lane count so neither reuses the other's jit trace.
@pytest.mark.parametrize("layout,lanes", [("smem", 2048), ("vmem", 3072)])
def test_wis_batch_kernel_layouts_match_ref(monkeypatch, layout, lanes):
    from repro.kernels.wis_dp import kernel as wis_kernel
    from repro.kernels.wis_dp.ref import wis_batch_reference

    rows, real = 3, lanes - 500
    if layout == "vmem":
        monkeypatch.setattr(wis_kernel, "SMEM_MAX_LANES", 1024)
    rng = np.random.default_rng(lanes)
    ends = np.sort(rng.uniform(0, 500, (rows, real)), axis=1)
    starts = ends - rng.uniform(0.5, 20, (rows, real))
    w = np.zeros((rows, lanes), np.float32)
    w[:, :real] = rng.uniform(0, 1, (rows, real))
    w[:, :real][rng.uniform(size=(rows, real)) < 0.2] = 0.0  # banned lanes
    pred = np.full((rows, lanes), lanes, np.int32)
    for k in range(rows):
        pred[k, :real] = np.searchsorted(ends[k], starts[k], side="right")
    sel, tot = wis_kernel.wis_batch_pallas(jnp.array(w), jnp.array(pred),
                                           interpret=True)
    sel_r, tot_r = wis_batch_reference(jnp.array(w), jnp.array(pred))
    np.testing.assert_array_equal(np.asarray(sel), np.asarray(sel_r))
    np.testing.assert_allclose(np.asarray(tot), np.asarray(tot_r), atol=1e-5)
    dp, take = wis_kernel.wis_dp_pallas(jnp.array(w[0]), jnp.array(pred[0]),
                                        interpret=True)
    dp_r, take_r = wis_dp_reference(jnp.array(w[0]), jnp.array(pred[0]))
    np.testing.assert_allclose(np.asarray(dp), np.asarray(dp_r), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(take), np.asarray(take_r))

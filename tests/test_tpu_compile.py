"""The main-path kernels compile for a described TPU v5e, at real widths.

Nothing here runs on a chip: ``topologies.get_topology_desc`` describes a
v5e:2x2 host, and each test lowers and compiles a kernel for one of its
chips with the installed TPU compiler.  That catches what interpret mode
cannot — primitives Mosaic does not lower, block shapes it refuses, and
scratch that overflows SMEM or VMEM.  The topology is described only
inside a fixture, so pytest workers that never run this file never load
the TPU library.  All chip-describing tests live in this one file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.common import log_ndtr
from repro.kernels.jasda_score.kernel import score_variants_pallas
from repro.kernels.wis_dp import ops as wis_ops
from repro.kernels.wis_dp.kernel import (SMEM_MAX_LANES, VMEM_MAX_LANES,
                                        wis_batch_pallas)

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without the chip; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# fj is the calibrated h column (Fj = 1) or the declared features (Fj = 3);
# fs is [utilization, slack, mem_headroom, age]; T = 1 without the safety
# recheck, 32 (the FMP grid) with it — the shapes pool_to_arrays_round packs
@pytest.mark.parametrize("fj,t", [(1, 1), (3, 32)])
def test_score_kernel_compiles_for_v5e(one_chip, fj, t):
    m = 1 << 14
    f32 = jnp.float32
    args = (_spec((m, fj), f32, one_chip), _spec((m, 4), f32, one_chip),
            _spec((fj,), f32, one_chip), _spec((4,), f32, one_chip),
            _spec((m, t), f32, one_chip), _spec((m, t), f32, one_chip))
    col = _spec((m,), f32, one_chip)
    compiled = score_variants_pallas.lower(
        *args, lam=col, capacity=col, theta=col, block_m=256,
        interpret=False).compile()
    assert _has_kernel(compiled)


# (256, 4096): a pod-scale round; SMEM_MAX_LANES: the widest SMEM row;
# 2 * SMEM_MAX_LANES: the first row that takes the VMEM table layout;
# VMEM_MAX_LANES: the widest row the kernel accepts
@pytest.mark.parametrize("w,lanes", [(256, 4096), (8, SMEM_MAX_LANES),
                                     (8, 2 * SMEM_MAX_LANES),
                                     (1, VMEM_MAX_LANES)])
def test_wis_batch_kernel_compiles_for_v5e(one_chip, w, lanes):
    compiled = wis_batch_pallas.lower(
        _spec((w, lanes), jnp.float32, one_chip),
        _spec((w, lanes), jnp.int32, one_chip), interpret=False).compile()
    assert _has_kernel(compiled)


def test_wis_kernel_refuses_rows_past_vmem():
    lanes = 2 * VMEM_MAX_LANES
    with pytest.raises(ValueError, match="VMEM"):
        wis_batch_pallas.lower(jax.ShapeDtypeStruct((1, lanes), jnp.float32),
                               jax.ShapeDtypeStruct((1, lanes), jnp.int32))


@pytest.mark.parametrize("transformed", [False, True])
def test_fused_settle_compiles_for_v5e(one_chip, transformed):
    m_pad, w, lanes = 1 << 15, 128, 512
    idx = _spec((w, lanes), jnp.int32, one_chip)
    mask = _spec((w, lanes), jnp.bool_, one_chip)
    pred = _spec((w, lanes), jnp.int32, one_chip)
    scores = _spec((m_pad,), jnp.float32, one_chip)
    if transformed:
        fn = wis_ops._settle_pallas_fused_tr_jit
        args = (scores, _spec((m_pad,), jnp.float32, one_chip), idx, mask, pred)
    else:
        fn = wis_ops._settle_pallas_fused_jit
        args = (scores, idx, mask, pred)
    compiled = fn.lower(*args, interpret=False).compile()
    assert _has_kernel(compiled)


def test_log_ndtr_matches_scipy():
    from scipy.special import log_ndtr as scipy_log_ndtr

    z = np.linspace(-40.0, 10.0, 100_001)
    got = np.asarray(jax.jit(log_ndtr)(jnp.asarray(z, jnp.float32)),
                     np.float64)
    np.testing.assert_allclose(got, scipy_log_ndtr(z), rtol=2e-5, atol=1e-6)

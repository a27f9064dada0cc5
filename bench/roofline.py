"""Chip peaks and the ``jasda_score`` kernel's operations and bytes.

A kernel's roofline share is the least time the chip could take for the
call — the larger of its operations over peak FLOP/s and its bytes over
peak HBM bandwidth — divided by the time its events took in the device
trace.  A device kind that is not in :data:`PEAKS` is an error.
"""
from __future__ import annotations

from typing import Dict, Tuple

#: device_kind -> peaks; source: Google Cloud documentation, "TPU v5e"
#: (197 TFLOP/s bf16, 819 GB/s HBM per chip)
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


#: the score kernel's events carry the jitted wrapper's name and are the
#: custom call itself (not the copies that lay its operands out)
SCORE_KERNEL = "score_variants_pallas"


def is_score_kernel(event) -> bool:
    return event.matches(SCORE_KERNEL) and "custom-call" in event.name


def custom_call_shapes(text: str):
    """Operand shapes of an HLO custom call, in order, as integer tuples."""
    import re

    args = text.split("custom-call(", 1)
    if len(args) < 2:
        return []
    body = args[1].split("custom_call_target", 1)[0]
    return [tuple(int(d) for d in dims.split(",") if d)
            for dims in re.findall(r"\w+\[([\d,]*)\]", body)]


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r}; "
                            f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def score_cost(m: int, fj: int, fs: int, t: int) -> Tuple[float, float]:
    """(operations, bytes) of one ``jasda_score`` call on padded shapes.

    Inputs read once: job features (m, fj), system features (m, fs), their
    weights (fj,) and (fs,), the memory profile means and deviations
    (m, t) each, and the λ, capacity and θ columns (m,) each, all float32;
    outputs written once: the score (m,) float32 and eligibility (m,)
    int32.  Operations: the two weighted feature sums (2·m·(fj + fs)),
    λ·h + (1 − λ)·f (3·m), per grid point the standardised margin and its
    log-probability (4·m·t), and the survival-to-exceedance step (m).
    """
    ops = 2.0 * m * (fj + fs) + 3.0 * m + 4.0 * m * t + m
    words = m * fj + m * fs + fj + fs + 2 * m * t + 3 * m + 2 * m
    return ops, 4.0 * words


def least_seconds(ops: float, nbytes: float, peaks: Dict[str, float]
                  ) -> Tuple[float, str]:
    """The roofline bound of one call and which side bounds it."""
    t_ops = ops / peaks["flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")

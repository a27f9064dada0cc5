"""The entries of a repartition coordinator's logs that fall in a run's
measured window, for the readers of ``bench/metrics/``.

A tick at simulated time t follows the round at t, so the window's ticks
are those at ``warmup <= t < warmup + rounds * round_dt``, where ``warmup``
is the mix's warm-up in simulated time and ``rounds`` the rounds the window
completed.  Both logs are lists of entries whose first item is that t.
Each log is bounded (the coordinator's ``MAX_TRACE`` ticks of
``frag_trace``, ``MAX_LAYOUT_LOG`` records of ``layout_log``): a full log
whose last entry precedes the window's end may have dropped some of the
window's entries, and is read as nothing.
"""
from __future__ import annotations

from typing import Optional

#: the coordinator's bound on each log
BOUNDS = {"frag_trace": "MAX_TRACE", "layout_log": "MAX_LAYOUT_LOG"}


def in_window(run, log: str) -> Optional[list]:
    """The entries of ``run.service.repartition.<log>`` in the window, or
    None where the run has no coordinator, the coordinator no such log, or
    the log filled before the window's end."""
    coord = getattr(run.service, "repartition", None)
    entries = getattr(coord, log, None)
    if entries is None:
        return None
    dt = float(run.cell.mix["round_dt"])
    lo = float(run.cell.mix["warmup_rounds"]) * dt
    hi = lo + run.rounds * dt
    bound = getattr(coord, BOUNDS[log], None)
    if bound is not None and len(entries) >= bound and \
            entries[-1][0] < hi - 1e-9:
        return None
    return [e for e in entries if lo - 1e-9 <= e[0] < hi - 1e-9]

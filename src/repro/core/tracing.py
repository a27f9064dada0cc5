"""Named host spans at the scheduler's layer boundaries.

``span("settle")`` opens a ``jax.profiler.TraceAnnotation`` named
``jasda.settle``: a host event in the profiler's own trace, on the same
clock as the device operations, so a trace can attribute device idle time
to the scheduler layer that held the host.  When no trace is running the
annotation records nothing and costs about a microsecond.  JAX is imported
on the first span, not when ``repro.core`` loads.

Spans sit at layer boundaries, once per round, never inside a per-window,
per-agent or per-bid loop:

=========================  ====================================================
``jasda.round``            one service tick (metadata ``round``)
``jasda.announce``         window announcement
``jasda.bids``             bid collection from every agent
``jasda.pack``             pool assembly, packing and the score dispatch
``jasda.score.dispatch``   operand packing and the scoring launch (the host
                           scoring itself on small pools)
``jasda.settle``           block on scores, clearing, commit and feedback
``jasda.score.wait``       the block on in-flight device scores
``jasda.settle.clear``     the clearing policy (WIS + conflict fixed point)
``jasda.settle.feedback``  the RoundFeedback build and broadcast
``jasda.repartition``      a repartition tick between rounds (the service's
                           ``RepartitionCoordinator.tick``: policy, drains,
                           moves)
=========================  ====================================================
"""
from __future__ import annotations

__all__ = ["span"]

_annotation = None


def span(name: str, **metadata):
    """A context manager that traces ``jasda.<name>`` (with ``metadata``)."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation(f"jasda.{name}", **metadata)

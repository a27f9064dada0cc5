#!/usr/bin/env python3
"""The program's own spans (``jasda.*``) in a profiler trace.

The scheduler opens a ``jax.profiler.TraceAnnotation`` named ``jasda.<layer>``
at each layer boundary (``repro.core.tracing``).  This module reads those
host events, with their metadata, from the newest ``*.xplane.pb`` under a
trace directory, with ``jax.profiler.ProfileData``, and hands plain lists
to the functions below, which the tests drive with synthetic spans.

The span metrics (``bench/metrics/<metric>.py``) read the run's trace
directory, ``harness.OUT_DIR / "trace" / <cell>``, once per run through
:func:`for_run`.  A trace that holds no ``jasda.*`` span at all comes from
a program without the spans: the readers then return nothing.

Run as a script on a trace directory, it prints one JSON object:

    python3 bench/programspans.py .bench_out/trace/<cell>

* ``idle_by_span``: the first device's idle seconds, by the innermost
  ``jasda.*`` span open at each gap's middle (``"(none)"`` when none is);
* ``longest_rounds``: the five longest ``jasda.round`` spans, each with
  the summed durations of the spans inside it, by name;
* ``per_round``: spans of each name per ``jasda.round``, and in all;
* ``totals_s``: seconds in ``jasda.bids``/``pack``/``settle`` beside the
  harness's ``bench.bids``/``pack``/``settle`` wrappers;
* ``kernel_in_dispatch_wait``: the share of ``jasda_score`` kernel events
  that start after the start of a ``jasda.score.dispatch`` span and end
  before the end of the first ``jasda.score.wait`` span that ends after
  it, which shows that host spans and device events share one clock.
"""
from __future__ import annotations

import json
import os
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tracereduce  # noqa: E402

PREFIX = "jasda."


@dataclass(frozen=True)
class ProgramSpan:
    name: str
    start_ns: float
    end_ns: float
    metadata: Tuple[Tuple[str, str], ...] = ()

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns

    def contains(self, other: "ProgramSpan") -> bool:
        return (self is not other and self.start_ns <= other.start_ns
                and other.end_ns <= self.end_ns)


def read_xplane(path: str) -> List[ProgramSpan]:
    """Every ``jasda.*`` event on the trace's host planes."""
    from jax.profiler import ProfileData

    out: List[ProgramSpan] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append(ProgramSpan(
                        ev.name, float(ev.start_ns),
                        float(ev.start_ns + ev.duration_ns),
                        tuple((str(k), str(v)) for k, v in ev.stats)))
    return sorted(out, key=lambda s: (s.start_ns, -s.end_ns))


_cache: Dict[Tuple[str, float], List[ProgramSpan]] = {}


def load(trace_dir) -> Optional[List[ProgramSpan]]:
    """The spans of the newest trace under ``trace_dir`` (cached by file),
    or None where there is no trace or it holds no ``jasda.*`` span."""
    try:
        path = tracereduce.newest_xplane(trace_dir)
    except tracereduce.TraceError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        _cache[key] = read_xplane(path)
    return _cache[key] or None


def for_run(run) -> Optional[List[ProgramSpan]]:
    """The program spans of a harness run's trace (None: nothing to read)."""
    import harness

    return load(harness.OUT_DIR / "trace" / run.cell.name)


def ms_per_round(spans: Optional[Sequence[ProgramSpan]], name: str,
                 rounds: int) -> Optional[float]:
    """Host ms a round inside ``jasda.<name>``: the sum of its durations
    over ``rounds``; 0.0 where the trace holds program spans but none of
    this name, None where it holds none at all."""
    if not spans or not rounds:
        return None
    full = PREFIX + name
    return sum(s.dur_ns for s in spans if s.name == full) / 1e6 / rounds


def innermost_at(spans: Sequence[ProgramSpan], t_ns: float) -> str:
    """The name of the latest-started span open at ``t_ns``."""
    best = None
    for s in spans:
        if s.start_ns > t_ns:
            break  # sorted by start
        if t_ns < s.end_ns and (best is None or s.start_ns >= best.start_ns):
            best = s
    return best.name if best is not None else "(none)"


def idle_by_span(spans: Sequence[ProgramSpan],
                 device_events: Sequence[tracereduce.DeviceEvent]
                 ) -> Dict[str, float]:
    """Idle seconds of the first device, by the innermost span open at
    each gap's middle."""
    spans = sorted(spans, key=lambda s: (s.start_ns, -s.end_ns))
    devs = sorted({e.device for e in device_events})
    out: Dict[str, float] = {}
    if not devs:
        return out
    gaps = tracereduce.gaps_ns([(e.start_ns, e.end_ns)
                                for e in device_events
                                if e.device == devs[0]])
    for s, e in gaps:
        name = innermost_at(spans, (s + e) / 2)
        out[name] = out.get(name, 0.0) + (e - s) / 1e9
    return out


def longest_rounds(spans: Sequence[ProgramSpan], top: int = 5) -> List[dict]:
    """The ``top`` longest ``jasda.round`` spans with their children's
    summed seconds, by name."""
    rounds = sorted((s for s in spans if s.name == PREFIX + "round"),
                    key=lambda s: -s.dur_ns)[:top]
    out = []
    for r in rounds:
        children: Dict[str, float] = {}
        for s in spans:
            if r.contains(s):
                children[s.name] = children.get(s.name, 0.0) + s.dur_ns / 1e9
        out.append({"round": dict(r.metadata).get("round"),
                    "seconds": r.dur_ns / 1e9, "children_s": children})
    return out


def per_round_counts(spans: Sequence[ProgramSpan]) -> Dict[str, float]:
    """Spans of each name per ``jasda.round`` span, and ``all`` of them."""
    n_rounds = sum(1 for s in spans if s.name == PREFIX + "round")
    if not n_rounds:
        return {}
    counts: Dict[str, int] = {}
    for s in spans:
        counts[s.name] = counts.get(s.name, 0) + 1
    out = {k: v / n_rounds for k, v in sorted(counts.items())}
    out["all"] = len(spans) / n_rounds
    return out


def kernel_in_dispatch_wait(spans: Sequence[ProgramSpan], kernel_events
                            ) -> Optional[float]:
    """Share of kernel events that start after the start of a
    ``jasda.score.dispatch`` and end before the end of the first
    ``jasda.score.wait`` that ends after that dispatch started."""
    dispatch = sorted(s.start_ns for s in spans
                      if s.name == PREFIX + "score.dispatch")
    waits = sorted(s.end_ns for s in spans if s.name == PREFIX + "score.wait")
    if not kernel_events:
        return None
    inside = 0
    for ev in kernel_events:
        starts = [t for t in dispatch if t <= ev.start_ns]
        if not starts:
            continue
        ends = [t for t in waits if t >= starts[-1]]
        if ends and ev.end_ns <= ends[0]:
            inside += 1
    return inside / len(kernel_events)


def summary(trace_dir) -> dict:
    import roofline

    path = tracereduce.newest_xplane(trace_dir)
    events, bench_spans = tracereduce.read_xplane(path)
    spans = read_xplane(path)
    totals = {}
    for layer in ("bids", "pack", "settle"):
        totals[layer] = {
            "jasda": sum(s.dur_ns for s in spans
                         if s.name == PREFIX + layer) / 1e9,
            "bench": sum(s.end_ns - s.start_ns for s in bench_spans
                         if s.name == tracereduce.SPAN_PREFIX + layer) / 1e9}
    kernels = [e for e in events if roofline.is_score_kernel(e)]
    return {"trace": path,
            "idle_by_span": idle_by_span(spans, events),
            "longest_rounds": longest_rounds(spans),
            "per_round": per_round_counts(spans),
            "totals_s": totals,
            "kernel_events": len(kernels),
            "kernel_in_dispatch_wait": kernel_in_dispatch_wait(spans,
                                                               kernels)}


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(f"usage: {sys.argv[0]} <trace directory>")
    print(json.dumps(summary(sys.argv[1]), indent=1))

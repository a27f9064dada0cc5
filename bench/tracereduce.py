"""From a profiler trace to the device numbers the metric readers use.

``load`` reads the newest ``*.xplane.pb`` that ``jax.profiler`` wrote under
a directory, with ``jax.profiler.ProfileData`` and nothing else, and hands
plain event lists to :func:`reduce`, which the tests drive with synthetic
events:

* device events are those on the ``XLA Ops`` line of every ``/device:``
  plane (each operation the chip ran, with its start and duration);
* busy time is the union of those intervals, so overlapping operations
  count once, averaged over the devices that ran any;
* an event matches a kernel where its name or its ``hlo_op``/``long_name``/
  ``tf_op`` stat contains the kernel's name (``DeviceEvent.matches``);
* host spans are the harness's ``bench.<layer>`` annotations; each idle gap
  between device operations is attributed to the span open at its middle,
  or to ``service`` when none is.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
NAME_STATS = ("hlo_op", "long_name", "tf_op", "name", "kernel_details")


class TraceError(RuntimeError):
    pass


@dataclass(frozen=True)
class DeviceEvent:
    device: str
    name: str
    start_ns: float
    dur_ns: float
    stats: Tuple[Tuple[str, str], ...] = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def matches(self, pattern: str) -> bool:
        if pattern in self.name:
            return True
        return any(k in NAME_STATS and pattern in v for k, v in self.stats)


@dataclass(frozen=True)
class HostSpan:
    name: str
    start_ns: float
    end_ns: float


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals: Sequence[Tuple[float, float]]
            ) -> List[Tuple[float, float]]:
    """The idle gaps between the union's pieces, as (start, end)."""
    out, cur_e = [], None
    for s, e in sorted(intervals):
        if cur_e is not None and s > cur_e:
            out.append((cur_e, s))
        cur_e = e if cur_e is None else max(cur_e, e)
    return out


@dataclass
class Reduced:
    window_s: float
    events: List[DeviceEvent]
    spans: List[HostSpan] = field(default_factory=list)

    @property
    def devices(self) -> List[str]:
        return sorted({e.device for e in self.events})

    @property
    def busy_s(self) -> float:
        """Union of device operation time, averaged over devices used."""
        devs = self.devices
        if not devs:
            return 0.0
        per = [union_ns((e.start_ns, e.end_ns) for e in self.events
                        if e.device == d) for d in devs]
        return sum(per) / len(per) / 1e9

    @property
    def idle_share(self) -> float:
        return max(0.0, 1.0 - self.busy_s / self.window_s)

    def span_at(self, t_ns: float) -> str:
        best = None
        for s in self.spans:
            if s.start_ns <= t_ns < s.end_ns and (
                    best is None or s.start_ns > best.start_ns):
                best = s
        return best.name[len(SPAN_PREFIX):] if best is not None else "service"

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        by_op: Dict[str, float] = {}
        for e in self.events:
            name = e.name.split(" = ", 1)[0]
            by_op[name] = by_op.get(name, 0.0) + e.dur_ns / 1e9
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        dev = self.devices[0] if self.devices else None
        gaps = gaps_ns([(e.start_ns, e.end_ns) for e in self.events
                        if e.device == dev])
        longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        idle = [[self.span_at((s + e) / 2), (e - s) / 1e9] for s, e in longest]
        return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": idle}

    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds of the first device, by the host span open."""
        dev = self.devices[0] if self.devices else None
        out: Dict[str, float] = {}
        for s, e in gaps_ns([(ev.start_ns, ev.end_ns) for ev in self.events
                             if ev.device == dev]):
            name = self.span_at((s + e) / 2)
            out[name] = out.get(name, 0.0) + (e - s) / 1e9
        return out


def reduce(window_s: float, device_events: List[DeviceEvent],
           spans: List[HostSpan]) -> Reduced:
    if window_s <= 0:
        raise TraceError("empty trace window")
    return Reduced(window_s=window_s, events=list(device_events),
                   spans=list(spans))


def newest_xplane(trace_dir) -> str:
    files = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def read_xplane(path: str) -> Tuple[List[DeviceEvent], List[HostSpan]]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    events: List[DeviceEvent] = []
    spans: List[HostSpan] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    stats = tuple((str(k), str(v)) for k, v in ev.stats)
                    events.append(DeviceEvent(plane.name, ev.name,
                                              float(ev.start_ns),
                                              float(ev.duration_ns), stats))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(HostSpan(
                            ev.name, float(ev.start_ns),
                            float(ev.start_ns + ev.duration_ns)))
    return events, spans


def load(trace_dir, window_s: float) -> Reduced:
    events, spans = read_xplane(newest_xplane(trace_dir))
    return reduce(window_s, events, spans)

"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The harness records the traced window with ``jax.profiler.trace`` and
marks it with host spans of its own (``jax.profiler.TraceAnnotation``):
``training`` around each ``run()``, ``between trainings`` around the
bookkeeping that separates them. This module reads the file with
``jax.profiler.ProfileData`` alone and returns, per device:

* busy time: the union of the intervals in which an operation ran;
* the device time of each operation, by name;
* exposed collective time: the part of all-gather / all-reduce events
  during which no other operation runs on that device;

and, for the run's ``breakdown``, the operations that took most device
time and the longest idle gaps, each gap labelled with the innermost
host span of the harness that was open when it began.

Device planes are the ``/device:TPU:<n>`` planes. An operation is an
event on the line ``XLA Ops``, named by its HLO instruction (the text
before `` = ``, e.g. ``%edge_scan.13``; Pallas kernels keep their
kernel's name); a collective may also run on ``Async XLA Ops``. A
program is an event on the line ``XLA Modules``, named like
``jit_chunk(<hash>)``. Times are in seconds.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

#: names of collective operations as XLA writes them into the trace
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all", re.I)
#: the harness's own host spans
SPANS = ("training", "between trainings")


@dataclass
class Device:
    name: str
    busy_s: float = 0.0
    op_s: dict[str, float] = field(default_factory=dict)
    op_calls: dict[str, int] = field(default_factory=dict)
    module_s: dict[str, float] = field(default_factory=dict)
    collective_exposed_s: float = 0.0
    gaps: list[tuple[float, float]] = field(default_factory=list)  # (start, length)


@dataclass
class Trace:
    window: tuple[float, float]  # (start, end) of the harness spans
    devices: list[Device]
    spans: list[tuple[float, float, str]]  # (start, end, name), host clock

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def op_time(self, pattern: str, device: Device) -> float:
        """Device seconds of the operations whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(t for n, t in device.op_s.items() if rx.search(n))

    def module_time(self, pattern: str, device: Device) -> float:
        rx = re.compile(pattern)
        return sum(t for n, t in device.module_s.items() if rx.search(n))

    def busiest(self) -> Device:
        return max(self.devices, key=lambda d: d.busy_s)

    def span_at(self, t: float) -> str:
        """Innermost harness span open at host time ``t``."""
        best = None
        for s, e, n in self.spans:
            if s <= t < e and (best is None or s >= best[0]):
                best = (s, e, n)
        return best[2] if best else "outside the spans"

    def breakdown(self, top: int = 10) -> dict:
        dev = self.busiest()
        ops = sorted(dev.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(dev.gaps, key=lambda g: -g[1])[:top]
        return {
            "device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[self.span_at(s), length] for s, length in gaps],
        }


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _subtract(a, b) -> float:
    """Length of the union ``a`` not covered by the union ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``."""
    return event_name.split(" = ", 1)[0]


def reduce_file(path: str) -> Trace:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def reduce_profile(prof) -> Trace:
    spans: list[tuple[float, float, str]] = []
    device_planes = []
    for plane in prof.planes:
        if plane.name.startswith("/device:TPU:") and "TPU" in plane.name:
            device_planes.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in SPANS:
                    spans.append((ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, ev.name))
    if not spans:
        raise ValueError("trace holds none of the harness's spans")
    lo = min(s for s, _, _ in spans)
    hi = max(e for _, e, _ in spans)
    devices = []
    for plane in device_planes:
        dev = Device(plane.name)
        ops, coll = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    if e > lo and s < hi:
                        dev.module_s[ev.name] = dev.module_s.get(ev.name, 0.0) + (min(e, hi) - max(s, lo))
            if line.name not in ("XLA Ops", "Async XLA Ops"):
                continue
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if e <= lo or s >= hi:
                    continue
                is_coll = bool(COLLECTIVE.search(ev.name))
                if line.name == "Async XLA Ops" and not is_coll:
                    continue  # copies and slices in flight overlap the ops they feed
                name = op_name(ev.name)
                dev.op_s[name] = dev.op_s.get(name, 0.0) + (min(e, hi) - max(s, lo))
                dev.op_calls[name] = dev.op_calls.get(name, 0) + 1
                (coll if is_coll else ops).append((s, e))
        if not ops and not coll:
            continue
        busy = _clip(_union(ops + coll), lo, hi)
        dev.busy_s = _length(busy)
        dev.collective_exposed_s = _subtract(_clip(_union(coll), lo, hi), _clip(_union(ops), lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        dev.gaps = [(edges[i], edges[i + 1] - edges[i]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
        devices.append(dev)
    if not devices:
        raise ValueError("trace holds no device operations")
    return Trace(window=(lo, hi), devices=devices, spans=spans)

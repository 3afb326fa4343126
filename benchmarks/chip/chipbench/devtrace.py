"""Reduction of a profiler trace to device busy time, idle gaps and
kernel time.

A traced run records the measured window with ``jax.profiler`` (the
Python tracer off, host TraceMe annotations on).  :func:`read` turns the
``.xplane.pb`` into plain lists; everything after that is arithmetic on
those lists, so the tests can check it on a few hand-made events.

* busy     union of the intervals of the operations on a device's
           "XLA Ops" line, clipped to the window; averaged over devices.
* idle gap an interval of the window in which no operation ran; the
           longest are named by the host span (the benchmark's own
           ``bench.*`` annotations) that overlaps each most; spans that
           only wait (``bench.sleep``) name a gap only where no working
           span does.
* kernel   the summed device time of the events whose name contains the
           kernel's name.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

#: the device line that holds one event per executed HLO operation
OPS_LINE = "XLA Ops"
#: annotation prefix of the benchmark's own host spans
SPAN_PREFIX = "bench."
#: spans in which the host only waits: they name a gap only as a last resort
WAIT_SPANS = ("bench.sleep",)
#: with no device plane (a CPU rehearsal), the host's executions of
#: compiled programs stand in for device operations
CPU_EXECUTE = "PjRtCpuExecutable::Execute"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: dict = dataclasses.field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    device_ops: dict          # device plane name -> [Event]
    host_spans: list          # [Event] of the benchmark's annotations


def read(log_dir: str) -> Trace:
    """The device ops and the benchmark's host spans of the newest trace
    under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    device, host, cpu = {}, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device.setdefault(plane.name, []).extend(
                        Event(e.name, e.start_ns, e.duration_ns,
                              dict(e.stats)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append(Event(e.name, e.start_ns, e.duration_ns))
                    elif e.name == CPU_EXECUTE:
                        cpu.append(Event(e.name, e.start_ns, e.duration_ns))
    if not device and cpu:
        device["/host:CPU"] = cpu
    return Trace(device, host)


def window_of(trace: Trace, name: str = "bench.window") -> tuple:
    """(start, end) in ns of the span that marks the measured window."""
    spans = [s for s in trace.host_spans if s.name == name]
    if len(spans) != 1:
        raise ValueError(f"expected one {name!r} span, found {len(spans)}")
    return spans[0].start_ns, spans[0].end_ns


def union(intervals, lo: float, hi: float) -> list:
    """Sorted disjoint union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy_ns(events, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(((ev.start_ns, ev.end_ns)
                                        for ev in events), lo, hi))


def gaps(events, lo: float, hi: float) -> list:
    """Idle intervals of ``[lo, hi]`` between the events' union."""
    out, t = [], lo
    for s, e in union(((ev.start_ns, ev.end_ns) for ev in events), lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_name(name: str, width: int = 160) -> str:
    """An HLO op's event name without layouts and trailing attributes:
    ``fusion.3 = s32[81199104] fusion(s32[90080000] %consts_1_.1, ...)``."""
    short = re.sub(r"\{[^{}]*\}", "", name).lstrip("%")
    for cut in (", kind=", ", custom_call_target=", ", metadata="):
        short = short.split(cut)[0]
    return short[:width]


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def name_gap(gap: tuple, spans) -> str:
    """The host span that overlaps ``gap`` most: working spans first, and
    among equal overlaps the shortest (innermost) one."""
    g0, g1 = gap
    best = None
    for s in spans:
        if s.name == "bench.window":
            continue
        ov = _overlap(g0, g1, s.start_ns, s.end_ns)
        if ov <= 0:
            continue
        rank = (s.name not in WAIT_SPANS, ov, -s.dur_ns)
        if best is None or rank > best[0]:
            best = (rank, s.name)
    return "no host span" if best is None else best[1][len(SPAN_PREFIX):]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # averaged over devices
    idle_gaps: list               # [(name, seconds)] longest first
    device_ops: list              # [(name, seconds)] most time first
    kernel_events: dict           # kernel name -> [Event] in the window
    n_gaps: int                   # idle gaps in the window, named or not

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def summarize(trace: Trace, kernels=(), top: int = 10) -> Summary:
    lo, hi = window_of(trace)
    if not trace.device_ops:
        raise ValueError("the trace holds no device operations")
    busy = [busy_ns(evs, lo, hi) for evs in trace.device_ops.values()]
    first = sorted(trace.device_ops)[0]
    evs = trace.device_ops[first]
    # Only the ``top`` longest gaps are named: naming scans every span, and
    # a fast server's window holds thousands of gaps and tens of thousands
    # of spans.  The stable sort by length alone keeps the order of ties.
    idle = sorted(((g, (g[1] - g[0]) * 1e-9) for g in gaps(evs, lo, hi)),
                  key=lambda x: -x[1])
    named = [(name_gap(g, trace.host_spans), s) for g, s in idle[:top]]
    per_op: dict = {}
    for dev in trace.device_ops.values():
        for ev in dev:
            d = _overlap(lo, hi, ev.start_ns, ev.end_ns)
            if d > 0:
                key = op_name(ev.name)
                per_op[key] = per_op.get(key, 0.0) + d * 1e-9
    n_dev = len(trace.device_ops)
    ops = sorted(((k, v / n_dev) for k, v in per_op.items()),
                 key=lambda x: -x[1])
    kev = {k: [ev for dev in trace.device_ops.values() for ev in dev
               if k in ev.name and lo <= ev.start_ns and ev.end_ns <= hi]
           for k in kernels}
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(busy) / n_dev * 1e-9,
                   idle_gaps=named, device_ops=ops[:top],
                   kernel_events=kev, n_gaps=len(idle))

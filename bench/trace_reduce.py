"""Reduction of a JAX profiler trace to the numbers the metrics read.

A trace (``<dir>/plugins/profile/<time>/*.xplane.pb``) holds device planes
(``/device:TPU:<n>``) whose ``XLA Ops`` line has one event per operation
run on the chip and whose ``XLA Modules`` line has one event per execution
of a compiled program, and a host plane (``/host:CPU``) with one line per
thread, where the benchmark's ``TraceAnnotation`` spans and the runtime's
own host events sit. All events carry nanoseconds on one clock.

:func:`load` reads the file with nothing but JAX; :func:`reduce` clips
everything to the window (the benchmark's ``bench.window`` span) and
returns busy and idle time, device time by operation and by program, the
idle gaps labelled by the innermost host span open in the middle of each,
and the host spans by name.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"

Interval = Tuple[float, float]


@dataclass
class Event:
    name: str
    start: float          # ns
    end: float            # ns


@dataclass
class Trace:
    #: device id → operations (``XLA Ops``), sorted by start
    ops: Dict[str, List[Event]]
    #: device id → program executions (``XLA Modules``)
    modules: Dict[str, List[Event]]
    #: host events of every host thread (benchmark spans included)
    host: List[Event]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _events(line) -> List[Event]:
    return [Event(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def _flow_ends(line, names=None) -> Dict[object, float]:
    """``_c`` flow id → end (``names`` None) or start of each event."""
    out = {}
    for e in line.events:
        if names is not None and e.name not in names:
            continue
        for k, v in e.stats:
            if k == "_c":
                out[v] = float(e.start_ns if names else
                               e.start_ns + e.duration_ns)
    return out


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file (or the newest one under a directory).

    The device's clock is put onto the host's: a program's execution ends
    before the host's ``CompleteCallbacks`` event of the same flow starts,
    so the device events are shifted by the least such gap (the runtime's
    own conversion was found to put them over a millisecond early)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    module_ends: Dict[object, float] = {}
    callbacks: Dict[object, float] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = _events(line)
                elif line.name == "XLA Modules":
                    modules[plane.name] = _events(line)
                    module_ends.update(_flow_ends(line))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(e for e in _events(line) if e.end > e.start)
                callbacks.update(_flow_ends(line, ("CompleteCallbacks",)))
    gaps_ = [callbacks[c] - t for c, t in module_ends.items()
             if c in callbacks]
    shift = min(gaps_) if gaps_ else 0.0
    for events in (*ops.values(), *modules.values()):
        for e in events:
            e.start += shift
            e.end += shift
        events.sort(key=lambda e: e.start)
    return Trace(ops=ops, modules=modules, host=host)


# ----------------------------------------------------------------------------
# interval arithmetic
# ----------------------------------------------------------------------------
def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(merged: List[Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the merged intervals cover."""
    return sum(e - s for s, e in clip(merged, lo, hi))


def gaps(merged: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in clip(merged, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


# ----------------------------------------------------------------------------
_SUFFIX = re.compile(r"[.(]\d+\)?$")


def base_name(name: str) -> str:
    """``fusion.12`` → ``fusion``; ``jit_step(3)`` → ``jit_step``."""
    return _SUFFIX.sub("", name)


_HLO = re.compile(r"%(\S+) = (\S+)")


def op_label(ev: Event) -> str:
    """What to call a device operation: its HLO instruction name and the
    start of its result type (the trace names an operation by its whole
    HLO text)."""
    m = _HLO.match(ev.name)
    return f"{m.group(1)} = {m.group(2)[:48]}" if m else ev.name[:80]


def is_custom_kernel(ev: Event) -> bool:
    """A Pallas (Mosaic) kernel: compiled into a ``tpu_custom_call``."""
    return 'custom_call_target="tpu_custom_call"' in ev.name


def leaves(events: List[Event]) -> List[Event]:
    """Drop container operations (a ``while`` or ``call`` whose body's
    operations are events of their own)."""
    events = sorted(events, key=lambda e: (e.start, -e.end))
    out = []
    for i, e in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is None or nxt.start >= e.end or e.end == e.start:
            out.append(e)
    return out


def window_of(trace: Trace) -> Interval:
    spans = [e for e in trace.host if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    w = max(spans, key=lambda e: e.end - e.start)
    return w.start, w.end


@dataclass
class Reduced:
    window_s: float
    #: busy seconds, averaged over the devices that ran anything
    busy_s: float
    idle_share: float
    #: device seconds inside the window, by operation label
    op_s: Dict[str, float]
    #: device operations inside the window (all devices; containers such
    #: as a ``while`` left out, so no time is counted twice)
    ops: List[Event]
    #: program base name → device seconds of each execution in the window
    module_runs: Dict[str, List[float]]
    #: idle gaps inside the window: (label of the host span open, seconds)
    idle_gaps: List[Tuple[str, float]]
    #: host span name → intervals (ns) inside the window
    spans: Dict[str, List[Interval]]
    #: merged busy intervals (ns) of all devices together
    busy: List[Interval]

    def top_ops(self, n: int = 10) -> List[list]:
        return [[k, v] for k, v in sorted(self.op_s.items(),
                                           key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> List[list]:
        by: Dict[str, float] = defaultdict(float)
        for label, s in self.idle_gaps:
            by[label] += s
        return [[k, v] for k, v in sorted(by.items(),
                                           key=lambda kv: -kv[1])[:n]]


def innermost(host: List[Event], starts: List[float], t: float, keep,
              lookback: int = 2000) -> Optional[Event]:
    """The shortest host event open at ``t`` for which ``keep`` holds;
    ``host`` is sorted by start and ``starts`` holds its starts. Only the
    ``lookback`` events that started last before ``t`` are searched."""
    best: Optional[Event] = None
    i = bisect.bisect_right(starts, t)
    for e in host[max(0, i - lookback):i]:
        if e.end >= t and keep(e) and (
                best is None or e.end - e.start < best.end - best.start):
            best = e
    return best


def gap_label(host: List[Event], starts: List[float], t: float) -> str:
    """``<benchmark span> / <runtime event>`` open at ``t``: what the
    benchmark was waiting on, and what the runtime was doing."""
    span = innermost(host, starts, t,
                     lambda e: e.name.startswith("bench.")
                     and e.name != WINDOW_SPAN)
    event = innermost(host, starts, t,
                      lambda e: not e.name.startswith(("bench.", "$")))
    return (f"{span.name if span else '-'} / "
            f"{event.name if event else '-'}")


def reduce(trace: Trace, window: Optional[Interval] = None) -> Reduced:
    lo, hi = window if window is not None else window_of(trace)
    win = hi - lo
    busy_per_dev = []
    all_busy: List[Interval] = []
    op_s: Dict[str, float] = defaultdict(float)
    ops: List[Event] = []
    for dev, events in trace.ops.items():
        inside = [e for e in events if e.end > lo and e.start < hi]
        if not inside:
            continue
        ivs = merge([(e.start, e.end) for e in inside])
        busy_per_dev.append(covered(ivs, lo, hi))
        all_busy.extend(ivs)
        inside = leaves(inside)
        for e in inside:
            op_s[op_label(e)] += (min(e.end, hi) - max(e.start, lo)) / 1e9
        ops.extend(inside)
    busy = merge(all_busy)
    busy_s = (sum(busy_per_dev) / len(busy_per_dev) / 1e9
              if busy_per_dev else 0.0)
    module_runs: Dict[str, List[float]] = defaultdict(list)
    for dev, events in trace.modules.items():
        for e in events:
            if e.start >= lo and e.end <= hi:
                module_runs[base_name(e.name)].append((e.end - e.start) / 1e9)
    host = sorted((e for e in trace.host if e.end > lo and e.start < hi),
                  key=lambda e: e.start)
    starts = [e.start for e in host]
    idle = [(gap_label(host, starts, (s + e) / 2), (e - s) / 1e9)
            for s, e in gaps(busy, lo, hi)]
    spans: Dict[str, List[Interval]] = defaultdict(list)
    for e in host:
        if e.name.startswith("bench."):
            spans[e.name].append((max(e.start, lo), min(e.end, hi)))
    return Reduced(window_s=win / 1e9, busy_s=busy_s,
                   idle_share=1.0 - busy_s / (win / 1e9) if win > 0 else 0.0,
                   op_s=dict(op_s), ops=ops, module_runs=dict(module_runs),
                   idle_gaps=idle, spans=dict(spans), busy=busy)


def uncovered_per_span(red: Reduced, name: str) -> List[float]:
    """Seconds of each ``name`` span that no device operation covers."""
    starts = [s for s, _ in red.busy]
    out = []
    for lo, hi in red.spans.get(name, []):
        i = max(0, bisect.bisect_right(starts, lo) - 1)
        j = bisect.bisect_left(starts, hi)
        out.append(((hi - lo) - covered(red.busy[i:j], lo, hi)) / 1e9)
    return out

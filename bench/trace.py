"""Reduce a profiler trace of the measured window to numbers.

The run wraps its window in a ``bench.window`` host span and each call
into the program in ``bench.submit`` / ``bench.step`` / ``bench.stamp``
/ ``bench.wait`` spans. From the trace this module takes, for each TPU:
the union of the intervals in which an XLA operation ran (busy time),
the self time of each operation by name, and each idle gap attributed to
the host span that overlaps it most.

On a TPU the "XLA Ops" line nests events: a ``while`` loop's event spans
the operations of its body. An operation's self time is its duration less
that of the events directly inside it, so the body's work is counted once.
Operations are named by their HLO instruction (``paged_flash_decode_gqa.8``
for ``%paged_flash_decode_gqa.8 = (...) custom-call(...)``).
"""
from __future__ import annotations

import glob
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

WINDOW = "bench.window"
HOST_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                      # mean over devices
    devices: int
    op_seconds: dict = field(default_factory=dict)   # summed over devices
    op_counts: dict = field(default_factory=dict)
    device_ops: list = field(default_factory=list)   # [[name, s]] top
    idle_gaps: list = field(default_factory=list)    # [[host span, s]] top


def union_seconds(intervals, lo: float, hi: float) -> float:
    """Length of the union of (start, end) intervals clipped to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of [lo, hi] between busy intervals."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def attribute(idle, spans) -> dict:
    """Seconds of idle time by the name of the host span overlapping each
    gap most (``host:none`` where no span does)."""
    out: dict = defaultdict(float)
    spans = sorted(spans, key=lambda x: x[1])
    for gs, ge in idle:
        best, best_ov = "host:none", 0.0
        for name, s, e in spans:
            if s >= ge:
                break
            ov = min(e, ge) - max(s, gs)
            if ov > best_ov:
                best, best_ov = name, ov
        out[best] += ge - gs
    return out


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device event."""
    head = event_name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def self_times(events):
    """(name, start, end, self seconds) of each event, nested events'
    time taken out of the event directly around them."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    inner = [0.0] * len(events)
    stack: list[int] = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][2]:
            inner[stack[-1]] += e - s
        stack.append(i)
    return [(n, s, e, (e - s) - inner[i])
            for i, (n, s, e) in enumerate(events)]


def reduce_events(device_events: dict, host_spans: list) -> TraceSummary:
    """device_events: device name -> [(op name, start s, end s)];
    host_spans: [(name, start s, end s)] with one ``bench.window``."""
    wins = [(s, e) for n, s, e in host_spans if n == WINDOW]
    if not wins:
        raise ValueError("no bench.window span in the trace")
    lo, hi = wins[0]
    inner = [x for x in host_spans if x[0] != WINDOW]
    op_s: dict = defaultdict(float)
    op_n: dict = defaultdict(int)
    idle_by: dict = defaultdict(float)
    busy = []
    for dev, evs in sorted(device_events.items()):
        iv = []
        for name, s, e, own in self_times(evs):
            if e <= lo or s >= hi:
                continue
            op_s[name] += own * (min(e, hi) - max(s, lo)) / max(e - s, 1e-12)
            op_n[name] += 1
            iv.append((s, e))
        busy.append(union_seconds(iv, lo, hi))
        for k, v in attribute(gaps(iv, lo, hi), inner).items():
            idle_by[k] += v
    n = max(1, len(busy))
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(((k, v / n) for k, v in idle_by.items()),
                  key=lambda kv: -kv[1])[:10]
    return TraceSummary(window_s=hi - lo, busy_s=sum(busy) / n,
                        devices=len(busy), op_seconds=dict(op_s),
                        op_counts=dict(op_n),
                        device_ops=[[k, v] for k, v in top],
                        idle_gaps=[[k, v] for k, v in idle])


def load_events(path: str):
    """Device op events and bench host spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_events, host_spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((op_name(e.name), e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9)
                               for e in line.events)
            if evs:
                device_events[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host_spans.append(
                            (e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9))
    return device_events, host_spans


def summarize(trace_dir) -> TraceSummary:
    files = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile" /
                                 "*" / "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    device_events, host_spans = load_events(files[-1])
    if not device_events:
        raise ValueError("the trace holds no TPU operation")
    return reduce_events(device_events, host_spans)

"""Reduction of a profiler trace to the device's busy time, its idle gaps
and its heaviest operations.

The traced run wraps its measured window in a host span named ``window``;
the benchmark's other host spans (``client``, ``pump``, ``wait``,
``feed``, ``train_step``) say what the host was doing.  For each device:
busy is the union of the intervals of its operations inside the window.
``busy_s`` is the mean over devices; an idle gap is a stretch of the window
in which a device ran nothing, labelled by the host span that covers most
of it (``host:other`` where none does).
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

__all__ = ["Events", "load", "reduce_events", "union_length", "HOST_SPANS",
           "WINDOW_SPAN", "OP_LINES"]

WINDOW_SPAN = "window"
HOST_SPANS = ("client", "pump", "wait", "feed", "train_step")
# the line of a device plane that holds one event per executed operation;
# a plane without it is read from the line of executed programs, whose
# union is the same busy time (its heaviest entries are then programs)
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
TOP = 10
# one plane per chip: "/device:TPU:0"; sub-planes ("/device:TPU:0 ...")
# and custom planes are not chips
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")


class Events:
    """What the reduction reads from a trace, in nanoseconds."""

    def __init__(self, device_ops, host_spans, window):
        self.device_ops = device_ops    # {device: [(start, end, name), ...]}
        self.host_spans = host_spans    # [(start, end, name), ...]
        self.window = window            # (start, end)


def _is_device(name: str) -> bool:
    return bool(_DEVICE_PLANE.match(name)) and not name.startswith(
        "/device:CUSTOM")


def load(trace_dir: str) -> Events:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(files[-1]))


def _op_name(name: str) -> str:
    """An op event's name; a TPU names its ops by their whole HLO
    instruction ("%fusion.12 = f32[...] fusion(...), ..."), of which the
    instruction's own name is kept."""
    if name.startswith("%") and " = " in name:
        return name[1:].split(" = ", 1)[0]
    return name


def from_profile(pd) -> Events:
    ops, spans, window = {}, [], None
    for plane in pd.planes:
        if _is_device(plane.name):
            lines = {line.name: line for line in plane.lines}
            names = ([n for n in OP_LINES if n in lines]
                     or [n for n in MODULE_LINES if n in lines])
            if not names:
                raise ValueError(f"device plane {plane.name!r} has none of "
                                 f"the lines {OP_LINES + MODULE_LINES}; it "
                                 f"has {sorted(lines)}")
            ops[plane.name] = [(e.start_ns, e.start_ns + e.duration_ns,
                                _op_name(e.name))
                               for n in names for e in lines[n].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in HOST_SPANS:
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    if window is None:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} host span")
    return Events(ops, spans, window)


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_length(intervals) -> float:
    return float(sum(e - s for s, e in _merge(intervals)))


def reduce_events(ev: Events) -> dict:
    """-> {busy_s, window_s, device_ops: [[name, s]], idle_gaps: [[label,
    s]]}, ``device_ops`` and ``idle_gaps`` at most ten entries each, the
    largest first.  Raises where no device operation ran in the window."""
    lo, hi = ev.window
    busy, op_time, gaps = [], collections.Counter(), []
    spans = _Spans(ev.host_spans)
    for dev, evs in sorted(ev.device_ops.items()):
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in evs
                  if e > lo and s < hi]
        merged = _merge([(s, e) for s, e, _ in inside])
        busy.append(sum(e - s for s, e in merged))
        for s, e, n in inside:
            op_time[n] += e - s
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, spans.label(a, b)))
    if not busy or max(busy) <= 0:
        raise ValueError("no device operation ran inside the window")
    n_dev = len(busy)
    by_label = collections.Counter()
    for length, label in gaps:
        by_label[label] += length
    gaps.sort(key=lambda g: -g[0])
    return {
        "busy_s": sum(busy) / n_dev * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": [[n, t / n_dev * 1e-9] for n, t in op_time.most_common(TOP)],
        "idle_gaps": [[label, length / n_dev * 1e-9]
                      for length, label in gaps[:TOP]],
        "idle_by_host_span": {k: v / n_dev * 1e-9 for k, v in by_label.items()},
    }


class _Spans:
    """Host spans sorted by start, for labelling gaps."""

    def __init__(self, spans):
        self.spans = sorted(spans)
        self.starts = [s for s, _, _ in self.spans]
        self.longest = max((e - s for s, e, _ in self.spans), default=0)

    def label(self, a, b) -> str:
        """The host span that covers most of [a, b)."""
        cover = collections.Counter()
        i = bisect.bisect_left(self.starts, a - self.longest)
        for s, e, n in self.spans[i:]:
            if s >= b:
                break
            if e > a:
                cover[n] += min(e, b) - max(s, a)
        return cover.most_common(1)[0][0] if cover else "host:other"

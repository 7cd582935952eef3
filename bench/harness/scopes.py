"""Device time by model layer, and idle time by the serving loop's spans.

The program names its work in two ways, both on the profiler's clock:

- each stage of the model runs under a ``jax.named_scope`` named
  ``<family>.<layer>``, for the family the cell's configuration names
  (the ``mace`` family: ``mace.edge``, ``mace.radial``, ``mace.conv``,
  ``mace.chain``, ``mace.mix_gate``, ``mace.readout``), which XLA keeps as
  the ``op_name`` path of every instruction, e.g.
  ``jit(batched)/vmap(transpose(jvp(mace.conv)))/mul``;
- the serving loop writes host spans named ``serve.*`` (``serve.admit``,
  ``serve.stage``, ``serve.dispatch``, ``serve.block``, ``serve.retire``),
  inside the benchmark's own ``pump``; a pool's spans carry its bucket as
  ``pool=<label>``.

`reduce` reads both from a trace that `trace.load` also reads, over the
same window and devices.  A device op is charged to the last
``<family>.<layer>`` of its path; every op whose path holds ``transpose(``
belongs to the force backward, split by the same rule; the rest is
``unscoped``.  Each stretch of device time is charged once, to the
innermost op that covers it: an op that encloses others on its line (a
``while`` around its body) keeps only the time no inner op covers, so the
layers add up to the busy time.  Each stretch of idle time is charged to
the innermost span that covers it, and to that span's pool where it has
one.

A TPU names each op event by its HLO instruction's text and keeps the path
in the ``tf_op`` stat of the event's metadata, which
`jax.profiler.ProfileData` does not expose; `metadata_paths` reads it from
the trace file itself.
"""
from __future__ import annotations

import bisect
import collections
import glob
import heapq
import os
import re

from . import trace as TR

__all__ = ["FORCE_BACKWARD", "UNSCOPED", "PROGRAM_SPAN_PREFIX",
           "reduce", "reduce_profile", "metadata_paths", "charge_innermost",
           "device_by_layer", "idle_gaps", "idle_by_program_span",
           "idle_by_pool", "layer_of"]

FORCE_BACKWARD = "force_backward"
UNSCOPED = "unscoped"
PROGRAM_SPAN_PREFIX = "serve."


def layer_of(path: str, family: str):
    """-> (layer or None, backward?) of one op's path, whose layers are
    the scopes ``<family>.<layer>``."""
    scopes = re.findall(re.escape(family) + r"\.(\w+)", path)
    return (scopes[-1] if scopes else None), "transpose(" in path


def charge_innermost(intervals) -> collections.Counter:
    """``intervals`` [(start, end, key)]: each instant covered by any of
    them is charged once, to the innermost interval covering it: the one
    that started last; of two that started together, the one that ends
    first; of two alike, the one listed later.  -> Counter key -> time; its
    total is the union's length."""
    out = collections.Counter()
    if not intervals:
        return out
    order = sorted(intervals, key=lambda iv: iv[0])
    points = sorted({x for s, e, _ in order for x in (s, e)})
    active, i = [], 0
    for x, nxt in zip(points, points[1:]):
        while i < len(order) and order[i][0] <= x:
            s, e, key = order[i]
            heapq.heappush(active, (-s, e, -i, key))
            i += 1
        while active and active[0][1] <= x:
            heapq.heappop(active)
        if active:
            out[active[0][3]] += nxt - x
    return out


def device_by_layer(device_ops, op_paths, lo, hi, family):
    """``device_ops`` {device: [(start, end, name)]} as `trace.Events` holds
    them, ``op_paths`` {device: [path of each op]}, ``family`` the prefix
    of the model's layer scopes.  -> ({layer |
    force_backward | unscoped: seconds}, {layer: seconds of its force
    backward}), each the mean over devices of the device time inside
    [lo, hi); (None, None) where no op carries a path."""
    if not any(any(p) for p in op_paths.values()):
        return None, None
    total = collections.Counter()
    for dev, evs in device_ops.items():
        keyed = []
        for (s, e, _), path in zip(evs, op_paths.get(dev, ())):
            if e > lo and s < hi:
                layer, backward = layer_of(path, family)
                if backward:
                    key = (FORCE_BACKWARD, layer or UNSCOPED)
                else:
                    key = (layer or UNSCOPED, None)
                keyed.append((max(s, lo), min(e, hi), key))
        total.update(charge_innermost(keyed))
    n_dev = len(device_ops)
    by_layer, backward = collections.Counter(), collections.Counter()
    for (name, sub), t in total.items():
        by_layer[name] += t / n_dev * 1e-9
        if sub is not None:
            backward[sub] += t / n_dev * 1e-9
    return dict(by_layer), dict(backward)


class _Index:
    """Spans sorted by start, for the ones that overlap a stretch."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda sp: sp[0])
        self.starts = [s for s, _, _ in self.spans]
        self.longest = max((e - s for s, e, _ in self.spans), default=0)

    def clipped(self, a, b):
        i = bisect.bisect_left(self.starts, a - self.longest)
        out = []
        for s, e, n in self.spans[i:]:
            if s >= b:
                break
            if e > a:
                out.append((max(s, a), min(e, b), n))
        return out


def _idle_cover(gaps, program_spans, host_spans):
    """Counter (label, pool) -> ns of the gaps: each stretch goes to the
    innermost span covering it, ``host:other`` where none does."""
    index = _Index([(s, e, (n, None)) for s, e, n in host_spans]
                   + [(s, e, (n, pool)) for s, e, n, pool in program_spans])
    out = collections.Counter()
    for a, b in gaps:
        cover = charge_innermost(index.clipped(a, b))
        out.update(cover)
        out[("host:other", None)] += (b - a) - sum(cover.values())
    return out


def idle_by_program_span(gaps, program_spans, host_spans, n_dev):
    """``gaps`` [(a, b)] of every device, ``program_spans`` [(start, end,
    name, pool or None)]: each stretch of a gap is charged to the innermost
    span that covers it, a ``serve.*`` span of the program or one of the
    benchmark's host spans (``pump`` holds the program's spans), and to
    ``host:other`` where none does.  -> {label: seconds}, the mean over
    devices; None where the trace holds no program span."""
    if not program_spans:
        return None
    out = collections.Counter()
    for (label, _), t in _idle_cover(gaps, program_spans, host_spans).items():
        out[label] += t
    return {k: v / n_dev * 1e-9 for k, v in out.items() if v > 0}


def idle_by_pool(gaps, program_spans, host_spans, n_dev):
    """As `idle_by_program_span`, the idle charged to a program span that
    names its pool, summed by pool: which bucket's staging, reads and
    retirement the device waited on.  None where no span names a pool."""
    if not any(sp[3] for sp in program_spans):
        return None
    out = collections.Counter()
    for (_, pool), t in _idle_cover(gaps, program_spans, host_spans).items():
        if pool:
            out[pool] += t
    return {k: v / n_dev * 1e-9 for k, v in out.items() if v > 0}


def idle_gaps(device_ops, lo, hi) -> list:
    """[(a, b)] of every device: the stretches of [lo, hi) in which it ran
    no op, as `trace.reduce_events` finds them."""
    gaps = []
    for evs in device_ops.values():
        merged = TR._merge([(max(s, lo), min(e, hi)) for s, e, _ in evs
                            if e > lo and s < hi])
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    return gaps


def reduce_profile(pd, raw: bytes, family: str) -> dict:
    """``pd`` a `jax.profiler.ProfileData` of the serialized trace ``raw``,
    ``family`` the prefix of the model's layer scopes (the cell's
    configuration names it).  -> {device_by_layer, force_backward_by_layer,
    idle_by_program_span, idle_by_pool}, over the window and devices
    `trace.from_profile` finds."""
    ev = TR.from_profile(pd)
    meta = metadata_paths(raw)
    paths, program = {}, []
    for plane in pd.planes:
        if plane.name in ev.device_ops:
            lines = {line.name: line for line in plane.lines}
            names = ([n for n in TR.OP_LINES if n in lines]
                     or [n for n in TR.MODULE_LINES if n in lines])
            named = meta.get(plane.name, {})
            paths[plane.name] = [named.get(e.name, "") for n in names
                                 for e in lines[n].events]
        elif plane.name.startswith("/host:"):
            program += [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                         dict(e.stats).get("pool"))
                        for line in plane.lines for e in line.events
                        if e.name.startswith(PROGRAM_SPAN_PREFIX)]
    lo, hi = ev.window
    by_layer, backward = device_by_layer(ev.device_ops, paths, lo, hi,
                                         family)
    gaps, n_dev = idle_gaps(ev.device_ops, lo, hi), len(ev.device_ops)
    return {"device_by_layer": by_layer,
            "force_backward_by_layer": backward,
            "idle_by_program_span": idle_by_program_span(
                gaps, program, ev.host_spans, n_dev),
            "idle_by_pool": idle_by_pool(gaps, program, ev.host_spans,
                                         n_dev)}


def reduce(trace_dir: str, family: str) -> dict:
    """`reduce_profile` of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(files[-1], "rb") as f:
        raw = f.read()
    return reduce_profile(ProfileData.from_serialized_xspace(raw), raw,
                          family)


# ------------------------------------------------- the trace file itself

def _varint(buf, i):
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value as a memoryview."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        num, wire = tag >> 3, tag & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield num, wire, v


def _map_value(entry):
    for num, _, v in _fields(entry):
        if num == 2:
            return v
    return b""


def _stat_value(stat, stat_names):
    """(name, value) of one XStat: a string, a number, or for a reference
    the name it refers to."""
    sid, value = None, None
    for num, wire, v in _fields(stat):
        if num == 1:
            sid = v
        elif num in (5, 6) and wire == 2:              # str, bytes
            value = bytes(v).decode(errors="replace")
        elif num in (3, 4) and wire == 0:              # uint64, int64
            value = v
        elif num == 7:                                 # ref_value
            value = stat_names.get(v, "")
    return stat_names.get(sid, ""), value


_OPERAND = re.compile(r"(?<![=\w])%([\w.\-]+)")


def _resolve(ops):
    """Paths of one plane's ops, by op text.  ``ops``: {op text: (program,
    display name, tf_op path, deduplicated name)}.  An op without a path of
    its own takes that of the op XLA deduplicated it from, else that of its
    first operand that has one: the compiler's layout copies and slices
    carry no metadata, and are charged to the layer whose value they move."""
    by_name = {(prog, disp): text for text, (prog, disp, _, _) in ops.items()}
    memo = {}

    def path(text, depth=0):
        if text in memo:
            return memo[text]
        prog, _, own, dedup = ops[text]
        memo[text] = ""                                # cycle guard
        out = own
        if not out and depth < 16:
            names = ([dedup] if dedup else []) + _OPERAND.findall(
                text.split(" = ", 1)[-1])
            for name in names:
                other = by_name.get((prog, name))
                if other is not None and path(other, depth + 1):
                    out = memo[other]
                    break
        memo[text] = out
        return out

    return {text: path(text) for text in ops}


def metadata_paths(data: bytes) -> dict:
    """{device plane name: {op event name: op_name path}} from the device
    planes' event metadata in a serialized XSpace (field numbers of
    tsl/profiler/protobuf/xplane.proto).  An op event is named by its
    metadata's name, the op's HLO text; its path is the ``tf_op`` stat."""
    out = {}
    for num, _, plane in _fields(memoryview(data)):
        if num != 1:                                   # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for pn, _, v in _fields(plane):
            if pn == 2:                                # XPlane.name
                name = bytes(v).decode()
            elif pn == 4:                              # event_metadata
                metas.append(_map_value(v))
            elif pn == 5:                              # stat_metadata
                sid, sname = 0, ""
                for fn, _, fv in _fields(_map_value(v)):
                    if fn == 1:
                        sid = fv
                    elif fn == 2:
                        sname = bytes(fv).decode()
                stat_names[sid] = sname
        if not name.startswith("/device:"):
            continue
        ops = {}
        for meta in metas:
            text = display = ""
            stats = {}
            for fn, _, fv in _fields(meta):
                if fn == 2:                            # name
                    text = bytes(fv).decode(errors="replace")
                elif fn == 4:                          # display_name
                    display = bytes(fv).decode(errors="replace")
                elif fn == 5:                          # stats
                    k, v = _stat_value(fv, stat_names)
                    stats[k] = v
            if text and text not in ops:
                # "<op_name path>:<op type>"
                own = (stats.get("tf_op") or "").rpartition(":")[0]
                ops[text] = (stats.get("program_id"),
                             display or text.split(" = ")[0].lstrip("%"),
                             own, stats.get("deduplicated_name"))
        out[name] = _resolve(ops)
    return out

"""Reduce a profiler trace of one window to what the metric readers need.

Reads the ``.xplane.pb`` (an ``XSpace`` protobuf) that ``jax.profiler``
writes, with the few message types declared here.  Two kinds of events
matter:

  * device ops: the events of the ``XLA Ops`` line of each
    ``/device:TPU:<n>`` plane.  Each is given to a layer by the JAX
    name stack in its op metadata (``LAYERS``: the first rule whose
    pattern occurs in the event's name or string stats wins; the
    descent kernel is found by its own name).
    A TPU op keeps its stats, the name stack among them, on its event
    metadata rather than on the event, and ``jax.profiler.ProfileData``
    shows only the event's own: so the file is read here, and both are
    used.  A TPU op's metadata name is its whole HLO instruction, its
    operands among them; the op is named by the metadata's display name,
    the instruction's own;
  * host spans: the ``bench.*`` ``TraceAnnotation``s that ``run.py``
    writes around each batch (``bench.batch``), around its steps
    (``bench.dispatch``, ``bench.wait``, ``bench.answers``) and around
    the generation of the next (``bench.generate``).

The window is the first ``bench.batch`` span's start to the last one's
end.  Busy time is the union of the device op intervals inside it,
averaged over the devices; idle gaps are the uncovered rest, each named
by the host span that holds its midpoint.

Nothing here imports the TPU library: the module reads files and arrays.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import List, NamedTuple, Sequence, Tuple

import numpy as np

# (layer, patterns): the name stack of an op names the jitted function
# or kernel it was traced in.  Checked in this order.
LAYERS: Sequence[Tuple[str, Tuple[str, ...]]] = (
    ("descent", ("splay_search_tiered", "splay_search_pipelined")),
    ("select", ("splay_select",)),
    ("refresh", ("refresh_device", "from_state_device")),
    ("fold", ("run_contains_batch", "run_ops")),
)
OTHER = "other"
BATCH_SPAN = "bench.batch"
SPAN_PREFIX = "bench."
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
TILE_LANES = 128
TILE_BYTES = TILE_LANES * 4


class Op(NamedTuple):
    device: str
    layer: str
    name: str
    start_ns: float
    dur_ns: float
    self_ns: float = None   # dur_ns less the ops nested inside it


def with_self_times(ops: Sequence[Op]) -> List[Op]:
    """``ops`` of one line with ``self_ns`` set: an op that holds others
    (a loop around its body's ops) keeps only the time none of them
    covers, so that summing self times never counts a nanosecond
    twice.  An op of no layer that holds others (a loop whose own op
    carries no name stack) takes the layer of most of the time nested
    in it: its self time is the control of that layer's loop."""
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i].start_ns, -ops[i].dur_ns))
    self_ns = [o.dur_ns for o in ops]
    parent = [-1] * len(ops)
    stack: List[int] = []
    for i in order:
        s = ops[i].start_ns
        while stack and s >= ops[stack[-1]].start_ns + ops[stack[-1]].dur_ns:
            stack.pop()
        if stack:
            parent[i] = stack[-1]
            top = ops[stack[-1]]
            self_ns[stack[-1]] -= (min(s + ops[i].dur_ns,
                                       top.start_ns + top.dur_ns) - s)
        stack.append(i)
    layers = [o.layer for o in ops]
    nested = [defaultdict(float) for _ in ops]
    for i in reversed(order):           # each op after all it holds
        if layers[i] == OTHER and nested[i]:
            layers[i] = max(nested[i], key=nested[i].get)
        if parent[i] >= 0:
            nested[parent[i]][layers[i]] += ops[i].dur_ns
    return [o._replace(layer=layer, self_ns=max(t, 0.0))
            for o, layer, t in zip(ops, layers, self_ns)]


class Span(NamedTuple):
    name: str
    start_ns: float
    end_ns: float


def layer_of(text: str) -> str:
    for layer, patterns in LAYERS:
        if any(p in text for p in patterns):
            return layer
    return OTHER


def _self(op: Op) -> float:
    return op.dur_ns if op.self_ns is None else op.self_ns


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                 float]]:
    """Merged ``(start, end)`` intervals, ascending."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy, lo: float, hi: float):
    """The parts of ``[lo, hi]`` that merged ``busy`` leaves uncovered."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


# Stats whose text names an op's operands (the HLO text), not the op
# itself: an op that reads the kernel's output would match the kernel.
OPERAND_STATS = ("long_name",)


def _stat_strings(stats, stat_names) -> List[str]:
    """The string values of ``stats`` (``XStat`` messages), a
    ``ref_value`` read as the name of the stat metadata it points to,
    leaving out ``OPERAND_STATS``."""
    out = []
    for st in stats:
        if stat_names.get(st.metadata_id) in OPERAND_STATS:
            continue
        if st.str_value:
            out.append(st.str_value)
        elif st.ref_value:
            out.append(stat_names.get(st.ref_value, ""))
    return out


def read_events(space) -> Tuple[List[Op], List[Span]]:
    """Device ops and ``bench.*`` host spans of an ``XSpace`` message."""
    ops, spans = [], []
    for plane in space.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        if not device and not plane.name.startswith("/host:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        layer_of_meta = {}
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            t0 = line.timestamp_ns
            found = []
            for ev in line.events:
                md = meta.get(ev.metadata_id)
                name = (md.display_name or md.name) if md is not None else ""
                start = t0 + ev.offset_ps * 1e-3
                dur = ev.duration_ps * 1e-3
                if not device:
                    if name.startswith(SPAN_PREFIX):
                        spans.append(Span(name, start, start + dur))
                    continue
                if ev.metadata_id not in layer_of_meta:
                    parts = [name]
                    if md is not None:
                        parts += _stat_strings(md.stats, stat_names)
                    layer_of_meta[ev.metadata_id] = " ".join(parts)
                text = layer_of_meta[ev.metadata_id]
                if ev.stats:
                    text = " ".join([text] + _stat_strings(ev.stats,
                                                           stat_names))
                found.append(Op(plane.name, layer_of(text), name, start,
                                dur))
            ops.extend(with_self_times(found))
    spans.sort(key=lambda x: x.start_ns)
    return ops, spans


class Reduction:
    """Device time by layer and by batch inside the traced window."""

    def __init__(self, ops: List[Op], spans: List[Span]):
        self.batches = [s for s in spans if s.name == BATCH_SPAN]
        if not self.batches:
            raise ValueError("the trace holds no bench.batch span")
        lo = self.batches[0].start_ns
        hi = self.batches[-1].end_ns
        self.window_ns = (lo, hi)
        self.window_s = (hi - lo) * 1e-9
        self.devices = sorted({o.device for o in ops})
        self.ops = [o for o in ops
                    if o.start_ns < hi and o.start_ns + o.dur_ns > lo]
        self.spans = spans
        busy = 0.0
        self.busy_by_device = {}
        for d in self.devices:
            merged = union(clip([(o.start_ns, o.start_ns + o.dur_ns)
                                 for o in self.ops if o.device == d],
                                lo, hi))
            self.busy_by_device[d] = merged
            busy += sum(e - s for s, e in merged)
        self.busy_s = busy * 1e-9 / max(len(self.devices), 1)

    def per_batch_s(self, layer: str) -> List[float]:
        """Device seconds (self time) of a layer's ops started inside each
        ``bench.batch`` span, averaged over the devices."""
        starts = np.array([b.start_ns for b in self.batches])
        ends = np.array([b.end_ns for b in self.batches])
        mine = [o for o in self.ops if o.layer == layer]
        op_start = np.array([o.start_ns for o in mine])
        op_dur = np.array([_self(o) for o in mine])
        i = np.searchsorted(starts, op_start, side="right") - 1
        inside = (i >= 0) & (op_start < ends[np.maximum(i, 0)])
        sums = np.zeros(len(self.batches))
        np.add.at(sums, i[inside], op_dur[inside])
        n = max(len(self.devices), 1)
        return [float(x) * 1e-9 / n for x in sums]

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (by layer and op name) and
        the longest idle gaps, summed by the host span that held them."""
        by_op = defaultdict(float)
        for o in self.ops:
            by_op[f"{o.layer}:{o.name}"] += _self(o) * 1e-9
        n = max(len(self.devices), 1)
        device_ops = sorted(([k, v / n] for k, v in by_op.items()),
                            key=lambda kv: -kv[1])[:top]
        lo, hi = self.window_ns
        host = [s for s in self.spans if s.name != BATCH_SPAN]
        starts = [h.start_ns for h in host]
        by_gap = defaultdict(float)
        for d in self.devices:
            for s, e in gaps(self.busy_by_device[d], lo, hi):
                mid = (s + e) / 2
                i = bisect.bisect_right(starts, mid) - 1
                owner = (host[i].name if i >= 0 and mid < host[i].end_ns
                         else "no bench span")
                by_gap[owner] += (e - s) * 1e-9 / n
        idle = sorted(([k, v] for k, v in by_gap.items()),
                      key=lambda kv: -kv[1])[:top]
        return {"device_ops": device_ops, "idle_gaps": idle}


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _xspace_class():
    """The ``XSpace`` message type, declared with the fields read here
    (the numbers are those of ``tsl/profiler/protobuf/xplane.proto``; a
    map is read as its repeated entries, which is its wire form)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, u64, text = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING
    sub = F.TYPE_MESSAGE
    messages = {
        "XStat": [("metadata_id", 1, i64, one), ("str_value", 5, text, one),
                  ("ref_value", 7, u64, one)],
        "XEvent": [("metadata_id", 1, i64, one), ("offset_ps", 2, i64, one),
                   ("duration_ps", 3, i64, one),
                   ("stats", 4, sub, many, "XStat")],
        "XLine": [("name", 2, text, one), ("timestamp_ns", 3, i64, one),
                  ("events", 4, sub, many, "XEvent")],
        "XEventMetadata": [("name", 2, text, one),
                           ("display_name", 4, text, one),
                           ("stats", 5, sub, many, "XStat")],
        "XStatMetadata": [("name", 2, text, one)],
        "EventMetadataEntry": [("key", 1, i64, one),
                               ("value", 2, sub, one, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, i64, one),
                              ("value", 2, sub, one, "XStatMetadata")],
        "XPlane": [("name", 2, text, one), ("lines", 3, sub, many, "XLine"),
                   ("event_metadata", 4, sub, many, "EventMetadataEntry"),
                   ("stat_metadata", 5, sub, many, "StatMetadataEntry")],
        "XSpace": [("planes", 1, sub, many, "XPlane")],
    }
    package = "bench_trace_reduce"
    fd = descriptor_pb2.FileDescriptorProto(
        name=package + ".proto", package=package, syntax="proto3")
    for msg_name, fields in messages.items():
        msg = fd.message_type.add(name=msg_name)
        for name, number, kind, label, *type_name in fields:
            f = msg.field.add(name=name, number=number, type=kind,
                              label=label)
            if type_name:
                f.type_name = f".{package}.{type_name[0]}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{package}.XSpace"))


def parse_xspace(data: bytes):
    space = _xspace_class()()
    space.ParseFromString(data)
    return space


def reduce_file(path: str) -> Reduction:
    with open(path, "rb") as f:
        return Reduction(*read_events(parse_xspace(f.read())))


def reduce_dir(trace_dir: str) -> Reduction:
    return reduce_file(find_xplane(trace_dir))


def touched_tiles(rows: np.ndarray, widths: np.ndarray,
                  queries: np.ndarray) -> int:
    """Distinct ``(row, tile)`` pairs an exact descent through this plane
    must read for these queries.  Each distinct key walks from the top
    row down to the row where it is found, or to the bottom row; at each
    non-empty row on that path it reads the aligned 128-lane tile of the
    row's live prefix that holds its predecessor position (tile 0 when
    it is below the row's first key)."""
    q = np.unique(np.asarray(queries, np.int64))
    open_ = np.ones(q.shape, bool)
    total = 0
    for r in range(rows.shape[0]):
        w = int(widths[r])
        if w == 0 or not open_.any():
            continue
        row = np.asarray(rows[r, :w], np.int64)
        pos = np.searchsorted(row, q, side="right") - 1
        at = np.maximum(pos, 0)
        total += len(np.unique(at[open_] // TILE_LANES))
        open_ &= ~((pos >= 0) & (row[at] == q))
    return total

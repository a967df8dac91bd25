#!/usr/bin/env python3
"""Read a kept ``--trace 1`` profile by the serving path's own names.

    python3 bench/scope_reduce.py TRACE_DIR      # one JSON object

``trace_reduce`` gives each device op a layer by the jitted function
it was traced in, and keeps the ``bench.*`` host spans only.  The
program also names its own work (``core/splaylist.py``,
``core/device_index.py``):

  * device scopes (``jax.named_scope``) in each op's name stack:
    ``splay.fold``, ``splay.descent``, ``splay.select``,
    ``splay.refresh``, ``splay.plane_rebuild``, ``splay.state_rebuild``
    and, inside the refresh and the rebuild, ``splay.compact`` (the
    plane's row compaction);
  * host spans (``jax.profiler.TraceAnnotation``) ``splay.serve.guard``
    and ``splay.serve.dispatch`` in ``run_serving`` / ``run_epoch``.

Here each op keeps the ``splay.*`` scopes of its name stack, the host
spans of both prefixes are kept, and the device's idle gaps are shared
out by overlap with the innermost host span that covers each part of
them (``trace_reduce`` names a whole gap by the span at its midpoint).
A trace of a program without these names reads ``None`` for the scope
and span sums.  ``bench/run.py`` does not call this module: its
readers see only ``trace_reduce.Reduction``.
"""

from __future__ import annotations

import json
import os
import re
import sys
from collections import defaultdict
from typing import List, Optional, Sequence, Tuple

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace_reduce as tr  # noqa: E402

SCOPE = re.compile(r"splay\.[a-z_]+")
SPAN_PREFIXES = (tr.SPAN_PREFIX, "splay.")
SERVE_SPAN_PREFIX = "splay.serve."
COMPACT_SCOPE = "splay.compact"
NO_SPAN = "no host span"


def read_events(space) -> Tuple[List[tr.Op], List[frozenset],
                                List[tr.Span]]:
    """Device ops (as ``trace_reduce.read_events`` gives them), the
    ``splay.*`` scopes of each op's name stack, and the ``bench.*`` and
    ``splay.*`` host spans of an ``XSpace`` message.  An op that the
    compiler merged from several places (hoisted out of both branches
    of a ``lax.cond``, or shared by common-subexpression elimination)
    holds the name stacks of all of them, so it carries every scope
    they had."""
    ops, scopes, spans = [], [], []
    for plane in space.planes:
        device = plane.name.startswith(tr.DEVICE_PLANE_PREFIX)
        if not device and not plane.name.startswith("/host:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        text_of_meta = {}
        for line in plane.lines:
            if device and line.name != tr.OPS_LINE:
                continue
            found, found_scopes = [], []
            for ev in line.events:
                md = meta.get(ev.metadata_id)
                name = (md.display_name or md.name) if md is not None else ""
                start = line.timestamp_ns + ev.offset_ps * 1e-3
                dur = ev.duration_ps * 1e-3
                if not device:
                    if name.startswith(SPAN_PREFIXES):
                        spans.append(tr.Span(name, start, start + dur))
                    continue
                if ev.metadata_id not in text_of_meta:
                    parts = [name]
                    if md is not None:
                        parts += tr._stat_strings(md.stats, stat_names)
                    text_of_meta[ev.metadata_id] = " ".join(parts)
                text = " ".join([text_of_meta[ev.metadata_id]]
                                + tr._stat_strings(ev.stats, stat_names))
                found.append(tr.Op(plane.name, tr.layer_of(text), name,
                                   start, dur))
                found_scopes.append(" ".join(sorted(set(SCOPE.findall(
                    text)))) or tr.OTHER)
            ops.extend(tr.with_self_times(found))
            # the same pass over the scopes: a loop op with no name stack
            # (the mixed path's walk loop) takes the scopes of its body
            scopes.extend(
                frozenset(o.layer.split()) if o.layer != tr.OTHER
                else frozenset() for o in tr.with_self_times(
                    [o._replace(layer=k) for o, k in zip(found,
                                                         found_scopes)]))
    spans.sort(key=lambda s: s.start_ns)
    return ops, scopes, spans


class ScopeReduction(tr.Reduction):
    """``trace_reduce.Reduction`` of the same events, with sums by scope
    and by host span, and idle gaps shared out by overlap."""

    def __init__(self, ops: List[tr.Op], scopes: List[frozenset],
                 spans: List[tr.Span]):
        bench_spans = [s for s in spans if s.name.startswith(tr.SPAN_PREFIX)]
        super().__init__(ops, bench_spans)
        lo, hi = self.window_ns
        self.scoped = [(o, sc) for o, sc in zip(ops, scopes)
                       if o.start_ns < hi and o.start_ns + o.dur_ns > lo]
        self.spans = spans

    def _by_batch(self, starts_durs) -> List[float]:
        starts = np.array([b.start_ns for b in self.batches])
        ends = np.array([b.end_ns for b in self.batches])
        sums = np.zeros(len(self.batches))
        for s, d in starts_durs:
            i = int(np.searchsorted(starts, s, side="right")) - 1
            if i >= 0 and s < ends[i]:
                sums[i] += d
        return [float(x) * 1e-9 for x in sums]

    def scope_per_batch_s(self, scope: str) -> List[float]:
        """Device seconds (self time) of the ops whose name stack holds
        ``scope``, started inside each ``bench.batch`` span, averaged
        over the devices."""
        n = max(len(self.devices), 1)
        return [x / n for x in self._by_batch(
            (o.start_ns, tr._self(o)) for o, sc in self.scoped
            if scope in sc)]

    def host_per_batch_s(self, prefix: str) -> List[float]:
        """Host seconds covered by the spans named ``prefix...`` inside
        each ``bench.batch`` span (overlaps counted once)."""
        mine = tr.union([(s.start_ns, s.end_ns) for s in self.spans
                         if s.name.startswith(prefix)])
        return self._by_batch((s, e - s) for s, e in mine)

    def idle_by_span(self) -> dict:
        """Idle seconds of the window by the innermost host span (other
        than ``bench.batch``) over each part of each gap, averaged over
        the devices; the parts no span covers go to ``NO_SPAN``."""
        host = [s for s in self.spans if s.name != tr.BATCH_SPAN]
        lo, hi = self.window_ns
        n = max(len(self.devices), 1)
        out = defaultdict(float)
        for d in self.devices:
            for s, e in tr.gaps(self.busy_by_device[d], lo, hi):
                for owner, t in share_by_overlap(host, s, e):
                    out[owner] += t * 1e-9 / n
        return dict(out)

    def breakdown(self, top: int = 10) -> dict:
        out = super().breakdown(top)
        out["idle_gaps"] = sorted(([k, v] for k, v in
                                   self.idle_by_span().items()),
                                  key=lambda kv: -kv[1])[:top]
        return out


def share_by_overlap(spans: Sequence[tr.Span], s: float,
                     e: float) -> List[Tuple[str, float]]:
    """``[s, e]`` cut at every span boundary inside it, each piece owned
    by the innermost span over it (the latest to start; the shorter of
    two that start together) or by ``NO_SPAN``."""
    cuts = sorted({s, e} | {t for sp in spans for t in (sp.start_ns,
                                                         sp.end_ns)
                            if s < t < e})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        over = [sp for sp in spans if sp.start_ns <= a and b <= sp.end_ns]
        owner = (max(over, key=lambda sp: (sp.start_ns, -sp.end_ns)).name
                 if over else NO_SPAN)
        out.append((owner, b - a))
    return out


def _mean_ms(per: List[float]) -> Optional[float]:
    if not per or sum(per) <= 0:
        return None
    return 1e3 * sum(per) / len(per)


def compaction_ms(red: ScopeReduction) -> Optional[float]:
    """Device ms per batch under ``splay.compact``."""
    return _mean_ms(red.scope_per_batch_s(COMPACT_SCOPE))


def serve_host_ms(red: ScopeReduction) -> Optional[float]:
    """Host ms per batch inside ``splay.serve.*`` spans."""
    return _mean_ms(red.host_per_batch_s(SERVE_SPAN_PREFIX))


def reduce_bytes(data: bytes) -> ScopeReduction:
    return ScopeReduction(*read_events(tr.parse_xspace(data)))


def summary(red: ScopeReduction) -> dict:
    """Per-batch means by layer (``trace_reduce``) and by scope, the two
    readings, and the breakdown with overlap-shared idle gaps."""
    scopes = sorted({s for _, sc in red.scoped for s in sc})
    layers = [name for name, _ in tr.LAYERS] + [tr.OTHER]
    return {
        "batches": len(red.batches),
        "layer_ms": {k: _mean_ms(red.per_batch_s(k)) for k in layers},
        "scope_ms": {k: _mean_ms(red.scope_per_batch_s(k)) for k in scopes},
        "compaction_ms": compaction_ms(red),
        "serve_host_ms": serve_host_ms(red),
        "idle_share": 100.0 * red.idle_share(),
        "breakdown": red.breakdown(),
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.splitlines()[2].strip(), file=sys.stderr)
        return 2
    with open(tr.find_xplane(argv[0]), "rb") as f:
        print(json.dumps(summary(reduce_bytes(f.read()))))
    return 0


if __name__ == "__main__":
    sys.exit(main())

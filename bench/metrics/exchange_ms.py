"""``exchange_ms``: device ms per window batch, averaged over the
chips, in the cross-chip collectives outside the plane refresh: the
routed query exchange (``splay.route``: the query and answer
all_to_alls, the count all_gather), the spill path's all_gather and the
few all-reduces around them.  A collective is told by its HLO name
(``all-to-all``, ``all-gather``, ``all-reduce``, ``collective-permute``,
``reduce-scatter``, their ``-start``/``-done`` halves, or the JAX
primitive a name was taken from); a trace with none reads nothing."""

import bisect
import re

COLLECTIVE = re.compile(r"all[-_]to[-_]all|all[-_]gather|all[-_]reduce|"
                        r"collective[-_]permute|reduce[-_]scatter|"
                        r"psum|ppermute")


def collective_ms(trace, refresh: bool):
    """Device ms per window batch, averaged over the devices, of the
    collective ops started inside a ``bench.batch`` span, counted by
    self time: those of the ``refresh`` layer, or those of every other
    layer.  ``None`` when there is no such op."""
    starts = [b.start_ns for b in trace.batches]
    total_ns, found = 0.0, False
    for o in trace.ops:
        if (o.layer == "refresh") != refresh or not COLLECTIVE.search(
                o.name):
            continue
        i = bisect.bisect_right(starts, o.start_ns) - 1
        if i < 0 or o.start_ns >= trace.batches[i].end_ns:
            continue
        total_ns += o.dur_ns if o.self_ns is None else o.self_ns
        found = True
    if not found:
        return None
    n = max(len(trace.devices), 1) * len(trace.batches)
    return 1e-6 * total_ns / n


def read(ctx):
    return collective_ms(ctx.trace, refresh=False)

"""The scope and span reduction of kept traces (``bench/scope_reduce.py``)
and the contract that lets the layer metrics ignore the program's own
scopes: a ``splay.*`` scope never changes the layer of an op."""

import gzip
import importlib.util
import os
import re

import numpy as np
import pytest

from bench import scope_reduce as sr
from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
UNSCOPED_TRACE = os.path.join(DATA, "paper-ro-99-1.batch1.xplane.pb.gz")
SCOPED_TRACE = os.path.join(DATA, "paper-ro-99-1.scoped.batch1.xplane.pb.gz")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(path):
    with gzip.open(path) as f:
        return f.read()


def _metric(name):
    spec = importlib.util.spec_from_file_location(
        "scope_test_" + name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _Ctx:
    def __init__(self, red):
        self.trace = red
        self.serving = {}


def test_gaps_are_shared_by_overlap_with_the_innermost_span():
    spans = [tr.Span("bench.dispatch", 0, 50), tr.Span("splay.serve.guard",
                                                       5, 20),
             tr.Span("splay.serve.dispatch", 20, 45),
             tr.Span("bench.wait", 60, 90)]
    got = sr.share_by_overlap(spans, 10, 70)
    assert got == [("splay.serve.guard", 10), ("splay.serve.dispatch", 25),
                   ("bench.dispatch", 5), (sr.NO_SPAN, 10),
                   ("bench.wait", 10)]
    assert sum(t for _, t in got) == 60


LOOP_XSPACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 10000 duration_ps: 60000 }
    events { metadata_id: 3 offset_ps: 120000 duration_ps: 30000 } }
  event_metadata { key: 1 value { id: 1 name: "while.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" stats {
    metadata_id: 1 str_value: "jit(_run_epoch)/splay.fold/jit(run_ops)/x" } } }
  event_metadata { key: 3 value { id: 3 name: "fusion.3" stats {
    metadata_id: 1 str_value: "jit(_run_epoch)/cond/branch_1_fun/splay.plane_rebuild/jit(from_state_device)/splay.compact/cond/branch_0_fun/splay.refresh/jit(refresh_device)/splay.compact/g" } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 2 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 200000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 5000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.batch" } }
  event_metadata { key: 2 value { id: 2 name: "splay.serve.guard" } }
}
'''


def test_unnamed_loops_take_their_body_scopes_and_merged_ops_keep_all():
    """The walk loop's op has no name stack: it takes the scopes of the
    ops nested in it.  An op the compiler hoisted out of both branches
    of a ``lax.cond`` holds both branches' name stacks."""
    from jax.profiler import ProfileData
    ops, scopes, spans = sr.read_events(tr.parse_xspace(
        ProfileData.text_proto_to_serialized_xspace(LOOP_XSPACE)))
    assert [(o.name, o.layer, sorted(sc)) for o, sc in zip(ops, scopes)] == [
        ("while.1", "fold", ["splay.fold"]),
        ("fusion.2", "fold", ["splay.fold"]),
        ("fusion.3", "refresh", ["splay.compact", "splay.plane_rebuild",
                                 "splay.refresh"])]
    assert [s.name for s in spans] == ["bench.batch", "splay.serve.guard"]
    red = sr.ScopeReduction(ops, scopes, spans)
    assert red.scope_per_batch_s("splay.fold") == pytest.approx([100e-9])
    assert red.scope_per_batch_s("splay.compact") == pytest.approx([30e-9])


def test_scope_sums_and_host_spans_on_synthetic_events():
    spans = [tr.Span("bench.batch", 0, 100),
             tr.Span("splay.serve.guard", 0, 4),
             tr.Span("splay.serve.dispatch", 4, 10),
             tr.Span("bench.batch", 110, 200),
             tr.Span("splay.serve.guard", 110, 113),
             tr.Span("splay.serve.dispatch", 113, 116)]
    ops = [tr.Op("/device:TPU:0", "refresh", "f", 10.0, 60.0, 60.0),
           tr.Op("/device:TPU:0", "refresh", "g", 70.0, 20.0, 20.0),
           tr.Op("/device:TPU:0", "fold", "h", 120.0, 30.0, 30.0)]
    scopes = [frozenset({"splay.refresh", "splay.compact"}),
              frozenset({"splay.refresh"}), frozenset({"splay.fold"})]
    red = sr.ScopeReduction(ops, scopes, spans)
    assert red.scope_per_batch_s("splay.compact") == pytest.approx(
        [60e-9, 0.0])
    assert red.scope_per_batch_s("splay.refresh") == pytest.approx(
        [80e-9, 0.0])
    assert red.host_per_batch_s("splay.serve.") == pytest.approx(
        [10e-9, 6e-9])
    assert sr.compaction_ms(red) == pytest.approx(30e-6)
    assert sr.serve_host_ms(red) == pytest.approx(8e-6)
    idle = red.idle_by_span()
    assert idle == pytest.approx({"splay.serve.guard": 7e-9,
                                  "splay.serve.dispatch": 9e-9,
                                  sr.NO_SPAN: 74e-9})
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s)


@pytest.mark.parametrize("path", [UNSCOPED_TRACE, SCOPED_TRACE],
                         ids=["unscoped", "scoped"])
def test_chip_traces_read_as_before(path):
    """Every accepted metric that reads the trace reads the same value
    from the scope reduction, and the idle time is the same however it
    is shared out: on the kept trace of a program without ``splay.*``
    names and on one with them."""
    data = _read(path)
    old = tr.Reduction(*tr.read_events(tr.parse_xspace(data)))
    new = sr.reduce_bytes(data)
    for layer in ("descent", "select", "refresh", "fold", "other"):
        assert new.per_batch_s(layer) == old.per_batch_s(layer)
    for name in ("fold_ms", "refresh_ms", "device_idle_share"):
        mod = _metric(name)
        assert mod.read(_Ctx(new)) == mod.read(_Ctx(old))
    assert (new.busy_s, new.window_s) == (old.busy_s, old.window_s)
    assert new.breakdown()["device_ops"] == old.breakdown()["device_ops"]
    assert sum(new.idle_by_span().values()) == pytest.approx(
        sum(v for _, v in old.breakdown()["idle_gaps"]))
    if path == UNSCOPED_TRACE:
        assert sr.compaction_ms(new) is None
        assert sr.serve_host_ms(new) is None


def test_scoped_chip_trace_reads_the_compaction_and_the_host_spans():
    """One batch of ``paper-ro-99-1`` traced on a TPU v5e with the
    program's scopes and spans (cut like the unscoped trace, keeping the
    host's ``splay.*`` spans too)."""
    data = _read(SCOPED_TRACE)
    red = sr.reduce_bytes(data)
    assert sr.compaction_ms(red) == pytest.approx(449.6406939)
    assert sr.compaction_ms(red) / (1e3 * red.per_batch_s("refresh")[0]) \
        == pytest.approx(0.9531, abs=1e-4)
    assert sr.serve_host_ms(red) == pytest.approx(6.41886)
    # the gap under bench.dispatch belongs to run_serving's own steps
    old = dict(tr.Reduction(*tr.read_events(tr.parse_xspace(data)))
               .breakdown()["idle_gaps"])
    new = red.idle_by_span()
    assert new["splay.serve.guard"] + new["splay.serve.dispatch"] \
        == pytest.approx(old["bench.dispatch"], rel=0.01)
    assert new["bench.dispatch"] < 0.01 * old["bench.dispatch"]


@pytest.mark.parametrize("flags", [
    dict(aggregate=True, plane_search=True, ordered=True), dict()],
    ids=["plane", "mixed"])
def test_scopes_leave_every_op_in_its_layer(flags):
    """Taking the ``splay.*`` scopes out of each op's name stack in the
    compiled serving program changes no op's layer."""
    from repro.core import device_index as dix
    from repro.core import splaylist as sx

    st = sx.make(64, max_level=8)
    plane = dix.from_state_device(st, n_levels=8, width=32)
    z = np.zeros((2, 8), np.int32)
    text = sx._run_serving.lower(st, plane, z, z, z.astype(bool),
                                 **flags).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    scoped = [n for n in names if sr.SCOPE.search(n)]
    assert len(scoped) > 10
    for n in scoped:
        assert tr.layer_of(n) == tr.layer_of(sr.SCOPE.sub("", n)), n

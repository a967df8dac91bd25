"""The trace reduction: interval arithmetic, layer attribution and the
touched-tile count."""

import bisect
import gzip
import os

import numpy as np
import pytest

from bench import trace_reduce as tr


def test_union_merges_overlaps_and_keeps_gaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)]) == [
        (0, 3), (5, 8), (10, 11)]
    assert tr.gaps([(2, 3), (5, 8)], 0, 10) == [(0, 2), (3, 5), (8, 10)]
    assert tr.clip([(0, 4), (6, 12)], 2, 10) == [(2, 4), (6, 10)]


def _op(start, dur, layer="fold", device="/device:TPU:0", name="f"):
    return tr.Op(device, layer, name, float(start), float(dur))


def test_busy_idle_share_and_per_batch_on_synthetic_events():
    spans = [tr.Span("bench.batch", 0, 100), tr.Span("bench.generate", 100,
                                                     110),
             tr.Span("bench.batch", 110, 200)]
    ops = [_op(10, 30), _op(20, 75, "refresh"),          # union 10..95
           _op(120, 50, "descent"), _op(150, 30, "fold"),  # 120..180
           _op(250, 10)]                                 # past the window
    red = tr.Reduction(ops, spans)
    assert red.window_ns == (0, 200)
    assert red.busy_s == pytest.approx(145e-9)
    assert red.idle_share() == pytest.approx(1 - 145 / 200)
    assert red.per_batch_s("fold") == pytest.approx([30e-9, 30e-9])
    assert red.per_batch_s("descent") == pytest.approx([0.0, 50e-9])
    assert red.per_batch_s("refresh") == pytest.approx([75e-9, 0.0])
    gaps = dict(red.breakdown()["idle_gaps"])
    # 0..10 and 180..200 under no finer span; 95..120 mostly generating
    assert gaps == pytest.approx({"bench.generate": 25e-9,
                                  "no bench span": 30e-9})


def test_busy_time_is_averaged_over_devices():
    spans = [tr.Span("bench.batch", 0, 100)]
    ops = [_op(0, 100, device="/device:TPU:0"),
           _op(0, 50, device="/device:TPU:1")]
    assert tr.Reduction(ops, spans).busy_s == pytest.approx(75e-9)


def test_nested_ops_count_their_time_once():
    line = [_op(0, 100, "fold", name="while"), _op(10, 20, "fold"),
            _op(40, 30, "refresh"), _op(45, 5, "refresh"),
            _op(120, 10, "descent")]
    got = {(o.name, o.start_ns): o.self_ns for o in tr.with_self_times(line)}
    assert got == {("while", 0): 50, ("f", 10): 20, ("f", 40): 25,
                   ("f", 45): 5, ("f", 120): 10}
    red = tr.Reduction(tr.with_self_times(line),
                       [tr.Span("bench.batch", 0, 200)])
    assert red.per_batch_s("fold") == pytest.approx([70e-9])
    assert red.per_batch_s("refresh") == pytest.approx([30e-9])
    assert red.busy_s == pytest.approx(110e-9)


def test_unnamed_loop_takes_the_layer_of_its_body():
    """A loop op with no name stack of its own is charged to the layer
    that most of the time nested in it belongs to, innermost first."""
    line = [_op(0, 100, tr.OTHER, name="while"),
            _op(10, 60, tr.OTHER, name="inner"),
            _op(15, 40, "fold", name="body"),
            _op(75, 20, "refresh", name="after"),
            _op(120, 10, tr.OTHER, name="copy")]
    got = {o.name: (o.layer, o.self_ns) for o in tr.with_self_times(line)}
    assert got == {"while": ("fold", 20), "inner": ("fold", 20),
                   "body": ("fold", 40), "after": ("refresh", 20),
                   "copy": (tr.OTHER, 10)}


@pytest.mark.parametrize("text, layer", [
    ("fusion.3 jit(_run_serving)/while/body/jit(_run_epoch)/"
     "jit(refresh_device)/cumsum", "refresh"),
    ("fusion.9 jit(_run_serving)/while/body/jit(_run_epoch)/"
     "jit(from_state_device)/gather", "refresh"),
    ("jit(_run_serving)/while/body/jit(_run_epoch)/jit(run_ops)/while",
     "fold"),
    ("jit(_run_serving)/jit(run_contains_batch)/scan", "fold"),
    ("splay_search_tiered jit(_splay_search_arrays)", "descent"),
    ("jit(_run_serving)/jit(splay_select)/gather", "select"),
    ("copy.4 jit(_run_serving)/while", "other"),
])
def test_layer_attribution_by_name_stack(text, layer):
    assert tr.layer_of(text) == layer


XSPACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000
             stats { metadata_id: 1
                     str_value: "jit(_run_serving)/jit(run_ops)/while" } }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 4000000 duration_ps: 500000
             stats { metadata_id: 2
                     str_value: "%fusion.2 = fusion(%splay_search_tiered.1)" }
             stats { metadata_id: 1
                     str_value: "jit(_run_serving)/jit(refresh_device)/x" } }
    events { metadata_id: 5 offset_ps: 4500000 duration_ps: 200000 }
    events { metadata_id: 6 offset_ps: 4700000 duration_ps: 100000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "splay_search_tiered" } }
  event_metadata { key: 3 value { id: 3 name: "jit__run_serving" } }
  event_metadata { key: 4 value { id: 4 name: "fusion.2" } }
  event_metadata { key: 5 value { id: 5 name: "fusion.5" stats {
    metadata_id: 1 str_value: "jit(_run_serving)/jit(run_contains_batch)/scan"
  } } }
  event_metadata { key: 6 value { id: 6 name: "fusion.6" stats {
    metadata_id: 1 ref_value: 3 } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "long_name" } }
  stat_metadata { key: 3 value { id: 3
    name: "jit(_run_serving)/jit(refresh_device)/gather" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 2 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.batch" } }
  event_metadata { key: 2 value { id: 2 name: "bench.generate" } }
}
'''


def test_read_events_from_an_xspace():
    from jax.profiler import ProfileData
    data = ProfileData.text_proto_to_serialized_xspace(XSPACE)
    ops, spans = tr.read_events(tr.parse_xspace(data))
    # an op is named by its own metadata, never by its operands'; the
    # name stack may sit on the event, on its metadata (as a TPU op's
    # does), or there as a reference to a stat metadata's name
    assert [(o.layer, o.name) for o in ops] == [
        ("fold", "fusion.1"), ("descent", "splay_search_tiered"),
        ("refresh", "fusion.2"), ("fold", "fusion.5"),
        ("refresh", "fusion.6")]
    assert [s.name for s in spans] == ["bench.batch", "bench.generate"]
    # times as jax.profiler.ProfileData reads them
    want = [(ev.start_ns, ev.duration_ns) for plane in
            ProfileData.from_serialized_xspace(data).planes
            if plane.name == "/device:TPU:0" for line in plane.lines
            if line.name == "XLA Ops" for ev in line.events]
    assert [(o.start_ns, o.dur_ns) for o in ops] == pytest.approx(want)
    red = tr.Reduction(ops, spans)
    assert red.busy_s == pytest.approx(3.8e-6)
    assert red.window_s == pytest.approx(5e-6)
    assert red.per_batch_s("fold") == pytest.approx([2.2e-6])


def _brute_tiles(rows, widths, queries):
    seen = set()
    for q in set(int(x) for x in queries):
        for r in range(rows.shape[0]):
            w = int(widths[r])
            if w == 0:
                continue
            row = [int(x) for x in rows[r, :w]]
            pos = bisect.bisect_right(row, q) - 1
            seen.add((r, max(pos, 0) // 128))
            if pos >= 0 and row[pos] == q:
                break
    return len(seen)


def _nested_plane(rng, n, levels, width):
    keys = np.sort(rng.choice(4 * n, n, replace=False))
    heights = np.minimum(rng.geometric(0.5, n) - 1, levels - 1)
    rows = np.full((levels, width), 2 ** 31 - 1, np.int64)
    widths = np.zeros(levels, np.int64)
    for r in range(levels):
        members = keys[heights >= levels - 1 - r]
        rows[r, :len(members)] = members
        widths[r] = len(members)
    return keys, rows, widths


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_touched_tiles_match_a_brute_force_count(seed):
    rng = np.random.default_rng(seed)
    keys, rows, widths = _nested_plane(rng, 3000, 9, 4096)
    hot = rng.choice(keys, 40)
    queries = np.concatenate([rng.choice(hot, 300), rng.choice(keys, 200),
                              rng.integers(-5, 4 * 3000 + 5, 100)])
    assert tr.touched_tiles(rows, widths, queries) == _brute_tiles(
        rows, widths, queries)



RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "paper-ro-99-1.batch1.xplane.pb.gz")


def test_recorded_chip_trace_reduces_by_layer():
    """One batch of ``paper-ro-99-1`` traced on a TPU v5e (cut from a
    ``--trace 1 --keep-trace`` run to the first ``bench.batch`` span,
    the device's ``XLA Ops`` line and the host's ``bench.*`` spans, with
    the ops' source-file stats dropped).
    Its name stacks sit on the ops' event metadata, where
    ``jax.profiler.ProfileData`` does not show them."""
    with gzip.open(RECORDED) as f:
        data = f.read()
    ops, spans = tr.read_events(tr.parse_xspace(data))
    red = tr.Reduction(ops, spans)
    assert len(red.batches) == 1 and red.devices == ["/device:TPU:0"]
    per = {layer: red.per_batch_s(layer)[0]
           for layer in ("descent", "select", "refresh", "fold", "other")}
    assert per == pytest.approx({"descent": 150.257422e-6, "select": 0.0,
                                 "refresh": 0.47185370125,
                                 "fold": 7.88349532e-3,
                                 "other": 1.211866008e-3})
    assert red.busy_s == pytest.approx(0.48109932)
    assert red.window_s == pytest.approx(0.489690503)
    # the descent is the kernel, found by its own name, and the squeeze
    # of its output that JAX names after it; ops are named by their
    # instruction, not by their HLO text
    assert {o.name for o in ops if o.layer == "descent"} == {
        "splay_search_tiered.2", "reduce.107"}
    top = red.breakdown()["device_ops"][0]
    assert top[0] == "refresh:fusion.219"
    assert sum(v for _, v in red.breakdown()["idle_gaps"]) == pytest.approx(
        red.window_s - red.busy_s)

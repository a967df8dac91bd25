"""The serving path's own instrumentation: the ``splay.*`` scopes in the
compiled program's op metadata, the ``splay.serve.*`` host spans in a
profiler trace, and ``SplayState.counters`` against a recount of the
work each epoch did, through snapshots and checkpoints."""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import device_index as dix
from repro.core import ref_py
from repro.core import route_controller as rc
from repro.core import splaylist as sx

L, W, B, CAP = 8, 32, 8, 64


def _empty():
    st = sx.make(CAP, max_level=L)
    return st, dix.from_state_device(st, n_levels=L, width=W)


def _op_names(compiled_text: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


@pytest.mark.parametrize("path, flags, scopes", [
    ("plane", dict(aggregate=True, plane_search=True),
     {"splay.descent", "splay.fold", "splay.refresh", "splay.plane_rebuild",
      "splay.compact", "splay.state_rebuild"}),
    ("ordered", dict(aggregate=True, plane_search=True, ordered=True),
     {"splay.descent", "splay.select", "splay.fold", "splay.refresh"}),
    ("mixed", dict(),
     {"splay.fold", "splay.refresh", "splay.plane_rebuild", "splay.compact",
      "splay.state_rebuild"}),
])
def test_compiled_serving_program_carries_the_layer_scopes(path, flags,
                                                           scopes):
    st, plane = _empty()
    z = np.zeros((2, B), np.int32)
    text = sx._run_serving.lower(st, plane, z, z, z.astype(bool),
                                 **flags).compile().as_text()
    names = _op_names(text)
    found = {s for n in names for s in re.findall(r"splay\.[a-z_.]+", n)}
    assert scopes <= found, scopes - found
    if path == "mixed":
        assert "splay.descent" not in found
    # the compaction runs inside both the incremental refresh and the
    # full rebuild
    for outer in ("splay.refresh", "splay.plane_rebuild"):
        assert any(outer in n and "splay.compact" in n for n in names)


def test_serving_spans_sit_inside_the_callers_span(tmp_path):
    st, plane = _empty()
    kinds = np.full((2, B), sx.OP_INSERT, np.int32)
    keys = np.arange(2 * B, dtype=np.int32).reshape(2, B)
    upd = np.ones((2, B), bool)
    jax.block_until_ready(sx.run_serving(st, plane, kinds, keys, upd))
    jax.block_until_ready(sx.run_epoch(st, plane, kinds[0], keys[0],
                                       upd[0]))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("caller"):
            jax.block_until_ready(sx.run_serving(st, plane, kinds, keys,
                                                 upd))
            jax.block_until_ready(sx.run_epoch(st, plane, kinds[0],
                                               keys[0], upd[0]))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [ev for p in jax.profiler.ProfileData.from_file(path).planes
              if p.name.startswith("/host:") for line in p.lines
              for ev in line.events]
    caller, = [e for e in events if e.name == "caller"]
    spans = sorted((e for e in events if e.name.startswith("splay.serve.")),
                   key=lambda e: e.start_ns)
    assert [e.name for e in spans] == ["splay.serve.guard",
                                       "splay.serve.dispatch"] * 2
    end = caller.start_ns + caller.duration_ns
    for a, b in zip(spans, spans[1:]):
        assert a.start_ns + a.duration_ns <= b.start_ns
    assert caller.start_ns <= spans[0].start_ns
    assert spans[-1].start_ns + spans[-1].duration_ns <= end
    assert [dict(e.stats) for e in spans] == [{"epochs": 2, "batch": B}] * 2 \
        + [{"epochs": 1, "batch": B}] * 2


def _changed_rows(a, b) -> int:
    ka, kb = np.asarray(a.keys), np.asarray(b.keys)
    wa, wb = np.asarray(a.widths), np.asarray(b.widths)
    return int(((ka != kb).any(axis=1) | (wa != wb)).sum())


def _live_keys(st) -> set:
    s = sx.to_numpy(st)
    idx = np.arange(s["key"].shape[0])
    alive = ((idx >= 2) & (idx < int(s["n_alloc"])) & ~s["deleted"]
             & (s["key"] < sx.POS_INF_32))
    return set(s["key"][alive].tolist())


def _recount(st, plane, kinds, keys, upd, flags, oracle=None):
    """Step the ``[E, B]`` batch one ``run_epoch`` at a time with the
    host mirror of ``run_serving``'s overflow machine, and count each
    epoch's work in numpy: the batch's lanes, its unique updated live
    keys, the rebuild flag, the state's rebuild trigger (or the
    oracle's rebuilds on the mixed path) and the rows that differ
    between the plane entering and leaving the epoch."""
    want = dict.fromkeys(sx.COUNTERS, 0)
    pending = pressed = False
    for e in range(keys.shape[0]):
        live = _live_keys(st)
        m, dhits = int(st.m), int(st.dhits)
        st2, plane2, _, _, ovf, _, _ = sx.run_epoch(
            st, plane, kinds[e], keys[e], upd[e], rebuild=pending, **flags)
        want["epochs"] += 1
        want["fold_steps"] += B
        want["plane_rebuilds"] += int(pending)
        want["plane_rows_rebuilt"] += L
        want["plane_rows_changed"] += _changed_rows(plane, plane2)
        # one device: nothing goes through the routed exchange (the
        # four-device counts are in tests/test_serving_layout.py)
        want["route_queries"] += 0
        want["route_spilled"] += 0
        if flags.get("aggregate"):
            hit = {int(k) for k, u in zip(keys[e], upd[e])
                   if u and int(k) in live}
            want["fold_active"] += len(hit)
            n_upd = sum(bool(u) and int(k) in live
                        for k, u in zip(keys[e], upd[e]))
            m_after = m + n_upd
            want["state_rebuilds"] += int(m_after > 0
                                          and 2 * dhits >= m_after)
        else:
            want["fold_active"] += B
            before = oracle.rebuilds
            for kd, k, u in zip(kinds[e], keys[e], upd[e]):
                op = {sx.OP_CONTAINS: oracle.contains,
                      sx.OP_INSERT: oracle.insert,
                      sx.OP_DELETE: oracle.delete}[int(kd)]
                op(int(k), upd=bool(u))
            want["state_rebuilds"] += oracle.rebuilds - before
        pending, pressed = rc.overflow_machine_step(
            int(ovf), int(st2.size), B, W, pressed)
        st, plane = st2, plane2
    return st, plane, want


def _check_call(st, plane, kinds, keys, upd, flags, oracle=None):
    """One ``run_serving`` call against the stepped recount; returns the
    state and plane after it."""
    before = sx.serving_counters(st)
    st_r, plane_r, want = _recount(st, plane, kinds, keys, upd, flags,
                                   oracle)
    st, plane, *_ = sx.run_serving(st, plane, jnp.asarray(kinds),
                                   jnp.asarray(keys), jnp.asarray(upd),
                                   **flags)
    after = sx.serving_counters(st)
    assert {c: after[c] - before[c] for c in sx.COUNTERS} == want
    assert sx.serving_counters(st_r) == after
    return st, plane, want


def test_counters_match_a_recount_of_each_epochs_work():
    rng = np.random.default_rng(7)
    pool = rng.choice(np.arange(10, 400), 3 * B, replace=False).astype(
        np.int32)
    st, plane = _empty()
    assert sx.serving_counters(st) == dict.fromkeys(sx.COUNTERS, 0)
    oracle = ref_py.SplayList(max_level=L, p=1.0)
    mixed = dict(max_new=B // 2)

    # three epochs of inserts: with max_new = B/2 epoch 0 overflows, so
    # epoch 1 takes the full plane rebuild; epoch 2 overflows again
    ins = np.full((3, B), sx.OP_INSERT, np.int32)
    st, plane, want = _check_call(st, plane, ins, pool.reshape(3, B),
                                  np.ones((3, B), bool), mixed, oracle)
    assert want["plane_rebuilds"] == 1

    # hit a third of the keys, then delete them: the deleted keys hold
    # most of the hit mass, so the state rebuilds inside the fold
    hot = pool[:B]
    kinds = np.array([[sx.OP_CONTAINS] * B] * 3 + [[sx.OP_DELETE] * B],
                     np.int32)
    keys = np.stack([hot] * 4)
    st, plane, want = _check_call(st, plane, kinds, keys,
                                  np.ones((4, B), bool), mixed, oracle)
    assert want["state_rebuilds"] >= 1

    # plane path with a planted trigger: the deleted hits reach half the
    # hit mass, so the aggregated fold's rebuild check fires once
    st = st._replace(dhits=st.m)
    live = np.array(sorted(_live_keys(st)), np.int32)
    keys = np.stack([np.concatenate([rng.choice(live, B - 2),
                                     [1000, 1001]]) for _ in range(3)])
    keys = keys.astype(np.int32)
    upd = rng.random((3, B)) < 0.5
    upd[:, 0] = True
    st, plane, want = _check_call(
        st, plane, np.zeros((3, B), np.int32), keys, upd,
        dict(aggregate=True, plane_search=True))
    assert want["state_rebuilds"] == 1
    assert 0 < want["fold_active"] < want["fold_steps"]
    assert want["plane_rows_changed"] <= want["plane_rows_rebuilt"]


def test_counters_survive_snapshot_and_checkpoint(tmp_path):
    from repro.core import workload as wl
    from repro.serve import snapshot as snap
    from repro.serve.kv_cache import PagedKVPool
    from repro.train.checkpoint import CheckpointManager

    pool = PagedKVPool(48, 8, device=True, index_width=W, index_batch=B)
    trace = wl.kv_request_trace(40, 12, seed=3)
    for k, s in zip(np.asarray(trace.kinds).tolist(),
                    np.asarray(trace.seq_ids).tolist()):
        if k == wl.KV_CREATE:
            pool.create(s)
        elif k == wl.KV_RELEASE:
            pool.release(s)
        else:
            pool.lookup_batch([s])
    got = sx.serving_counters(pool._st)
    assert got["epochs"] > 0 and got["fold_steps"] > 0

    mgr = CheckpointManager(str(tmp_path / "snap"))
    snap.save_serving_snapshot(mgr, 40, pool)
    back, _, _ = snap.restore_serving_snapshot(mgr)
    assert sx.serving_counters(back._st) == got

    ckpt = CheckpointManager(str(tmp_path / "ckpt"))
    ckpt.save(1, pool._st, blocking=True)
    flat, _ = ckpt.load(1)
    np.testing.assert_array_equal(flat["params/counters"],
                                  np.asarray(pool._st.counters))

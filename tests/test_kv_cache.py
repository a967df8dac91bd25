"""The paged-KV pool and its splay index — host mode unit contracts,
the static-shape op padding seam, and the meshless host-vs-device
differential on recorded request traces (the forced-1x4-mesh half of
the differential runs in the ``benchmarks/serving_probe.py --parity``
subprocess, invoked by ``tests/test_serving_parity.py`` and CI)."""

import numpy as np
import pytest

from repro.core import splaylist as sx
from repro.core import workload as wl
from repro.serve.kv_cache import PagedKVPool


def _pool(device=False, n_pages=8, page_size=4, **kw):
    return PagedKVPool(n_pages, page_size, device=device, **kw)


# ---------------------------------------------------------------------------
# host-mode unit contracts
# ---------------------------------------------------------------------------

def test_create_lookup_release_roundtrip():
    p = _pool()
    assert p.create(7)
    assert p.lookup(7) == []              # live, no pages yet
    assert p.append_tokens(7, 5)          # 5 tokens -> 2 pages of 4
    assert len(p.lookup(7)) == 2
    p.release(7)
    assert p.lookup(7) is None
    assert len(p.free) == 8


def test_double_create_refused():
    p = _pool()
    assert p.create(1)
    assert not p.create(1)
    assert p.lookup(1) == []              # first create untouched


def test_lookup_absent_and_release_absent_are_noops():
    p = _pool()
    assert p.lookup(42) is None
    p.release(42)                         # must not raise
    assert len(p.free) == 8


def test_page_table_padding():
    p = _pool()
    p.create(3)
    p.append_tokens(3, 9)                 # 3 pages
    pt = p.page_table(3, 6)
    assert pt.shape == (6,) and pt.dtype == np.int32
    assert (pt[:3] >= 0).all() and (pt[3:] == -1).all()
    assert (p.page_table(99, 4) == -1).all()


def test_utilization_accounting():
    p = _pool()
    assert p.utilization == 0.0
    p.create(0)
    p.append_tokens(0, 16)                # 4 of 8 pages
    assert p.utilization == pytest.approx(0.5)
    p.release(0)
    assert p.utilization == 0.0


def test_append_exhaustion_keeps_partial_reservation():
    p = _pool(n_pages=2)
    p.create(0)
    assert p.append_tokens(0, 8)          # both pages
    p.create(1)
    assert not p.append_tokens(1, 1)      # dry free list
    assert p.lengths[1] == 0, "failed reservation must not count tokens"
    p.release(0)
    assert p.append_tokens(1, 1), "freed pages must be reclaimable"


def test_free_list_reclamation_under_churn():
    p = _pool(n_pages=4, page_size=2)
    for round_ in range(20):
        sid = round_ % 3
        assert p.create(sid)
        assert p.append_tokens(sid, 2 + round_ % 3)
        p.release(sid)
    assert sorted(p.free) == [0, 1, 2, 3]
    assert p.chains == {} and p.lengths == {}


def test_lookup_batch_host_matches_scalar():
    p = _pool()
    for s in (2, 5, 9):
        p.create(s)
    got = p.lookup_batch([2, 3, 5, 9, 11])
    assert got.tolist() == [True, False, True, True, False]


# ---------------------------------------------------------------------------
# pad_op_batch (the jit-stability seam the device pool relies on)
# ---------------------------------------------------------------------------

def test_pad_op_batch_is_noop_padding():
    kd, ks, up, n = sx.pad_op_batch(
        [sx.OP_INSERT, sx.OP_DELETE], [10, 20], [True, True], 6)
    assert n == 2 and kd.shape == (6,)
    assert kd[:2].tolist() == [sx.OP_INSERT, sx.OP_DELETE]
    assert (kd[2:] == sx.OP_CONTAINS).all()
    assert not up[2:].any()
    assert set(ks[2:]) <= {10, 20}, "pads must cycle the live keys"


def test_pad_op_batch_empty_and_overfull():
    kd, ks, up, n = sx.pad_op_batch([], [], [], 4)
    assert n == 0 and (kd == sx.OP_CONTAINS).all() and not up.any()
    with pytest.raises(ValueError):
        sx.pad_op_batch([0] * 5, [0] * 5, [True] * 5, 4)
    with pytest.raises(ValueError):
        sx.pad_op_batch([0, 0], [0], [True, True], 4)


def test_padded_epoch_leaves_state_bit_identical():
    """A padded op batch must change the state exactly as the unpadded
    one: pads are pure reads."""
    import jax.numpy as jnp
    from repro.core import device_index as dix

    def run(pad):
        st = sx.make(32, max_level=8)
        plane = dix.from_state_device(st, n_levels=8, width=16)
        kinds = np.full(3, sx.OP_INSERT, np.int32)
        keys = np.array([5, 9, 3], np.int32)
        upd = np.ones(3, bool)
        if pad:
            kinds, keys, upd, _ = sx.pad_op_batch(kinds, keys, upd, 8)
        st, plane, *_ = sx.run_epoch(st, plane, jnp.asarray(kinds),
                                     jnp.asarray(keys), jnp.asarray(upd))
        return st, plane

    st_a, pl_a = run(False)
    st_b, pl_b = run(True)
    for f in sx.SplayState._fields:
        if f == "counters":
            continue
        np.testing.assert_array_equal(np.asarray(getattr(st_a, f)),
                                      np.asarray(getattr(st_b, f)))
    # the work counters see the pads: 5 more fold steps, nothing else
    ca, cb = sx.serving_counters(st_a), sx.serving_counters(st_b)
    assert {c: cb[c] - ca[c] for c in sx.COUNTERS} == {
        **dict.fromkeys(sx.COUNTERS, 0), "fold_steps": 5, "fold_active": 5}
    np.testing.assert_array_equal(np.asarray(pl_a.keys),
                                  np.asarray(pl_b.keys))


# ---------------------------------------------------------------------------
# host-vs-device differential (meshless; the mesh half runs in the
# serving_probe subprocess)
# ---------------------------------------------------------------------------

def _replay(pool, trace):
    log = []
    for k, s in zip(trace.kinds.tolist(), trace.seq_ids.tolist()):
        if k == wl.KV_CREATE:
            ok = pool.create(s)
            if ok:
                ok = pool.append_tokens(s, 3) and ok
            log.append((k, s, ok))
        elif k == wl.KV_LOOKUP:
            c = pool.lookup(s)
            log.append((k, s, None if c is None else tuple(c)))
        else:
            pool.release(s)
            log.append((k, s, round(pool.utilization, 6)))
    return log, sorted(pool.chains)


@pytest.mark.parametrize("seed", [0, 3])
def test_device_pool_matches_host_on_trace(seed):
    trace = wl.kv_request_trace(150, 12, seed=seed)
    host = _replay(_pool(n_pages=24), trace)
    dev = _replay(_pool(n_pages=24, device=True, index_width=32,
                        index_batch=8), trace)
    assert dev == host


def test_device_pool_create_reject_at_index_width():
    p = _pool(n_pages=8, device=True, index_width=8, index_batch=4)
    for s in range(8):
        assert p.create(s)
    assert not p.create(99), "index at width must refuse admission"
    assert p.stats["create_rejects"] == 1
    p.release(0)
    assert p.create(99), "admission must reopen after a release"


def test_device_pool_batched_verdicts_and_telemetry():
    p = _pool(device=True, index_width=16, index_batch=4)
    for s in (1, 4, 6):
        p.create(s)
    got = p.lookup_batch([0, 1, 4, 5, 6, 7])
    assert got.tolist() == [False, True, True, False, True, False]
    assert p.stats["plane_queries"] == 6
    assert p.stats["plane_epochs"] == 2   # 6 ids in 4-wide epochs
    assert p.stats["flush_epochs"] >= 1
    assert p.stats["spill"] == 0
    # meshless: the single-pseudo-shard occupancy vector stays zero
    # (nothing is routed) and the controller never actuates on it
    assert p.last_occupancy.shape == (1,)
    assert p.ctrl.retraces == 0 and p.ctrl.escalations == 0

"""Ordered-operation kernels (DESIGN.md §5.10): predecessor/successor,
rank/select, range_count/range_scan, top_k on the device index plane.

The meshless edge-case battery runs here in-process: empty/inverted
ranges, int32-extreme endpoints, ``select`` past the live count, the
``range_scan`` counted-truncation contract, segmented-plane rejection,
and the ``OP_PRED``/``OP_RANGE`` epoch op codes against the state-walk
oracle.  The cross-shard battery (boundary-exact and boundary-straddling
ranges, duplicate boundary keys from empty shards, equal-lane AND
mass-weighted splits) needs ``--xla_force_host_platform_device_count``
before jax initializes, so it runs in the
``benchmarks/ordered_search_probe.py --parity`` subprocess — the same
pattern as the sharded-search and serving batteries.  CI runs that
probe in its "Ordered-op parity" step; locally both ride ``make test``.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import device_index as dix
from repro.core import splaylist as sx
from repro.core import workload as wl
from repro.kernels import ops as kops
from repro.kernels import splay_search as ssk
from repro.launch.mesh import make_auto_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAD, NEG = ssk.PAD_KEY, ssk.NEG_INF_KEY


def _seed_state(keys, cap=512, max_level=12):
    st = sx.make(capacity=cap, max_level=max_level)
    st, _, _ = sx.run_ops(
        st, jnp.full((len(keys),), sx.OP_INSERT, jnp.int32),
        jnp.asarray(np.asarray(keys, np.int32)),
        jnp.ones((len(keys),), bool))
    return st


def _plane(keys, n_levels=12, width=126, cap=512):
    st = _seed_state(keys, cap=cap, max_level=n_levels)
    return st, dix.from_state_device(st, n_levels=n_levels, width=width)


def test_rank_pred_succ_against_sorted_oracle():
    keys = np.unique(np.random.default_rng(0).integers(0, 900, 70))
    st, plane = _plane(keys)
    live = np.sort(keys)
    qs = np.concatenate([live[:10], live[:10] + 1, live[:10] - 1,
                         [-5, 0, 901]]).astype(np.int32)
    r = np.asarray(kops.splay_rank(plane, jnp.asarray(qs)))
    np.testing.assert_array_equal(
        r, np.searchsorted(live, qs, side="right"))
    pk, pr = (np.asarray(a) for a in
              kops.splay_predecessor(plane, jnp.asarray(qs)))
    for i, q in enumerate(qs):
        j = int(np.searchsorted(live, q, "right")) - 1
        assert (pk[i], pr[i]) == \
            ((live[j], j) if j >= 0 else (NEG, -1)), q
    sk, sr_ = (np.asarray(a) for a in
               kops.splay_successor(plane, jnp.asarray(qs)))
    for i, q in enumerate(qs):
        j = int(np.searchsorted(live, q, "left"))
        assert (sk[i], sr_[i]) == \
            ((live[j], j) if j < len(live) else (PAD, len(live))), q


def test_select_past_live_count_yields_pad():
    keys = list(range(0, 120, 3))
    _, plane = _plane(keys)
    n = len(keys)
    ranks = np.asarray([-10, -1, 0, n - 1, n, n + 1, 10 ** 6], np.int32)
    out = np.asarray(kops.splay_select(plane, jnp.asarray(ranks)))
    np.testing.assert_array_equal(
        out, [PAD, PAD, 0, keys[-1], PAD, PAD, PAD])


def test_empty_and_inverted_ranges():
    keys = list(range(100, 200, 5))
    _, plane = _plane(keys)
    lo = np.asarray([0, 101, 150, 300, 199, 150], np.int32)
    hi = np.asarray([99, 104, 149, 400, 100, 150], np.int32)
    cnt = np.asarray(kops.splay_range_count(
        plane, jnp.asarray(lo), jnp.asarray(hi)))
    np.testing.assert_array_equal(cnt, [0, 0, 0, 0, 0, 1])
    ks, c2, tr = (np.asarray(a) for a in kops.splay_range_scan(
        plane, jnp.asarray(lo), jnp.asarray(hi), max_range=4))
    np.testing.assert_array_equal(c2, cnt)
    np.testing.assert_array_equal(tr, 0)
    assert (ks[:5] == PAD).all()
    np.testing.assert_array_equal(ks[5], [150, PAD, PAD, PAD])


def test_int32_extreme_endpoints():
    keys = [NEG + 1, -7, 0, 3, PAD - 1]       # full legal key domain
    _, plane = _plane(keys, n_levels=8, width=30, cap=64)
    qs = np.asarray([-2 ** 31, NEG, NEG + 1, PAD - 1, PAD, 2 ** 31 - 1],
                    np.int32)
    r = np.asarray(kops.splay_rank(plane, jnp.asarray(qs)))
    np.testing.assert_array_equal(r, [0, 0, 1, 5, 5, 5])
    pk, _ = kops.splay_predecessor(plane, jnp.asarray(qs))
    np.testing.assert_array_equal(
        np.asarray(pk), [NEG, NEG, NEG + 1, PAD - 1, PAD - 1, PAD - 1])
    sk, sr_ = kops.splay_successor(plane, jnp.asarray(qs))
    np.testing.assert_array_equal(
        np.asarray(sk), [NEG + 1, NEG + 1, NEG + 1, PAD - 1, PAD, PAD])
    np.testing.assert_array_equal(np.asarray(sr_), [0, 0, 0, 4, 5, 5])
    # whole-domain and degenerate extreme ranges
    lo = np.asarray([-2 ** 31, PAD, -2 ** 31], np.int32)
    hi = np.asarray([2 ** 31 - 1, PAD, NEG], np.int32)
    cnt = np.asarray(kops.splay_range_count(
        plane, jnp.asarray(lo), jnp.asarray(hi)))
    np.testing.assert_array_equal(cnt, [5, 0, 0])


def test_range_scan_truncation_is_counted_never_silent():
    keys = list(range(0, 300, 2))             # 150 live keys
    _, plane = _plane(keys, width=254)
    lo = np.asarray([0, 0, 100], np.int32)
    hi = np.asarray([299, 19, 119], np.int32)
    ks, cnt, tr = (np.asarray(a) for a in kops.splay_range_scan(
        plane, jnp.asarray(lo), jnp.asarray(hi), max_range=8))
    np.testing.assert_array_equal(cnt, [150, 10, 10])
    np.testing.assert_array_equal(tr, [142, 2, 2])
    np.testing.assert_array_equal(ks[0], np.arange(0, 16, 2))
    np.testing.assert_array_equal(ks[1], np.arange(0, 16, 2))
    np.testing.assert_array_equal(ks[2], np.arange(100, 116, 2))
    # every lane is either a real member or PAD — capacity never drops
    # members without the truncated counter saying exactly how many
    assert ((ks != PAD).sum(axis=1) == np.minimum(cnt, 8)).all()
    np.testing.assert_array_equal(tr, np.maximum(cnt - 8, 0))


def test_top_k_by_hit_mass_ties_by_rank():
    keys = list(range(0, 60, 2))
    st, plane = _plane(keys, n_levels=8, width=62, cap=128)
    # drive hit mass onto a few keys via update-contains epochs
    hot = np.asarray([10, 10, 10, 40, 40, 4], np.int32)
    st, _, _ = sx.run_ops(
        st, jnp.full((len(hot),), sx.OP_CONTAINS, jnp.int32),
        jnp.asarray(hot), jnp.ones((len(hot),), bool))
    plane = dix.from_state_device(st, n_levels=8, width=62)
    tk, th, tr = (np.asarray(a) for a in kops.splay_top_k(
        plane, jnp.asarray(np.asarray(st.selfhits)), 5))
    assert tk[0] == 10 and tk[1] == 40 and tk[2] == 4
    assert th[0] >= th[1] >= th[2] >= th[3] == th[4]
    # past the hot set (the insert-only keys all tie on hit mass) the
    # tie breaks by ascending rank, i.e. key order itself
    assert (np.diff(tr[3:]) > 0).all()
    # k past the live count pads out
    tk2, th2, tr2 = (np.asarray(a) for a in kops.splay_top_k(
        plane, jnp.asarray(np.asarray(st.selfhits)), len(keys)))
    assert (tk2 != PAD).all()


def test_ordered_ops_reject_segmented_replicated_plane():
    """Interior pad runs (a concrete mass-split snapshot seen without
    its mesh) would silently corrupt the packed-rank arithmetic."""
    _, plane = _plane(list(range(0, 80, 2)), n_levels=6, width=124,
                      cap=256)
    keys = np.asarray(plane.keys).copy()
    keys[-1, 10:20] = PAD                     # interior pad run
    seg = plane._replace(keys=jnp.asarray(keys))
    qs = jnp.asarray(np.asarray([0, 4], np.int32))
    with pytest.raises(ValueError, match="segmented"):
        kops.splay_select(seg, jnp.asarray(np.asarray([0], np.int32)))
    with pytest.raises(ValueError, match="segmented"):
        kops.splay_predecessor(seg, qs)
    with pytest.raises(ValueError, match="segmented"):
        kops.splay_range_scan(seg, qs, qs, max_range=2)


def test_epoch_op_codes_match_state_walk():
    """OP_PRED/OP_RANGE through the ordered plane_search epoch ==
    the run_ops state walk == the numpy oracle; ordered lanes are pure
    reads (no hit mass folded)."""
    rng = np.random.default_rng(3)
    keys = np.unique(rng.integers(0, 800, 90)).astype(np.int32)
    st, plane = _plane(keys, cap=512)
    live = np.sort(keys)
    B = 32
    kinds = rng.choice([sx.OP_CONTAINS, sx.OP_PRED, sx.OP_RANGE],
                       B).astype(np.int32)
    qs = rng.integers(-5, 900, B).astype(np.int32)
    ups = rng.random(B) < 0.5

    def oracle(kd, q):
        if kd == sx.OP_CONTAINS:
            return int(q in live)
        i = int(np.searchsorted(live, q, side="right"))
        if kd == sx.OP_PRED:
            return int(live[i - 1]) if i > 0 else sx.NEG_INF_32
        return i
    exp = np.asarray([oracle(k, q) for k, q in zip(kinds, qs)], np.int32)

    _, res1, _ = sx.run_ops(st, jnp.asarray(kinds), jnp.asarray(qs),
                            jnp.asarray(ups))
    np.testing.assert_array_equal(np.asarray(res1), exp)
    assert np.asarray(res1).dtype == np.int32

    st2, _, res2, _, _, _, _ = sx.run_epoch(
        st, plane, jnp.asarray(kinds), jnp.asarray(qs),
        jnp.asarray(ups), aggregate=True, plane_search=True,
        ordered=True)
    np.testing.assert_array_equal(np.asarray(res2), exp)
    # pure reads: only update-contains lanes fold hit mass
    st3, _, _ = sx.run_ops(
        st, jnp.asarray(kinds), jnp.asarray(qs),
        jnp.asarray(ups & (kinds == sx.OP_CONTAINS)))
    np.testing.assert_array_equal(np.asarray(st2.selfhits),
                                  np.asarray(st3.selfhits))


def test_kv_pool_ordered_queries_host_vs_device():
    """PagedKVPool.predecessor / lookup_range answer identically from
    the host live-set and the device plane, with truncation counted in
    the stats."""
    from repro.serve.kv_cache import PagedKVPool
    pools = [PagedKVPool(32, 4),
             PagedKVPool(32, 4, device=True, index_width=32,
                         index_batch=8)]
    for p in pools:
        for s in (2, 3, 5, 8, 13, 21):
            assert p.create(s)
    outs = []
    for p in pools:
        got = [p.predecessor(1), p.predecessor(8), p.predecessor(99)]
        ids, cnt, tr = p.lookup_range(3, 20, max_range=3)
        got.append((tuple(ids.tolist()), cnt, tr))
        outs.append((got, p.stats["range_truncated"],
                     p.stats["pred_queries"], p.stats["range_queries"]))
    assert outs[0] == outs[1]
    got, truncated, npred, nrange = outs[0]
    assert got[:3] == [None, 8, 21]
    assert got[3] == ((3, 5, 8), 4, 1)
    assert (truncated, npred, nrange) == (1, 3, 1)


def test_kv_scan_trace_shape():
    tr = wl.kv_scan_trace(120, 12, seed=5)
    assert tr.hi_ids is not None and len(tr.hi_ids) == len(tr.kinds)
    n_scan = int((tr.kinds == wl.KV_SCAN).sum())
    n_pred = int((tr.kinds == wl.KV_PRED).sum())
    assert n_scan > 0 and n_pred > 0
    m = tr.kinds == wl.KV_SCAN
    assert (tr.hi_ids[m] >= tr.seq_ids[m]).all()
    # membership traces stay scan-free
    base = wl.kv_request_trace(120, 12, seed=5)
    assert base.hi_ids is None
    assert not np.isin(base.kinds, [wl.KV_SCAN, wl.KV_PRED]).any()


def test_ordered_parity_on_host_mesh():
    """The cross-shard battery (boundary-exact/straddling ranges under
    both splits, int32 extremes, truncation) in the probe subprocess."""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)            # probe sets its own
    r = subprocess.run(
        [sys.executable, "benchmarks/ordered_search_probe.py",
         "--parity"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=1800)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "ORDERED PARITY OK" in r.stdout


_needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs a multi-device runtime (forced host mesh)")


@_needs_mesh
def test_duplicate_boundary_keys_on_sparse_segmented_plane():
    """A mass-split plane with fewer live keys than shards leaves
    shards empty — the suffix-min boundary table then carries duplicate
    boundary keys, and every ordered op must still answer exactly."""
    from repro.parallel import sharding as shd
    keys = [5, 9, 700]
    st, plane = _plane(keys, n_levels=6, width=16, cap=64)
    mesh = make_auto_mesh((1, 4), ("data", "model"))
    pl = shd.shard_index_plane(plane, mesh)
    for split in ("lanes", "mass"):
        ps, ovf = dix.refresh_device_sharded(st, pl, mesh=mesh,
                                             split=split)
        assert int(ovf) == 0
        qs = jnp.asarray(np.asarray([0, 5, 9, 10, 700, 701], np.int32))
        np.testing.assert_array_equal(
            np.asarray(kops.splay_rank(ps, qs)), [0, 1, 2, 2, 3, 3])
        sel = kops.splay_select(
            ps, jnp.asarray(np.asarray([0, 1, 2, 3], np.int32)))
        np.testing.assert_array_equal(np.asarray(sel), [5, 9, 700, PAD])
        ks, cnt, tr = kops.splay_range_scan(
            ps, jnp.asarray(np.asarray([0, 6], np.int32)),
            jnp.asarray(np.asarray([1000, 8], np.int32)), max_range=2)
        np.testing.assert_array_equal(np.asarray(cnt), [3, 0])
        np.testing.assert_array_equal(np.asarray(tr), [1, 0])
        np.testing.assert_array_equal(np.asarray(ks)[0], [5, 9])

"""The tiered descent (DESIGN.md §5.2) against the pure-jnp oracle
``kernels/ref.splay_search_ref``: a sweep of plane shapes, the int32
boundaries, host planes and bare matrices, the degenerate planes the
fetch schedule and row widths must handle, the query-block validation
seam, and the resident-sub-plane fast path (single-device half — the
shard_map half runs in ``benchmarks/sharded_search_probe.py --parity``
via ``tests/test_sharded_search.py``)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import device_index as dix
from repro.core import level_arrays as la
from repro.core import workload as wl
from repro.kernels import ref
from repro.kernels import splay_search as ssk


def _device_plane(keys, heights, width, n_levels):
    kk = np.full(width, ssk.PAD_KEY, np.int32)
    hh = np.zeros(width, np.int32)
    kk[:len(keys)] = keys
    hh[:len(keys)] = heights
    return dix.build_device(jnp.asarray(kk), jnp.asarray(hh), n_levels)


def _assert_matches_ref(plane, qs, qb=64):
    """The tiered triple on ``plane`` (a plane struct or a bare matrix)
    equals the oracle's on its key matrix."""
    qsj = jnp.asarray(np.asarray(qs, np.int32))
    out = ssk.splay_search(plane, qsj, query_block=qb, sharded=False)
    keys = jnp.asarray(getattr(plane, "keys", plane))
    for got, want, name in zip(out, ref.splay_search_ref(keys, qsj),
                               ("found", "rank", "level_found")):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=name)


@pytest.mark.parametrize("n,width,levels,nq,qb", [
    (90, 128, 6, 96, 32),
    (40, 48, 6, 80, 16),          # width under one 128-lane chunk
    (40, 48, 6, 37, 16),          # non-divisible batch (padding lanes)
    (200, 257, 6, 40, 16),        # prime width: a 1-lane last chunk
    (900, 1000, 8, 300, 64),      # width not a chunk multiple
    (40, 64, 6, 1, 16),           # a single query
    (50, 64, 1, 40, 16),          # one level: the bottom row alone
])
def test_tiered_matches_ref_sweep(n, width, levels, nq, qb):
    rng = np.random.default_rng(n + width)
    keys = np.sort(rng.choice(10 ** 6, n, replace=False)).astype(np.int32)
    h = np.minimum(rng.geometric(0.5, n) - 1, levels - 1).astype(np.int32)
    plane = _device_plane(keys, h, width, levels)
    qs = np.concatenate([rng.choice(keys, nq // 2),
                         rng.integers(0, 10 ** 6, nq - nq // 2)])
    _assert_matches_ref(plane, qs, qb)


def test_tiered_boundaries():
    """Extremes: int32 edges, below-min/above-max, the PAD sentinel
    neighbourhood.  A query equal to the PAD sentinel itself is no key:
    not found, ranked past the last live key, at no level (the oracle
    would count the pad lanes, so it is checked directly)."""
    rng = np.random.default_rng(7)
    keys = np.sort(rng.choice(10 ** 6, 60, replace=False)).astype(np.int32)
    h = np.minimum(rng.geometric(0.5, 60) - 1, 5).astype(np.int32)
    plane = _device_plane(keys, h, 64, 6)
    i32 = 2 ** 31 - 1
    qs = [-2 ** 31, -i32, int(keys[0]) - 1, int(keys[0]), int(keys[-1]),
          int(keys[-1]) + 1, ssk.PAD_KEY - 1]
    _assert_matches_ref(plane, qs, qb=8)
    f, r, lv = ssk.splay_search(plane, jnp.asarray([i32], jnp.int32),
                                query_block=8, sharded=False)
    assert (bool(f[0]), int(r[0]), int(lv[0])) == (False, 59, 6)


def _fixture(width, alpha, nq, seed=0):
    keys, heights, qs = wl.zipf_level_fixture(width, alpha, nq, seed)
    return la.build(keys, heights, min_levels=6), qs


def test_tiered_host_plane_and_bare_matrix():
    """Host ``LevelArrays`` planes and bare matrices (row widths counted
    on the fly, or passed in) answer like the oracle."""
    L, qs = _fixture(256, 1.0, 128, seed=3)
    _assert_matches_ref(L, qs, qb=32)
    _assert_matches_ref(jnp.asarray(L.keys), qs, qb=32)
    qsj = jnp.asarray(qs)
    out = ssk.splay_search(jnp.asarray(L.keys), qsj, query_block=32,
                           widths=jnp.asarray(L.widths))
    for got, want in zip(out, ref.splay_search_ref(jnp.asarray(L.keys),
                                                   qsj)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ordered_ops_and_sharded_search_refuse_a_bare_matrix():
    """Packed-global ranks are a plane-level notion: the sharded search
    and the ordered ops take a plane struct, never a bare matrix."""
    L, qs = _fixture(64, 1.0, 8, seed=4)
    bare, qsj = jnp.asarray(L.keys), jnp.asarray(qs)
    with pytest.raises(TypeError, match="index plane struct"):
        ssk.splay_search_sharded(bare, qsj)
    with pytest.raises(TypeError, match="index plane struct"):
        ssk.splay_rank(bare, qsj)
    with pytest.raises(TypeError, match="index plane struct"):
        ssk.splay_select(bare, jnp.zeros((2,), jnp.int32))
    # the plane itself is accepted by both
    assert ssk.splay_rank(L, qsj).shape == (8,)
    assert ssk.splay_search_sharded(L, qsj)[0].shape == (8,)


# ---------------------------------------------------------------------------
# row widths and fetch schedule on degenerate planes
# ---------------------------------------------------------------------------

def test_helpers_all_empty_plane():
    lvk = jnp.full((4, 16), ssk.PAD_KEY, jnp.int32)
    assert np.asarray(ssk.row_widths(lvk)).tolist() == [0, 0, 0, 0]
    # every row aliases the bottom block: no DMA for empty rows
    fetch = ssk._fetch_schedule(ssk.row_widths(lvk), 4)
    assert np.asarray(fetch).tolist() == [3, 3, 3, 3]
    # the search itself: nothing found, rank -1
    plane = _device_plane(np.empty(0, np.int32), np.empty(0, np.int32),
                          16, 4)
    _assert_matches_ref(plane, [0, 5, -3], qb=4)


def test_helpers_single_live_lane():
    lvk = np.full((3, 8), ssk.PAD_KEY, np.int32)
    lvk[:, 0] = 42                      # one key, full height
    lvk = jnp.asarray(lvk)
    assert np.asarray(ssk.row_widths(lvk)).tolist() == [1, 1, 1]
    assert np.asarray(ssk._fetch_schedule(
        ssk.row_widths(lvk), 3)).tolist() == [0, 1, 2]
    plane = _device_plane(np.array([42], np.int32),
                          np.array([2], np.int32), 8, 3)
    np.testing.assert_array_equal(np.asarray(plane.keys),
                                  np.asarray(lvk))
    _assert_matches_ref(plane, [41, 42, 43], qb=4)


def test_helpers_empty_top_rows():
    """Empty rows (always a top prefix — heights are contiguous): the
    fetch schedule aliases them to the first live row below, and the
    descent answers through them like the oracle."""
    keys = np.arange(0, 40, 2, dtype=np.int32)
    h = np.zeros(20, np.int32)
    h[3] = 2                            # tallest key: rows 0-1 empty
    plane = _device_plane(keys, h, 32, 5)
    w = np.asarray(plane.widths)
    assert (w[:2] == 0).all() and (w[2:4] == 1).all() and w[4] == 20
    fetch = np.asarray(ssk._fetch_schedule(plane.widths, 5))
    assert fetch.tolist() == [2, 2, 2, 3, 4]
    _assert_matches_ref(plane, list(range(-1, 42)), qb=16)


def test_helpers_segmented_empty_block():
    """A mass-split shard can receive an empty segment: its local
    sub-plane assembles to the all-empty plane and answers nothing."""
    seg = jnp.full((12,), ssk.PAD_KEY, jnp.int32)
    local = dix._assemble_device(seg, jnp.zeros((12,), jnp.int32),
                                 jnp.full((12,), -1, jnp.int32), 4)
    assert np.asarray(local.widths).tolist() == [0, 0, 0, 0]
    _assert_matches_ref(local, [1, 2, 3], qb=4)


# ---------------------------------------------------------------------------
# query-block validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bad", [0, -4, 2.5, "64", True])
def test_query_block_validation(bad):
    L, qs = _fixture(64, 1.0, 16, seed=1)
    qsj = jnp.asarray(qs)
    with pytest.raises(ValueError, match="query_block"):
        ssk.splay_search(L, qsj, query_block=bad)
    with pytest.raises(ValueError, match="query_block"):
        ssk.splay_search(jnp.asarray(L.keys), qsj, query_block=bad)
    with pytest.raises(ValueError, match="query_block"):
        ssk.splay_search_sharded(L, qsj, query_block=bad)


# ---------------------------------------------------------------------------
# resident sub-plane (single-device half)
# ---------------------------------------------------------------------------

def test_local_subplane_resident_matches_assembled():
    """On a packed plane the resident branch (residency bit forced on)
    must reproduce the assembled local plane exactly — same keys
    blocks, widths re-derived from provenance — and flag
    ``assembled=0`` where the stale branch flags 1."""
    rng = np.random.default_rng(3)
    keys = np.sort(rng.choice(10 ** 5, 50, replace=False)).astype(np.int32)
    h = np.minimum(rng.geometric(0.5, 50) - 1, 5).astype(np.int32)
    plane = _device_plane(keys, h, 64, 6)
    stale = plane._replace(local_ok=jnp.zeros((1,), jnp.int32))
    resident = plane._replace(local_ok=jnp.ones((1,), jnp.int32))
    loc_s, a_s = ssk._local_subplane(stale, n_levels=6)
    loc_r, a_r = ssk._local_subplane(resident, n_levels=6)
    assert int(a_s) == 1 and int(a_r) == 0
    for f in ("keys", "widths"):
        np.testing.assert_array_equal(
            np.asarray(getattr(loc_s, f)), np.asarray(getattr(loc_r, f)),
            err_msg=f"resident-vs-assembled field={f}")


def test_as_device_plane_host_promotion():
    """Host planes promote to the full device pytree with stale
    residency (the assemble fallback stays their path), an unknown slot
    map, and the host keys/widths/heights unchanged."""
    L, _ = _fixture(64, 1.0, 16, seed=2)
    p = ssk._as_device_plane(L)
    assert hasattr(p, "local_ok") and int(p.local_ok[0]) == 0
    for f in ("keys", "widths", "heights"):
        np.testing.assert_array_equal(np.asarray(getattr(p, f)),
                                      getattr(L, f), err_msg=f)
    assert (np.asarray(p.slots) == -1).all()
    assert ssk._as_device_plane(p) is p

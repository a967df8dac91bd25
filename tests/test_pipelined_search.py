"""Foresight-pipelined descent (DESIGN.md §5.8): parity against the
tiered interpret-mode oracle, the streamed-bytes counter and its
block-level early exit, the degenerate-plane behaviour of the window
helpers the pipeline schedules from, the query-block validation seam,
and the resident-sub-plane fast path (single-device half — the
shard_map half runs in ``benchmarks/sharded_search_probe.py --parity``
via ``tests/test_sharded_search.py``)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import device_index as dix
from repro.core import level_arrays as la
from repro.core import workload as wl
from repro.kernels import ops
from repro.kernels import splay_search as ssk


def _device_plane(keys, heights, width, n_levels):
    kk = np.full(width, ssk.PAD_KEY, np.int32)
    hh = np.zeros(width, np.int32)
    kk[:len(keys)] = keys
    hh[:len(keys)] = heights
    return dix.build_device(jnp.asarray(kk), jnp.asarray(hh), n_levels)


def _assert_parity(plane, qs, qb=64):
    """Pipelined triple == tiered triple on the same plane; returns the
    per-block streamed-bytes counter for byte-model assertions."""
    qsj = jnp.asarray(np.asarray(qs, np.int32))
    f0, r0, l0 = ssk.splay_search(plane, qsj, query_block=qb,
                                  sharded=False, pipelined=False)
    f1, r1, l1, nb = ssk.splay_search_pipelined(plane, qsj,
                                                query_block=qb)
    np.testing.assert_array_equal(np.asarray(f0), np.asarray(f1))
    np.testing.assert_array_equal(np.asarray(r0), np.asarray(r1))
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))
    return np.asarray(nb)


@pytest.mark.parametrize("n,width,levels,nq,qb", [
    (90, 128, 6, 96, 32),
    (40, 48, 6, 80, 16),          # width 48 -> 16-wide DMA tiles
    (40, 48, 6, 37, 16),          # non-divisible batch (padding lanes)
])
def test_pipelined_parity_sweep(n, width, levels, nq, qb):
    rng = np.random.default_rng(n + width)
    keys = np.sort(rng.choice(10 ** 6, n, replace=False)).astype(np.int32)
    h = np.minimum(rng.geometric(0.5, n) - 1, levels - 1).astype(np.int32)
    plane = _device_plane(keys, h, width, levels)
    qs = np.concatenate([rng.choice(keys, nq // 2),
                         rng.integers(0, 10 ** 6, nq - nq // 2)])
    _assert_parity(plane, qs, qb)


def test_pipelined_parity_boundaries():
    """Extremes: int32 edges, below-min/above-max, the PAD sentinel
    neighbourhood — every lane must resolve to the tiered answer."""
    rng = np.random.default_rng(7)
    keys = np.sort(rng.choice(10 ** 6, 60, replace=False)).astype(np.int32)
    h = np.minimum(rng.geometric(0.5, 60) - 1, 5).astype(np.int32)
    plane = _device_plane(keys, h, 64, 6)
    i32 = 2 ** 31 - 1
    qs = [-2 ** 31, -i32, int(keys[0]) - 1, int(keys[0]), int(keys[-1]),
          int(keys[-1]) + 1, ssk.PAD_KEY - 1, i32]
    _assert_parity(plane, qs, qb=8)


def test_pipelined_host_plane_and_bare_matrix():
    """Host ``LevelArrays`` planes and bare matrices take the derived-
    companion path (``bottom_ranks`` on the fly) and still match."""
    L, qs = _fixture(256, 1.0, 128, seed=3)
    _assert_parity(L, qs, qb=32)
    qsj = jnp.asarray(qs)
    f0, r0, l0 = ssk.splay_search(jnp.asarray(L.keys), qsj,
                                  query_block=32, pipelined=False)
    f1, r1, l1, _ = ssk.splay_search_pipelined(jnp.asarray(L.keys), qsj,
                                               query_block=32)
    np.testing.assert_array_equal(np.asarray(f0), np.asarray(f1))
    np.testing.assert_array_equal(np.asarray(r0), np.asarray(r1))
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))


def _fixture(width, alpha, nq, seed=0):
    keys, heights, qs = wl.zipf_level_fixture(width, alpha, nq, seed)
    return la.build(keys, heights, min_levels=6), qs


def test_pipelined_dispatch_seam():
    """``splay_search(pipelined=True)`` returns the same triple as the
    4-tuple entry point minus the bytes counter, and ``pipelined=None``
    resolves to the tiered kernel (on every backend)."""
    L, qs = _fixture(128, 1.0, 64, seed=5)
    qsj = jnp.asarray(qs)
    out_p = ssk.splay_search(L, qsj, query_block=32, sharded=False,
                             pipelined=True)
    out_4 = ssk.splay_search_pipelined(L, qsj, query_block=32)
    out_d = ssk.splay_search(L, qsj, query_block=32, sharded=False)
    for a, b in zip(out_p, out_4[:3]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(out_d, out_4[:3]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("width,pipelined,kind", [
    (131072, None, "tiered"),
    (131072, False, "tiered"),
    (4096, True, "pipelined"),
    (257, True, "tiered (pipelined width fallback)"),
    (131072, True, "tiered (pipelined width fallback)"),
])
def test_descent_kind_names_the_kernel(width, pipelined, kind):
    assert ssk.descent_kind(width, pipelined) == kind


def test_pipelined_refuses_to_compile():
    """The pipelined probes are gathers the TPU compiler refuses, so a
    compiled (non-interpret) request raises instead of swapping kernels."""
    L, qs = _fixture(128, 1.0, 8, seed=5)
    with pytest.raises(ValueError, match="interpret mode only"):
        ssk._splay_search_pipelined_arrays(jnp.asarray(L.keys),
                                           jnp.asarray(qs), query_block=8,
                                           interpret=False)


# ---------------------------------------------------------------------------
# streamed bytes + block-level early exit
# ---------------------------------------------------------------------------

def test_early_exit_suppresses_row_fetches():
    """All keys at top height: every row is the full key set, so every
    query resolves on row 0 (hit, or a width-1 bottom window).  The
    pipeline may have row 1 speculatively in flight, but rows 2+ must
    never be fetched — the counter stays under a 2-row cover while the
    whole-row model pays all of them."""
    n_levels, width = 8, 64
    keys = np.arange(10, 10 + 3 * 48, 3, dtype=np.int32)
    plane = _device_plane(keys, np.full(48, n_levels - 1, np.int32),
                          width, n_levels)
    qs = np.concatenate([keys[:16], keys[:16] + 1])
    nb = _assert_parity(plane, qs, qb=32)
    two_row_cover = 2 * 3 * width * 4          # keys+rank_map+bot_rank
    assert (nb <= two_row_cover).all(), nb
    assert (nb < 2 * n_levels * width * 4).all(), nb


def test_hot_members_stream_fewer_bytes():
    """A batch of tall-key members early-exits high and streams strictly
    fewer bytes than a miss-heavy batch descending to the bottom row."""
    rng = np.random.default_rng(11)
    L, _ = _fixture(512, 1.4, 64, seed=14)
    hot = np.asarray(L.keys[0])
    hot = hot[hot != ssk.PAD_KEY]
    assert hot.size, "fixture has no top-row keys"
    q_hot = rng.choice(hot, 64).astype(np.int32)
    bot = np.asarray(L.keys[-1])
    bot = bot[bot != ssk.PAD_KEY]
    q_miss = (bot[rng.integers(0, bot.size - 1, 64)] + 1).astype(np.int32)
    nb_hot = _assert_parity(L, q_hot, qb=64)
    nb_miss = _assert_parity(L, q_miss, qb=64)
    assert nb_hot.sum() < nb_miss.sum(), (nb_hot, nb_miss)


def test_untileable_width_falls_back_to_tiered():
    """A width with no DMA tile <= 256 inside the 64-tile budget (257 is
    prime) falls back to the tiered stream and reports its whole-row
    byte model: every level's key row, padded to whole 128-lane chunks
    (257 -> 384 lanes)."""
    rng = np.random.default_rng(13)
    keys = np.sort(rng.choice(10 ** 6, 200, replace=False)).astype(np.int32)
    h = np.minimum(rng.geometric(0.5, 200) - 1, 5).astype(np.int32)
    plane = _device_plane(keys, h, 257, 6)
    qs = np.concatenate([keys[:20], rng.integers(0, 10 ** 6, 20)])
    nb = _assert_parity(plane, qs, qb=16)
    assert (nb == 6 * 384 * 4).all(), nb


# ---------------------------------------------------------------------------
# window helpers on degenerate planes
# ---------------------------------------------------------------------------

def test_helpers_all_empty_plane():
    lvk = jnp.full((4, 16), ssk.PAD_KEY, jnp.int32)
    assert np.asarray(ssk.row_widths(lvk)).tolist() == [0, 0, 0, 0]
    # every row aliases the bottom block: no DMA for empty rows
    fetch = ssk._fetch_schedule(ssk.row_widths(lvk), 4)
    assert np.asarray(fetch).tolist() == [3, 3, 3, 3]
    # pad entries map to the next row's live width (0 here)
    assert (np.asarray(ssk.rank_windows(lvk))[:-1] == 0).all()
    assert (np.asarray(ssk.bottom_ranks(lvk))[:-1] == 0).all()
    # the search itself: nothing found, rank -1 semantics via parity
    plane = _device_plane(np.empty(0, np.int32), np.empty(0, np.int32),
                          16, 4)
    _assert_parity(plane, [0, 5, -3], qb=4)


def test_helpers_single_live_lane():
    lvk = np.full((3, 8), ssk.PAD_KEY, np.int32)
    lvk[:, 0] = 42                      # one key, full height
    lvk = jnp.asarray(lvk)
    assert np.asarray(ssk.row_widths(lvk)).tolist() == [1, 1, 1]
    assert np.asarray(ssk._fetch_schedule(
        ssk.row_widths(lvk), 3)).tolist() == [0, 1, 2]
    rm = np.asarray(ssk.rank_windows(lvk))
    br = np.asarray(ssk.bottom_ranks(lvk))
    assert rm[0, 0] == 0 and br[0, 0] == 0
    assert (rm[:-1, 1:] == 1).all()     # pads -> next live width
    plane = _device_plane(np.array([42], np.int32),
                          np.array([2], np.int32), 8, 3)
    _assert_parity(plane, [41, 42, 43], qb=4)


def test_helpers_empty_top_rows():
    """Empty rows (always a top prefix — heights are contiguous): the
    fetch schedule aliases them to the first live row below, the rank
    windows stay the p=-1 virtual window through them, and the descent
    answers identically."""
    keys = np.arange(0, 40, 2, dtype=np.int32)
    h = np.zeros(20, np.int32)
    h[3] = 2                            # tallest key: rows 0-1 empty
    plane = _device_plane(keys, h, 32, 5)
    w = np.asarray(plane.widths)
    assert (w[:2] == 0).all() and (w[2:4] == 1).all() and w[4] == 20
    fetch = np.asarray(ssk._fetch_schedule(plane.widths, 5))
    assert fetch.tolist() == [2, 2, 2, 3, 4]
    _assert_parity(plane, list(range(-1, 42)), qb=16)


def test_helpers_segmented_empty_block():
    """A mass-split shard can receive an empty segment: its local
    sub-plane assembles to the all-empty plane and answers nothing."""
    seg = jnp.full((12,), ssk.PAD_KEY, jnp.int32)
    local = dix._assemble_device(seg, jnp.zeros((12,), jnp.int32),
                                 jnp.full((12,), -1, jnp.int32), 4)
    assert np.asarray(local.widths).tolist() == [0, 0, 0, 0]
    _assert_parity(local, [1, 2, 3], qb=4)


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
        ssk.splay_search_pipelined(L, qsj, query_block=bad)
    with pytest.raises(ValueError, match="query_block"):
        ssk.splay_search_full(jnp.asarray(L.keys), qsj, query_block=bad)


# ---------------------------------------------------------------------------
# resident sub-plane (single-device half)
# ---------------------------------------------------------------------------

def test_local_subplane_resident_matches_assembled():
    """On a packed plane the resident branch (residency bit forced on)
    must reproduce the assembled local plane exactly — same keys /
    rank_map / bot_rank blocks, widths re-derived from provenance —
    and flag ``assembled=0`` where the stale branch flags 1."""
    rng = np.random.default_rng(3)
    keys = np.sort(rng.choice(10 ** 5, 50, replace=False)).astype(np.int32)
    h = np.minimum(rng.geometric(0.5, 50) - 1, 5).astype(np.int32)
    plane = _device_plane(keys, h, 64, 6)
    stale = plane._replace(local_ok=jnp.zeros((1,), jnp.int32))
    resident = plane._replace(local_ok=jnp.ones((1,), jnp.int32))
    loc_s, a_s = ssk._local_subplane(stale, n_levels=6)
    loc_r, a_r = ssk._local_subplane(resident, n_levels=6)
    assert int(a_s) == 1 and int(a_r) == 0
    for f in ("keys", "widths", "rank_map", "bot_rank"):
        np.testing.assert_array_equal(
            np.asarray(getattr(loc_s, f)), np.asarray(getattr(loc_r, f)),
            err_msg=f"resident-vs-assembled field={f}")


def test_as_device_plane_host_promotion():
    """Host planes promote to the full device pytree with stale
    residency (the assemble fallback stays their path) and a derived
    ``bottom_ranks`` companion."""
    L, _ = _fixture(64, 1.0, 16, seed=2)
    p = ssk._as_device_plane(L)
    assert hasattr(p, "local_ok") and int(p.local_ok[0]) == 0
    np.testing.assert_array_equal(
        np.asarray(p.bot_rank),
        np.asarray(ssk.bottom_ranks(jnp.asarray(L.keys))))
    assert ssk._as_device_plane(p) is p

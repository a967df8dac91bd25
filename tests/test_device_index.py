"""Device-resident index plane vs the host numpy oracle (DESIGN.md §5.3).

The contract under test: ``build_device``/``from_state_device``/
``refresh_device`` produce level arrays bit-identical to the host
``level_arrays.build`` on the same state, at stable shapes, across
insert/delete/height-churn epoch streams — with the level arrays never
leaving the device (the epoch loop is one jit; the jaxpr is asserted
callback-free)."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import device_index as dix
from repro.core import level_arrays as la
from repro.core import splaylist as sx
from repro.kernels import ops, ref


def _assert_plane_equal(plane: dix.DeviceLevelArrays, host: la.LevelArrays,
                        msg=""):
    for f in ("keys", "widths", "heights"):
        np.testing.assert_array_equal(
            np.asarray(getattr(plane, f)), getattr(host, f),
            err_msg=f"{f} {msg}")


@pytest.mark.parametrize("n,hmax,min_levels", [
    (0, 1, 2), (1, 1, 2), (57, 4, 2), (300, 6, 3),
    (123, 1, 8),          # empty top rows (min_levels >> max height)
    (500, 7, 2),
])
def test_build_device_matches_host(n, hmax, min_levels):
    rng = np.random.default_rng(n + hmax)
    keys = rng.choice(10 ** 6, n, replace=False).astype(np.int32)
    heights = rng.integers(0, hmax, n).astype(np.int32)
    host = la.build(keys, heights, min_levels=min_levels)
    n_levels, width = host.keys.shape
    kp = np.full(width, dix.PAD_KEY, np.int32)
    hp = np.zeros(width, np.int32)
    kp[:n], hp[:n] = keys, heights
    dev = dix.build_device(jnp.asarray(kp), jnp.asarray(hp),
                           n_levels=n_levels)
    _assert_plane_equal(dev, host)


from conftest import seed_splay_state as _seed_state  # noqa: E402


def test_refresh_device_differential_mixed_epochs():
    """Insert/delete/height-churn streams: after every epoch the
    incrementally-refreshed plane equals a from-scratch host build at
    the same (stable) shape, and the slot map stays live-valid."""
    pool = list(range(0, 160, 2))
    st = _seed_state(pool)
    W, L = 254, 12
    plane = dix.from_state_device(st, n_levels=L, width=W)
    _assert_plane_equal(plane, la.from_state(st, min_levels=L, width=W))
    r = random.Random(1)
    for epoch in range(10):
        kinds, ks, ups = [], [], []
        for _ in range(64):
            x = r.random()
            if x < 0.55:
                kinds.append(sx.OP_CONTAINS); ks.append(r.choice(pool))
            elif x < 0.75:
                kinds.append(sx.OP_INSERT); ks.append(r.randrange(0, 400))
            else:
                kinds.append(sx.OP_DELETE)
                ks.append(r.choice(pool + list(range(1, 400, 7))))
            ups.append(r.random() < 0.7)
        st, _, _ = sx.run_ops(
            st, jnp.asarray(np.asarray(kinds, np.int32)),
            jnp.asarray(np.asarray(ks, np.int32)), jnp.asarray(ups))
        plane = dix.refresh_device(st, plane, max_new=64)
        assert plane.keys.shape == (L, W)      # stable, no recompiles
        _assert_plane_equal(
            plane, la.from_state(st, min_levels=L, width=W),
            msg=f"epoch {epoch}")
        w_bot = int(plane.widths[-1])
        slots = np.asarray(plane.slots)[:w_bot]
        assert (np.asarray(st.key)[slots]
                == np.asarray(plane.keys)[-1][:w_bot]).all()


def test_refresh_device_height_only_epochs():
    pool = list(range(0, 120, 2))
    st = _seed_state(pool)
    plane = dix.from_state_device(st, n_levels=12, width=254)
    for _ in range(3):
        qs = jnp.asarray(np.asarray(pool[:5] * 30, np.int32))
        st, _, _ = sx.run_contains_batch(st, qs,
                                         jnp.ones((len(qs),), bool))
        plane = dix.refresh_device(st, plane, max_new=64)
        _assert_plane_equal(
            plane, la.from_state(st, min_levels=12, width=254))


def test_refresh_device_survives_rebuild():
    """A delete-heavy epoch triggers splaylist.rebuild, which compacts
    slots and invalidates the plane's slot map — the refresh must detect
    staleness and re-derive it (scatter fallback), still bit-exact."""
    pool = list(range(0, 100, 2))
    st = _seed_state(pool)
    plane = dix.from_state_device(st, n_levels=12, width=254)
    dels = np.asarray(pool[:40], np.int32)
    st, _, _ = sx.run_ops(
        st, jnp.full((len(dels),), sx.OP_DELETE, jnp.int32),
        jnp.asarray(dels), jnp.ones((len(dels),), bool))
    plane = dix.refresh_device(st, plane, max_new=64)
    _assert_plane_equal(plane, la.from_state(st, min_levels=12, width=254))
    # and the re-derived slot map carries into the next epoch cleanly
    ins = np.asarray([1, 3, 9], np.int32)
    st, _, _ = sx.run_ops(
        st, jnp.full((3,), sx.OP_INSERT, jnp.int32), jnp.asarray(ins),
        jnp.ones((3,), bool))
    plane = dix.refresh_device(st, plane, max_new=64)
    _assert_plane_equal(plane, la.from_state(st, min_levels=12, width=254))


def test_refresh_device_transient_empty_keeps_shape():
    pool = list(range(0, 40, 2))
    st = _seed_state(pool, cap=128)
    plane = dix.from_state_device(st, n_levels=12, width=126)
    dels = np.asarray(pool, np.int32)
    st, _, _ = sx.run_ops(
        st, jnp.full((len(dels),), sx.OP_DELETE, jnp.int32),
        jnp.asarray(dels), jnp.ones((len(dels),), bool))
    plane = dix.refresh_device(st, plane, max_new=64)
    assert plane.keys.shape == (12, 126)
    assert int(plane.widths[-1]) == int(st.size)   # may be 0 or tiny
    # refresh out of the empty works too
    st, _, _ = sx.run_ops(
        st, jnp.full((3,), sx.OP_INSERT, jnp.int32),
        jnp.asarray(np.asarray([5, 7, 11], np.int32)),
        jnp.ones((3,), bool))
    plane = dix.refresh_device(st, plane, max_new=64)
    _assert_plane_equal(plane, la.from_state(st, min_levels=12, width=126))


def test_run_epoch_and_serving_loop_on_device():
    """The jitted epoch loop: batched contains + inserts + device
    refresh under one jit, no host callbacks in the jaxpr, final plane
    bit-identical to the host build of the final state."""
    pool = list(range(0, 200, 4))
    st = _seed_state(pool, cap=512, ml=14)
    W, L = 510, 14
    plane = dix.from_state_device(st, n_levels=L, width=W)

    E, B = 5, 32
    rng = np.random.default_rng(3)
    kinds = rng.choice([sx.OP_CONTAINS, sx.OP_CONTAINS, sx.OP_CONTAINS,
                        sx.OP_INSERT], (E, B)).astype(np.int32)
    keys = rng.choice(np.arange(0, 220), (E, B)).astype(np.int32)
    ups = rng.random((E, B)) < 0.6

    jaxpr = jax.make_jaxpr(
        lambda s, p, k, q, u: sx.run_serving(s, p, k, q, u))(
            st, plane, jnp.asarray(kinds), jnp.asarray(keys),
            jnp.asarray(ups))
    prims = {e.primitive.name for e in jaxpr.jaxpr.eqns}
    assert not prims & {"pure_callback", "io_callback", "callback"}

    st2, plane2, res, plen, ovf, spl, occ = sx.run_serving(
        st, plane, jnp.asarray(kinds), jnp.asarray(keys),
        jnp.asarray(ups))
    assert res.shape == plen.shape == (E, B)
    assert ovf.shape == (E,) and not np.asarray(ovf).any()
    assert spl.shape == (E,) and not np.asarray(spl).any()
    assert occ.shape == (E, 1) and not np.asarray(occ).any()
    _assert_plane_equal(plane2, la.from_state(st2, min_levels=L, width=W))

    # aggregate (flat-combined contains) epoch variant
    st3, plane3, res3, _, _, _, _ = sx.run_epoch(
        st, plane, jnp.asarray(kinds[0]), jnp.asarray(keys[0]),
        jnp.asarray(ups[0]), aggregate=True)
    _assert_plane_equal(plane3, la.from_state(st3, min_levels=L, width=W))
    assert res3.shape == (B,)


def test_plane_holds_only_what_its_readers_read():
    """The plane format: the key rectangle and row widths the descent
    reads, the heights and slot map the refresh reads, and the
    residency set of the sharded search — on host and device alike."""
    assert dix.DeviceLevelArrays._fields == (
        "keys", "widths", "heights", "slots", "local_bot",
        "local_heights", "local_live", "local_ok")
    assert la.LevelArrays._fields == ("keys", "widths", "heights")
    plane = dix.build_device(jnp.asarray([5, 3, dix.PAD_KEY, 9], jnp.int32),
                             jnp.asarray([0, 2, 0, 1], jnp.int32), 3)
    assert np.asarray(plane.keys).tolist() == [
        [3, dix.PAD_KEY, dix.PAD_KEY, dix.PAD_KEY],
        [3, 9, dix.PAD_KEY, dix.PAD_KEY],
        [3, 5, 9, dix.PAD_KEY]]
    assert np.asarray(plane.widths).tolist() == [1, 2, 3]


def test_kernels_consume_device_plane():
    """The search wrappers take the plane struct directly — the device
    plane and its host copy alike; results match the jnp reference
    oracle on the same rectangle."""
    pool = list(range(0, 256, 2))
    st = _seed_state(pool, cap=512, ml=14)
    plane = dix.from_state_device(st, n_levels=14, width=510)
    rng = np.random.default_rng(5)
    qs = jnp.asarray(np.concatenate(
        [rng.choice(pool, 100), rng.integers(0, 300, 60)]).astype(np.int32))
    f, r, lv = ops.splay_search(plane, qs)
    f0, r0, lv0 = ref.splay_search_ref(jnp.asarray(plane.keys), qs)
    np.testing.assert_array_equal(np.asarray(f), np.asarray(f0))
    np.testing.assert_array_equal(np.asarray(r), np.asarray(r0))
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(lv0))
    out_host = ops.splay_search(dix.to_host(plane), qs)
    for a, b in zip(out_host, (f0, r0, lv0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_refresh_overflow_counted_not_silent():
    """Regression for the silent-drop bug: an insert burst past
    ``max_new`` used to vanish from the plane with no signal.  Now the
    refresh reports exactly how many alive keys it could not represent,
    and a full rebuild restores them."""
    st = _seed_state(list(range(0, 100, 2)), cap=512)
    W, L = 254, 12
    plane = dix.from_state_device(st, n_levels=L, width=W)
    burst = np.arange(1, 81, 2, dtype=np.int32)          # 40 inserts
    st, _, _ = sx.run_ops(
        st, jnp.full((len(burst),), sx.OP_INSERT, jnp.int32),
        jnp.asarray(burst), jnp.ones((len(burst),), bool))
    plane, ovf = dix.refresh_device(st, plane, max_new=16,
                                    return_overflow=True)
    assert int(ovf) == len(burst) - 16
    # the plane is stale (missing exactly the dropped keys), not corrupt
    w_bot = int(plane.widths[-1])
    assert w_bot == int(st.size) - int(ovf)
    # the kept inserts are the smallest of the burst (documented policy)
    kept = set(np.asarray(plane.keys)[-1][:w_bot].tolist())
    assert set(burst[:16].tolist()) <= kept
    assert not (set(burst[16:].tolist()) & kept)
    # recovery: the full rebuild is bit-identical to a fresh build
    plane = dix.from_state_device(st, n_levels=L, width=W)
    _assert_plane_equal(plane, la.from_state(st, min_levels=L, width=W))
    # and a follow-up incremental refresh reports clean
    plane, ovf = dix.refresh_device(st, plane, max_new=16,
                                    return_overflow=True)
    assert int(ovf) == 0
    _assert_plane_equal(plane, la.from_state(st, min_levels=L, width=W))


def test_run_serving_overflow_triggers_rebuild_next_epoch():
    """The overflow/rebuild state machine (DESIGN.md §5.4): epoch 0's
    insert burst exceeds ``max_new`` (overflow reported, keys missing
    from the plane), epoch 1 runs the automatic ``from_state_device``
    rebuild — the final plane is bit-identical to a fresh build, no
    dropped keys."""
    st = _seed_state(list(range(0, 100, 2)), cap=512)
    W, L = 254, 12
    plane = dix.from_state_device(st, n_levels=L, width=W)
    E, B = 3, 48
    kinds = np.full((E, B), sx.OP_CONTAINS, np.int32)
    keys = np.zeros((E, B), np.int32)
    kinds[0, :] = sx.OP_INSERT
    keys[0, :] = np.arange(1, 2 * B, 2)                  # 48 fresh inserts
    keys[1:, :] = np.resize(np.arange(0, 100, 2), (E - 1, B))
    ups = np.ones((E, B), bool)
    st2, plane2, _, _, ovf, _, _ = sx.run_serving(
        st, plane, jnp.asarray(kinds), jnp.asarray(keys),
        jnp.asarray(ups), max_new=16)
    ovf = np.asarray(ovf)
    assert ovf[0] == B - 16                              # burst flagged
    assert (ovf[1:] == 0).all()                          # rebuilt clean
    _assert_plane_equal(plane2, la.from_state(st2, min_levels=L, width=W))
    # no dropped keys: every inserted key is present in the final plane
    w_bot = int(plane2.widths[-1])
    final = set(np.asarray(plane2.keys)[-1][:w_bot].tolist())
    assert set(keys[0].tolist()) <= final


def test_run_serving_repeated_overflow_bursts():
    """Sustained pressure on the overflow state machine: two insert
    bursts past ``max_new``, separated by one quiet epoch, each arm
    their own rebuild — the machine re-arms after recovering, it is not
    a one-shot latch — and the final plane drops nothing."""
    st = _seed_state(list(range(0, 100, 2)), cap=512)
    W, L = 254, 12
    plane = dix.from_state_device(st, n_levels=L, width=W)
    E, B = 5, 48
    kinds = np.full((E, B), sx.OP_CONTAINS, np.int32)
    keys = np.resize(np.arange(0, 100, 2), (E, B)).astype(np.int32)
    for e, lo in ((0, 1), (2, 101)):                     # fresh odd keys
        kinds[e, :] = sx.OP_INSERT
        keys[e, :] = np.arange(lo, lo + 2 * B, 2)
    ups = np.ones((E, B), bool)
    st2, plane2, _, _, ovf, _, _ = sx.run_serving(
        st, plane, jnp.asarray(kinds), jnp.asarray(keys),
        jnp.asarray(ups), max_new=16)
    ovf = np.asarray(ovf)
    assert ovf[0] == B - 16 and ovf[2] == B - 16         # both flagged
    assert ovf[1] == 0 and (ovf[3:] == 0).all()          # both rebuilt
    _assert_plane_equal(plane2, la.from_state(st2, min_levels=L, width=W))
    w_bot = int(plane2.widths[-1])
    final = set(np.asarray(plane2.keys)[-1][:w_bot].tolist())
    assert set(keys[0].tolist()) | set(keys[2].tolist()) <= final


def test_run_serving_burst_on_rebuild_epoch_absorbed():
    """A second burst landing on the rebuild epoch itself does NOT
    overflow: the epoch's ops run before its refresh, so the
    ``from_state_device`` rebuild already sees (and holds) the new
    keys — back-to-back bursts cost one overflow epoch, not two."""
    st = _seed_state(list(range(0, 100, 2)), cap=512)
    W, L = 254, 12
    plane = dix.from_state_device(st, n_levels=L, width=W)
    E, B = 3, 48
    kinds = np.full((E, B), sx.OP_CONTAINS, np.int32)
    keys = np.resize(np.arange(0, 100, 2), (E, B)).astype(np.int32)
    for e, lo in ((0, 1), (1, 101)):                     # consecutive
        kinds[e, :] = sx.OP_INSERT
        keys[e, :] = np.arange(lo, lo + 2 * B, 2)
    ups = np.ones((E, B), bool)
    st2, plane2, _, _, ovf, _, _ = sx.run_serving(
        st, plane, jnp.asarray(kinds), jnp.asarray(keys),
        jnp.asarray(ups), max_new=16)
    ovf = np.asarray(ovf)
    assert ovf[0] == B - 16
    assert (ovf[1:] == 0).all()                          # absorbed
    _assert_plane_equal(plane2, la.from_state(st2, min_levels=L, width=W))
    w_bot = int(plane2.widths[-1])
    final = set(np.asarray(plane2.keys)[-1][:w_bot].tolist())
    assert set(keys[0].tolist()) | set(keys[1].tolist()) <= final


def test_from_state_device_pads_small_states():
    """capacity < width: the plane pads out to the requested rectangle
    (serving reserves width for growth)."""
    pool = [4, 8, 15]
    st = _seed_state(pool, cap=64)
    plane = dix.from_state_device(st, n_levels=12, width=256)
    assert plane.keys.shape == (12, 256)
    _assert_plane_equal(plane, la.from_state(st, min_levels=12, width=256))
    st, _, _ = sx.run_ops(
        st, jnp.full((1,), sx.OP_INSERT, jnp.int32),
        jnp.asarray(np.asarray([6], np.int32)), jnp.ones((1,), bool))
    plane = dix.refresh_device(st, plane, max_new=8)
    _assert_plane_equal(plane, la.from_state(st, min_levels=12, width=256))


# ---------------------------------------------------------------------------
# row compaction and insert merge by sort, against the binary-search oracle
# ---------------------------------------------------------------------------

def _assemble_by_search(keys_sorted, rel_h, n_levels):
    """The plane's rows by the inverse prefix sum: ``_compact_take``'s
    binary search per output lane, then numpy gathers through it.
    Returns ``(keys, widths)``."""
    ks, h = np.asarray(keys_sorted), np.asarray(rel_h)
    width = ks.size
    h = np.where(ks != dix.PAD_KEY, h, -1)
    mask = h[None, :] >= (n_levels - 1 - np.arange(n_levels))[:, None]
    cs = np.cumsum(mask, axis=1).astype(np.int32)
    widths = cs[:, -1]
    take = np.stack([np.asarray(dix._compact_take(jnp.asarray(c), width))
                     for c in cs])
    live = np.arange(width)[None, :] < widths[:, None]
    keys = np.where(live, ks[take], dix.PAD_KEY)
    return keys, widths


@pytest.mark.parametrize("n,width,n_levels,heights", [
    (0, 64, 4, "geometric"),            # empty plane
    (256, 256, 6, "geometric"),         # full width
    (100, 128, 5, "top"),               # every key in the top row
    (211, 300, 7, "geometric"),         # width not a power of two
    (90, 128, 12, "low"),               # levels above the largest height
    (120, 160, 5, "high"),              # heights past the top saturate
    (0, 300, 1, "top"),                 # empty plane of one level
    (1, 64, 6, "top"),                  # one key, at full height
    (100, 128, 1, "geometric"),         # one level: the bottom row alone
])
def test_sorted_compaction_matches_search_oracle(n, width, n_levels,
                                                 heights):
    rng = np.random.default_rng(n + width)
    ks = np.full(width, dix.PAD_KEY, np.int32)
    ks[:n] = np.sort(rng.choice(10 ** 6, n, replace=False))
    h = {"geometric": np.minimum(rng.geometric(0.5, width) - 1,
                                 n_levels - 1),
         "top": np.full(width, n_levels - 1),
         "low": rng.integers(0, 3, width),
         "high": rng.integers(0, 2 * n_levels, width)}[heights]
    h = h.astype(np.int32)
    slots = rng.permutation(width).astype(np.int32)
    plane = jax.jit(dix._assemble_device, static_argnums=3)(
        jnp.asarray(ks), jnp.asarray(h), jnp.asarray(slots), n_levels)
    keys, widths = _assemble_by_search(ks, h, n_levels)
    np.testing.assert_array_equal(np.asarray(plane.keys), keys)
    np.testing.assert_array_equal(np.asarray(plane.widths), widths)
    np.testing.assert_array_equal(np.asarray(plane.heights),
                                  np.where(ks != dix.PAD_KEY, h, 0))
    np.testing.assert_array_equal(np.asarray(plane.slots)[:n], slots[:n])
    np.testing.assert_array_equal(np.asarray(plane.local_bot), ks)
    np.testing.assert_array_equal(np.asarray(plane.local_live),
                                  (ks != dix.PAD_KEY).astype(np.int32))
    assert int(plane.local_ok[0]) == 0
    assert int(widths[-1]) == n


def _slot_state(keys, heights, deleted, cap, max_level):
    """A state holding ``keys`` in slots ``2 ..`` with relative heights
    ``heights``, ``deleted`` marking slots dead: the fields the refresh
    reads (``key``, ``top``, ``zl``, ``deleted``, ``n_alloc``)."""
    st = sx.make(capacity=cap, max_level=max_level)
    n = len(keys)
    key = np.asarray(st.key).copy()
    top = np.asarray(st.top).copy()
    dead = np.zeros(cap, bool)
    key[2:2 + n] = keys
    top[2:2 + n] = int(st.zl) + np.asarray(heights)
    dead[2:2 + n] = deleted
    return st._replace(key=jnp.asarray(key), top=jnp.asarray(top),
                       deleted=jnp.asarray(dead),
                       n_alloc=jnp.int32(2 + n))


@pytest.mark.parametrize("case", [
    "new_fill_max_new",       # n_new == max_new
    "truncated",              # n_old + n_new > width
    "over_max_new",           # more inserts than max_new
    "all_survivors_deleted",
    "inserts_outside",        # below the first key and above the last
])
def test_refresh_merge_by_sort(case):
    """One incremental refresh with inserts against the host build of
    the keys it can hold: the merged bottom row, every row above it
    (each nested in the row below), the live slots, and the overflow
    count."""
    cap, L, W, kk = 128, 6, 48, 8
    rng = np.random.default_rng(len(case))
    n_old = {"truncated": 44}.get(case, 30)
    old = rng.choice(np.arange(100, 1000, 2), n_old, replace=False)
    new = rng.choice(np.arange(101, 1000, 2),
                     {"over_max_new": 12}.get(case, kk), replace=False)
    if case == "inserts_outside":
        new = np.asarray([3, 50, 99, 1001, 5000, 2 ** 31 - 2])
    dead = np.zeros(n_old, bool)
    if case == "all_survivors_deleted":
        dead[:] = True
    elif case != "truncated":
        dead[rng.choice(n_old, 3, replace=False)] = True
    keys = np.concatenate([old, new]).astype(np.int32)
    hts = rng.integers(0, L, keys.size).astype(np.int32)
    st0 = _slot_state(old, hts[:n_old], np.zeros(n_old, bool), cap, L)
    plane0 = dix.from_state_device(st0, n_levels=L, width=W)
    st1 = _slot_state(keys, hts, np.concatenate(
        [dead, np.zeros(new.size, bool)]), cap, L)
    plane, ovf = dix.refresh_device(st1, plane0, max_new=kk,
                                    return_overflow=True)

    # what the plane can hold: the survivors and the smallest max_new
    # inserts, the smallest `W` of those
    kept_new = np.argsort(new)[:kk]
    held = np.concatenate([np.nonzero(~dead)[0], n_old + kept_new])
    held = held[np.argsort(keys[held])]
    expect_ovf = (new.size - kept_new.size) + max(held.size - W, 0)
    held = held[:W]
    assert int(ovf) == expect_ovf
    _assert_plane_equal(plane, la.build(keys[held], hts[held],
                                        min_levels=L, width=W), case)
    w_bot = int(plane.widths[-1])
    bottom = np.asarray(plane.keys)[-1]
    np.testing.assert_array_equal(np.asarray(plane.slots)[:w_bot],
                                  2 + held)
    rows = np.asarray(plane.keys)
    widths = np.asarray(plane.widths)
    for r in range(L - 1):
        assert np.isin(rows[r, :widths[r]],
                       rows[r + 1, :widths[r + 1]]).all(), r

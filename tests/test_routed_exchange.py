"""Routed query exchange (DESIGN.md §5.6) — the pieces that do not need
a multi-device runtime.

The differential battery (routed vs replicate-and-mask vs replicated on
1/2/4-way forced host meshes: duplicate boundary keys, forced capacity
spill, single-owner batches, empty-plane routing, mass-weighted
re-split epochs with boundary-table monotonicity) runs in the
``benchmarks/sharded_search_probe.py --parity`` subprocess, invoked by
``tests/test_sharded_search.py::test_sharded_parity_on_host_mesh``.
Here: the static capacity math, the mass-split boundary solver's
invariants, the no-mesh fallback contract of the routed entry point
(including its stats convention), and the split-argument validation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import device_index as dix
from repro.core import splaylist as sx
from repro.kernels.ref import splay_search_ref
from repro.kernels import splay_search as ssk
from repro.parallel import sharding as shd

from conftest import seed_splay_state as _seed_state  # noqa: E402
from repro.launch.mesh import make_auto_mesh           # noqa: E402


def _plane(pool, n_levels=12, width=252, cap=512):
    return (dix.from_state_device(_seed_state(pool, cap=cap),
                                  n_levels=n_levels, width=width))


# ---------------------------------------------------------------------------
# route_capacity: the static per-shard receive block
# ---------------------------------------------------------------------------

def test_route_capacity_default_math():
    # ceil(q/S) * slack, clamped into [1, q]
    assert ssk.route_capacity(4096, 4) == int(np.ceil(1024 * 1.5))
    assert ssk.route_capacity(4096, 4, slack=1.0) == 1024
    assert ssk.route_capacity(10, 4, slack=1.5) == 5       # ceil(3*1.5)
    assert ssk.route_capacity(3, 4) == 2                   # <= q=3
    assert ssk.route_capacity(1, 4, slack=100.0) == 1      # clamp to q
    # slack >= S caps at q exactly: the controller's spill-proof rung
    assert ssk.route_capacity(4096, 4, slack=4.0) == 4096
    assert ssk.route_capacity(4097, 4, slack=4.0) == 4097


def test_route_capacity_rejects_nonsense():
    with pytest.raises(ValueError, match="nq"):
        ssk.route_capacity(0, 4)
    with pytest.raises(ValueError, match="nq"):
        ssk.route_capacity(-8, 4)
    with pytest.raises(ValueError, match="n_shards"):
        ssk.route_capacity(64, 0)
    with pytest.raises(ValueError, match="slack"):
        ssk.route_capacity(64, 4, slack=0.99)
    with pytest.raises(ValueError, match="slack"):
        ssk.route_capacity(64, 4, slack=0.0)
    # exactly 1.0 is the legal floor
    assert ssk.route_capacity(64, 4, slack=1.0) == 16


def test_route_args_rejected_at_every_entry_point():
    """slack < 1 / capacity < 1 raise host-side everywhere — the search
    wrapper and the epoch/serving wrappers, mesh or no mesh — instead
    of silently jitting a spill-guaranteed exchange."""
    plane = _plane(list(range(0, 80, 2)), width=124, cap=256)
    qs = jnp.zeros((8,), jnp.int32)
    with pytest.raises(ValueError, match="slack"):
        ssk.splay_search_sharded(plane, qs, slack=0.5)
    with pytest.raises(ValueError, match="capacity"):
        ssk.splay_search_sharded(plane, qs, capacity=0)
    st = _seed_state(list(range(0, 80, 2)), cap=256)
    args = (st, plane, jnp.zeros((8,), jnp.int32),
            jnp.zeros((8,), jnp.int32), jnp.ones((8,), bool))
    with pytest.raises(ValueError, match="route_slack"):
        sx.run_epoch(*args, aggregate=True, plane_search=True,
                     route_slack=0.5)
    with pytest.raises(ValueError, match="route_capacity"):
        sx.run_epoch(*args, aggregate=True, plane_search=True,
                     route_capacity=0)
    eargs = (st, plane, jnp.zeros((1, 8), jnp.int32),
             jnp.zeros((1, 8), jnp.int32), jnp.ones((1, 8), bool))
    with pytest.raises(ValueError, match="route_slack"):
        sx.run_serving(*eargs, aggregate=True, plane_search=True,
                       route_slack=0.999)
    with pytest.raises(ValueError, match="route_capacity"):
        sx.run_serving(*eargs, aggregate=True, plane_search=True,
                       route_capacity=-1)


# ---------------------------------------------------------------------------
# mass_split_bounds: monotone, feasible, quantile-placed
# ---------------------------------------------------------------------------

def _check_bounds(b, total, S, lane_cap):
    b = np.asarray(b)
    assert b.shape == (S + 1,)
    assert b[0] == 0 and b[-1] == total
    assert (np.diff(b) >= 0).all(), b
    assert (np.diff(b) <= lane_cap).all(), b


def test_mass_bounds_uniform_mass_equals_equal_lanes():
    # uniform mass over a 75%-occupied row: quantiles ARE the equal-
    # count boundaries
    W, S = 64, 4
    total = 48
    mass = np.zeros(W, np.int32)
    mass[:total] = 1
    b = shd.mass_split_bounds(jnp.cumsum(jnp.asarray(mass)),
                              jnp.int32(total), S, W // S)
    _check_bounds(b, total, S, W // S)
    np.testing.assert_array_equal(np.asarray(b), [0, 12, 24, 36, 48])


def test_mass_bounds_skewed_mass_moves_boundaries():
    # all mass on the first 4 keys: each of them anchors a shard, the
    # cold tail spreads over the remainder under the lane cap
    W, S = 64, 4
    total = 40
    mass = np.ones(W, np.int32)
    mass[total:] = 0
    mass[:4] = 1000
    b = np.asarray(shd.mass_split_bounds(
        jnp.cumsum(jnp.asarray(mass)), jnp.int32(total), S, W // S))
    _check_bounds(b, total, S, W // S)
    # the first boundary lands inside the hot head (mass quantile), the
    # later ones are pushed right by the lane-cap feasibility window so
    # the 36-key cold tail still fits in the remaining shards
    assert b[1] <= 4, b
    np.testing.assert_array_equal(b[2:], [8, 24, 40])


def test_mass_bounds_full_plane_forces_equal_lanes():
    # total == S * lane_cap leaves zero freedom: every shard must hold
    # exactly lane_cap keys whatever the mass says
    W, S = 64, 4
    mass = np.ones(W, np.int32)
    mass[:3] = 10 ** 6
    b = shd.mass_split_bounds(jnp.cumsum(jnp.asarray(mass)),
                              jnp.int32(W), S, W // S)
    np.testing.assert_array_equal(np.asarray(b), [0, 16, 32, 48, 64])


def test_mass_bounds_empty_and_single_shard():
    b0 = shd.mass_split_bounds(jnp.zeros((16,), jnp.int32),
                               jnp.int32(0), 4, 4)
    np.testing.assert_array_equal(np.asarray(b0), [0, 0, 0, 0, 0])
    b1 = shd.mass_split_bounds(jnp.cumsum(jnp.ones((16,), jnp.int32)),
                               jnp.int32(16), 1, 16)
    np.testing.assert_array_equal(np.asarray(b1), [0, 16])


def test_mass_bounds_capacity_clamp_keeps_feasibility():
    # one key owns ~all mass -> the quantile solver would put every
    # boundary at rank <=1, but then the LAST shard would need more
    # than lane_cap keys; the feasibility window must push boundaries
    # right so every segment still fits
    W, S = 32, 4
    total = 32
    mass = np.ones(W, np.int32)
    mass[0] = 10 ** 6
    b = np.asarray(shd.mass_split_bounds(
        jnp.cumsum(jnp.asarray(mass)), jnp.int32(total), S, W // S))
    _check_bounds(b, total, S, W // S)


# ---------------------------------------------------------------------------
# wrapper fallbacks and stats conventions (single-device runtime)
# ---------------------------------------------------------------------------

def test_routed_no_mesh_fallback_with_stats():
    """Without a resolvable mesh the routed entry point IS the
    replicated search; the stats report zero spill and one pseudo-shard
    owning the whole batch."""
    plane = _plane(list(range(0, 160, 2)))
    qs = jnp.asarray(np.asarray([0, 1, 2, 77, 158, 300, -4], np.int32))
    f, r, lv, stats = ssk.splay_search_sharded(plane, qs,
                                               return_stats=True)
    out_r = ssk.splay_search(plane, qs, sharded=False)
    for a, b in zip((f, r, lv), out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(stats.spill) == 0
    np.testing.assert_array_equal(np.asarray(stats.occupancy),
                                  [qs.shape[0]])


def test_routed_empty_queries_with_stats():
    plane = _plane(list(range(0, 40, 2)), width=124, cap=128)
    f, r, lv, stats = ssk.splay_search_sharded(
        plane, jnp.zeros((0,), jnp.int32), return_stats=True)
    assert f.shape == r.shape == lv.shape == (0,)
    assert int(stats.spill) == 0


def test_refresh_split_validation():
    plane = _plane([2, 4, 6], n_levels=6, width=62, cap=64)
    st = _seed_state([2, 4, 6], cap=64)
    with pytest.raises(ValueError, match="split"):
        dix.refresh_device_sharded(st, plane, split="massive")
    # no mesh: both valid split modes fall back to the replicated
    # refresh (which packs) with the sharded return convention
    p1, ov1 = dix.refresh_device_sharded(st, plane, split="mass")
    p2, ov2 = dix.refresh_device_sharded(st, plane, split="lanes")
    assert int(ov1) == int(ov2) == 0
    np.testing.assert_array_equal(np.asarray(p1.keys),
                                  np.asarray(p2.keys))


def test_gather_path_rejects_segmented_plane():
    """A concrete mass-split (segmented) plane has interior pad runs in
    its bottom row — silently wrong under the single-device binary
    descent, so the gather-to-replicated path must refuse it."""
    plane = _plane(list(range(0, 80, 2)), n_levels=6, width=124, cap=256)
    keys = np.asarray(plane.keys).copy()
    keys[-1, 10:20] = ssk.PAD_KEY                 # interior pad run
    seg = plane._replace(keys=jnp.asarray(keys))
    qs = jnp.asarray(np.asarray([0, 4, 30], np.int32))
    with pytest.raises(ValueError, match="segmented"):
        ssk.splay_search(seg, qs, sharded=False)
    with pytest.raises(ValueError, match="segmented"):
        ssk.splay_select(seg, jnp.asarray([0, 1], jnp.int32),
                         sharded=False)
    # packed planes (trailing pads only) pass untouched, answering like
    # the oracle
    out = ssk.splay_search(plane, qs, sharded=False)
    assert bool(out[0][0])
    for got, want in zip(out, splay_search_ref(plane.keys, qs)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_meshless_paths_reject_mass_and_segmented():
    """The replicated epoch/refresh fallbacks must refuse what they
    cannot represent: split='mass' (needs the sharded refresh) and a
    concrete segmented plane (packed-row invariants would silently
    corrupt/answer wrongly)."""
    st = _seed_state(list(range(0, 80, 2)), cap=256)
    plane = dix.from_state_device(st, n_levels=12, width=126)
    B = 8
    args = (st, plane, jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), jnp.int32), jnp.ones((B,), bool))
    with pytest.raises(ValueError, match="mass"):
        sx.run_epoch(*args, split="mass")
    with pytest.raises(ValueError, match="mass"):
        sx.run_serving(st, plane, jnp.zeros((1, B), jnp.int32),
                       jnp.zeros((1, B), jnp.int32),
                       jnp.ones((1, B), bool), split="mass")
    keys = np.asarray(plane.keys).copy()
    keys[-1, 10:20] = dix.PAD_KEY                 # fake segmentation
    seg = plane._replace(keys=jnp.asarray(keys))
    with pytest.raises(ValueError, match="segmented"):
        sx.run_epoch(st, seg, *args[2:])
    with pytest.raises(ValueError, match="segmented"):
        dix.refresh_device_sharded(st, seg)       # meshless fallback
    assert dix.plane_is_segmented(seg)
    assert not dix.plane_is_segmented(plane)


_needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs a multi-device runtime (forced host mesh)")


@_needs_mesh
def test_overflow_and_spill_same_epoch_sharded():
    """Sustained pressure on BOTH signals at once: an alive count past
    the plane width (persistent overflow — a rebuild at the same shape
    cannot fix it) while a deliberately tiny route_capacity spills
    queries every epoch.  The state machine must keep reporting both
    without corrupting either loop."""
    from repro.parallel import sharding as shd
    n_dev = len(jax.devices())
    pool = list(range(0, 320, 2))                        # 160 alive
    W = 128 if 128 % n_dev == 0 else n_dev * (128 // n_dev)
    st = _seed_state(pool, cap=512)
    plane = dix.from_state_device(st, n_levels=12, width=W)
    mesh = make_auto_mesh((1, n_dev), ("data", "model"))
    plane_s = shd.shard_index_plane(plane, mesh)
    E, B = 3, 32
    keys = np.resize(np.asarray(pool, np.int32), (E, B))
    out = sx.run_serving(
        st, plane_s, jnp.zeros((E, B), jnp.int32), jnp.asarray(keys),
        jnp.ones((E, B), bool), aggregate=True, plane_search=True,
        mesh=mesh, route_capacity=1)
    ovf, spl, occ = (np.asarray(out[4]), np.asarray(out[5]),
                     np.asarray(out[6]))
    # overflow persists at exactly the unrepresentable excess ...
    assert (ovf == len(pool) - W).all(), ovf
    # ... and the same epochs ALSO spill on the routed exchange
    assert (spl > 0).all(), spl
    assert occ.shape == (E, n_dev) and (occ.sum(1) == B).all()
    # spilled-or-not, the answers come from the (stale-by-overflow)
    # plane exactly: compare against the meshless loop on the same
    # replicated plane, which shares the staleness
    ref = sx.run_serving(
        st, plane, jnp.zeros((E, B), jnp.int32), jnp.asarray(keys),
        jnp.ones((E, B), bool), aggregate=True, plane_search=True)
    np.testing.assert_array_equal(np.asarray(out[2]),
                                  np.asarray(ref[2]))
    np.testing.assert_array_equal(np.asarray(out[3]),
                                  np.asarray(ref[3]))


@_needs_mesh
def test_rebuild_while_segmented_plane():
    """The near-full pressure trigger fires while the carried plane is
    mass-split (segmented): the full_rebuild branch must consume the
    segmented plane, emit the packed layout, and the following mass
    refresh re-split it — answers bit-identical to the replicated loop
    throughout (DESIGN.md §5.4 + §5.6)."""
    from repro.parallel import sharding as shd
    n_dev = len(jax.devices())
    W = 128 if 128 % n_dev == 0 else n_dev * (128 // n_dev)
    pool = list(range(0, 2 * (W - 8), 2))                # W-8 alive
    st = _seed_state(pool, cap=2 * W)
    plane = dix.from_state_device(st, n_levels=12, width=W)
    mesh = make_auto_mesh((1, n_dev), ("data", "model"))
    plane_s = shd.shard_index_plane(plane, mesh)
    E, B = 4, 32                                         # size+B > W
    rng = np.random.default_rng(0)
    keys = rng.choice(pool, (E, B)).astype(np.int32)
    out = sx.run_serving(
        st, plane_s, jnp.zeros((E, B), jnp.int32), jnp.asarray(keys),
        jnp.ones((E, B), bool), aggregate=True, plane_search=True,
        mesh=mesh, split="mass")
    ref = sx.run_serving(
        st, plane, jnp.zeros((E, B), jnp.int32), jnp.asarray(keys),
        jnp.ones((E, B), bool), aggregate=True, plane_search=True)
    assert not np.asarray(out[4]).any()                  # no overflow
    np.testing.assert_array_equal(np.asarray(out[2]),
                                  np.asarray(ref[2]))
    np.testing.assert_array_equal(np.asarray(out[3]),
                                  np.asarray(ref[3]))
    # the final carried plane holds every alive key exactly once
    bot = np.asarray(out[1].keys)[-1]
    alive = bot[bot != ssk.PAD_KEY]
    np.testing.assert_array_equal(np.sort(alive), np.asarray(pool))


def test_run_epoch_returns_spill_and_occupancy():
    """The epoch tuple carries the routed exchange's feedback: a spill
    counter and the per-shard occupancy vector, both zero (and the
    occupancy a single pseudo-shard) everywhere off the routed sharded
    plane-search path."""
    st = _seed_state(list(range(0, 80, 2)), cap=256)
    plane = dix.from_state_device(st, n_levels=12, width=126)
    B = 16
    out = sx.run_epoch(st, plane, jnp.zeros((B,), jnp.int32),
                       jnp.zeros((B,), jnp.int32), jnp.ones((B,), bool),
                       aggregate=True, plane_search=True)
    assert len(out) == 7
    assert out[5].shape == () and int(out[5]) == 0
    assert out[6].shape == (1,) and int(out[6][0]) == 0
    sout = sx.run_serving(st, plane, jnp.zeros((2, B), jnp.int32),
                          jnp.zeros((2, B), jnp.int32),
                          jnp.ones((2, B), bool),
                          aggregate=True, plane_search=True)
    assert len(sout) == 7
    assert sout[5].shape == (2,) and sout[6].shape == (2, 1)
    assert int(np.asarray(sout[6]).sum()) == 0

"""Sharded-search probe: parity + race, in a forced host-device mesh.

Self-contained subprocess target (it forces
``--xla_force_host_platform_device_count`` *before* importing jax, which
cannot be done from an already-initialized parent process), mirroring
``sharded_refresh_probe.py``:

  python benchmarks/sharded_search_probe.py --parity           # differential
  python benchmarks/sharded_search_probe.py --bench --routed   # JSON to stdout

``--parity`` drives the width-sharded search
(``kernels.splay_search.splay_search_sharded``, DESIGN.md §5.5–§5.6) on
1/2/4-way meshes and asserts bit-identity with the replicated tiered
search on every (found, rank, level_found) triple, across: the full
wrapper-dispatch seam (sharded plane + routed exchange vs sharded plane
+ replicate-and-mask vs gather-to-replicated vs fully replicated
plane), queries whose rank window straddles a shard boundary, boundary
keys themselves (including duplicated boundary keys in one batch),
misses in cross-boundary gaps, forced capacity overflow (the spill
path), a batch owned entirely by one shard, transient-empty rows, the
all-empty plane, membership-churn epoch streams interleaving sharded
refresh + sharded search, mass-weighted re-split epochs (segmented
planes; boundary-table monotonicity checked each epoch), the §5.8
resident sub-planes (``RouteStats.assembled`` pinned 0 on the resident
mass steady state and > 0 on stale planes), and the end-to-end sharded serving loop
(``splaylist.run_serving(plane_search=True, mesh=...)``, lanes and mass
splits).  Exits nonzero on any mismatch.

``--bench`` races the sharded search on a 1x4 host mesh against the
replicated tiered search and the gather-to-replicated dispatch over
Zipf query batches and prints one JSON object (consumed by
``benchmarks/kernels_bench.py`` into the ``search_sharded`` entry of
``BENCH_kernels.json``).  With ``--routed`` the primary sharded
measurement is the routed all_to_all exchange (the default execution)
and the payload gains the §5.6 routing-balance columns: spill
count/rate, per-shard occupancy after routing, a Gini coefficient
alongside ``routing_max_share``, the same columns after a
mass-weighted re-split, and the §5.8 assemble-overhead columns
(resident segmented descent vs the same plane with the residency bit
cleared, plus both ``assembled`` counters).  Host-mesh timings measure collective and
dispatch overhead, not accelerator scaling — the structural columns
(per-shard resident bytes, wire per batch, routing balance) are the
part that transfers to TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

N_DEV = 4
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        f"{_flags} --xla_force_host_platform_device_count={N_DEV}").strip()

import jax                                             # noqa: E402
import jax.numpy as jnp                                # noqa: E402
import numpy as np                                     # noqa: E402

from repro.core import device_index as dix             # noqa: E402
from repro.core import splaylist as sx                 # noqa: E402
from repro.kernels import ops as kops                  # noqa: E402
from repro.kernels import splay_search as ssk          # noqa: E402
from repro.parallel import sharding as shd             # noqa: E402
from repro.launch.mesh import make_auto_mesh           # noqa: E402

CMP_FIELDS = ("keys", "widths", "heights")


def _seed_state(pool, cap=512, ml=12):
    st = sx.make(capacity=cap, max_level=ml)
    st, _, _ = sx.run_ops(
        st, jnp.full((len(pool),), sx.OP_INSERT, jnp.int32),
        jnp.asarray(np.asarray(pool, np.int32)),
        jnp.ones((len(pool),), bool))
    return st


def _assert_triple(a, b, msg):
    for name, x, y in zip(("found", "rank", "level_found"), a, b):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=f"{msg} field={name}")


def _boundary_queries(plane, mesh, extra=()):
    """Queries concentrated on shard boundaries: every block-first
    bottom-row key TWICE (duplicate keys straddling a boundary must
    bucket to distinct exchange lanes of the same owner), its
    neighbours at ±1 (present keys and cross-boundary-gap misses),
    below-min/above-max, plus ``extra``."""
    bot = np.asarray(plane.keys)[-1]
    W = bot.shape[0]
    S = mesh.shape["model"]
    wl = W // S
    qs = []
    i32 = 2 ** 31 - 1
    for s in range(S):
        first = int(bot[s * wl])
        qs += [first, first, max(first - 1, -i32), min(first + 1, i32)]
    live = bot[bot != ssk.PAD_KEY]
    if live.size:
        qs += [int(live[0]) - 7, int(live[-1]) + 7]
    # the int32 extremes: INT32_MIN sits below even the -inf routing
    # sentinel, PAD_KEY is the pad sentinel itself — both must still
    # route to exactly one owner and match the replicated kernel
    qs += [-2 ** 31, -i32, i32 - 1, i32]
    qs += list(extra)
    return jnp.asarray(np.asarray(qs, np.int32))


def _search_all_ways(plane_r, plane_s, qs, mesh, spill_cap=None):
    """The wrapper-dispatch seam: sharded plane + routed exchange,
    sharded plane + replicate-and-mask, sharded plane + forced
    gather-to-replicated, fully replicated plane — all bit-identical.
    ``spill_cap`` additionally forces the routed path through the spill
    branch (capacity below the batch size) and checks it still
    matches."""
    out_re = ssk.splay_search(plane_r, qs, sharded=False)
    out_rt = ssk.splay_search_sharded(plane_s, qs, mesh=mesh,
                                      return_stats=True)
    out_mk = ssk.splay_search_sharded(plane_s, qs, mesh=mesh,
                                      routed=False)
    out_ga = ssk.splay_search(plane_s, qs, sharded=False)
    # every real query has exactly one owner; batch-padding fill lanes
    # are excluded from the exchange stats
    assert int(np.asarray(out_rt[3].occupancy).sum()) == qs.shape[0]
    _assert_triple(out_rt[:3], out_re, "routed-vs-replicated")
    _assert_triple(out_mk, out_re, "masked-vs-replicated")
    _assert_triple(out_ga, out_re, "gather-vs-replicated")
    # lane-packed shard planes carry no §5.8 residency bit: every
    # descent re-assembles its local sub-plane (counted per shard body)
    assert int(out_rt[3].assembled) > 0, int(out_rt[3].assembled)
    if spill_cap is not None:
        out_sp = ssk.splay_search_sharded(plane_s, qs, mesh=mesh,
                                          capacity=spill_cap,
                                          return_stats=True)
        _assert_triple(out_sp[:3], out_re, "forced-spill-vs-replicated")
        assert int(out_sp[3].spill) > 0, "forced spill did not trigger"
    return out_re


def _assert_bounds_monotone(plane, mesh, msg):
    """Boundary-table monotonicity: block-first keys of live blocks
    ascend (the suffix-min routing table is then exact)."""
    bot = np.asarray(plane.keys)[-1]
    S = mesh.shape["model"]
    wl = bot.shape[0] // S
    firsts = [int(bot[s * wl]) for s in range(S)
              if bot[s * wl] != ssk.PAD_KEY]
    assert firsts == sorted(firsts), f"{msg}: {firsts}"


def run_parity() -> None:
    W, L = 252, 12
    print(f"sharded search parity: mode={kops.exec_mode()}")
    rng0 = np.random.default_rng(0)

    for S in (1, 2, 4):
        mesh = make_auto_mesh((1, S), ("data", "model"))
        # skewed heights: the tall (hot) keys cluster at the low end of
        # the keyspace, so upper rows live almost entirely in shard 0's
        # key range — queries owned by later shards then carry rank
        # windows that straddle shard boundaries on the global plane
        pool = list(range(0, 320, 2))
        st = _seed_state(pool)
        pr = dix.from_state_device(st, n_levels=L, width=W)
        ps = shd.shard_index_plane(pr, mesh)
        qs = _boundary_queries(
            pr, mesh, extra=list(rng0.integers(-10, 340, 64)))
        _search_all_ways(pr, ps, qs, mesh, spill_cap=3)

        # a batch owned entirely by one shard: occupancy concentrates
        # S× above q/S, so the default capacity overflows and the whole
        # overflowing remainder must come back through the spill path.
        # Target the range of the last LIVE shard (trailing blocks can
        # be empty — their +INF "first key" owns nothing)
        bot = np.asarray(pr.keys)[-1]
        hi_key = int(bot[bot != ssk.PAD_KEY][-1])
        one_owner = jnp.asarray(
            rng0.integers(hi_key - 40, hi_key + 40, 64).astype(np.int32))
        out_re = ssk.splay_search(pr, one_owner, sharded=False)
        out_one = ssk.splay_search_sharded(ps, one_owner, mesh=mesh,
                                           return_stats=True)
        _assert_triple(out_one[:3], out_re, "single-owner batch")
        if S > 1:
            assert int(out_one[3].spill) > 0, \
                "single-owner batch should overflow ceil(q/S)*slack"
            assert int(np.asarray(out_one[3].occupancy).max()) >= 64

        # membership-churn epochs: sharded refresh feeding sharded
        # search, vs the replicated chain
        rng = np.random.default_rng(S)
        for epoch in range(6):
            kinds = rng.choice(
                [sx.OP_CONTAINS, sx.OP_INSERT, sx.OP_DELETE], 48,
                p=[.5, .3, .2]).astype(np.int32)
            ks = rng.integers(0, 340, 48).astype(np.int32)
            st, _, _ = sx.run_ops(st, jnp.asarray(kinds), jnp.asarray(ks),
                                  jnp.ones((48,), bool))
            pr, ovr = dix.refresh_device(st, pr, max_new=48,
                                         return_overflow=True)
            ps, ovs = dix.refresh_device_sharded(st, ps, max_new=48,
                                                 mesh=mesh)
            assert int(ovr) == int(ovs) == 0, (int(ovr), int(ovs))
            qs = _boundary_queries(
                pr, mesh, extra=list(rng.integers(-10, 360, 64)))
            _search_all_ways(pr, ps, qs, mesh)
        print(f"parity S={S}: dispatch seam + boundary windows + "
              f"forced spill + single-owner + 6 churn epochs OK")

    mesh = make_auto_mesh((1, 4), ("data", "model"))

    # mass-weighted re-split epochs (§5.6): hammer a hot set so the hit
    # counters skew, re-split every epoch, and check the segmented
    # plane answers bit-identically to the replicated kernel on the
    # packed plane — boundary table monotone after every re-split
    st = _seed_state(list(range(0, 320, 2)))
    rngm = np.random.default_rng(11)
    hot = np.arange(0, 20, 2, dtype=np.int32)
    pr = dix.from_state_device(st, n_levels=L, width=W)
    ps = shd.shard_index_plane(pr, mesh)
    for epoch in range(4):
        ks = np.where(rngm.random(48) < 0.7, rngm.choice(hot, 48),
                      rngm.integers(0, 340, 48)).astype(np.int32)
        kinds = rngm.choice(
            [sx.OP_CONTAINS, sx.OP_INSERT, sx.OP_DELETE], 48,
            p=[.7, .2, .1]).astype(np.int32)
        st, _, _ = sx.run_ops(st, jnp.asarray(kinds), jnp.asarray(ks),
                              jnp.ones((48,), bool))
        pr, _ = dix.refresh_device(st, pr, max_new=48,
                                   return_overflow=True)
        ps, ovm = dix.refresh_device_sharded(st, ps, max_new=48,
                                             mesh=mesh, split="mass")
        assert int(ovm) == 0
        _assert_bounds_monotone(ps, mesh, f"mass epoch {epoch}")
        qs = _boundary_queries(
            pr, mesh, extra=list(rngm.integers(-10, 360, 64)))
        out_re = ssk.splay_search(pr, qs, sharded=False)
        out_rt = ssk.splay_search_sharded(ps, qs, mesh=mesh,
                                          return_stats=True)
        out_mk = ssk.splay_search_sharded(ps, qs, mesh=mesh,
                                          routed=False)
        out_sp = ssk.splay_search_sharded(ps, qs, mesh=mesh, capacity=3,
                                          return_stats=True)
        _assert_triple(out_rt[:3], out_re, "mass routed")
        _assert_triple(out_mk, out_re, "mass masked")
        _assert_triple(out_sp[:3], out_re, "mass forced-spill")
        # §5.8 residency: the mass-split blocks ARE the local sub-plane
        # — the steady-state routed descent must not re-assemble (the
        # counted probe for the "no _assemble_device" acceptance)
        assert int(out_rt[3].assembled) == 0, int(out_rt[3].assembled)
    # a lanes refresh repacks the segmented plane bit-identically
    pl_back, _ = dix.refresh_device_sharded(st, ps, max_new=48,
                                            mesh=mesh)
    for f in CMP_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(pl_back, f)), np.asarray(getattr(pr, f)),
            err_msg=f"mass->lanes repack field={f}")
    print("parity mass re-split epochs + boundary monotonicity + "
          "repack OK")

    # transient-empty rows: few live keys -> upper rows empty; then the
    # all-empty plane (delete everything), then refill out of it.  The
    # all-empty plane also exercises empty-plane *routing*: every query
    # owner-routes to shard 0's [-inf, +inf) range
    st = _seed_state(list(range(0, 40, 2)), cap=128)
    pr = dix.from_state_device(st, n_levels=L, width=124)
    ps = shd.shard_index_plane(pr, mesh)
    qs = _boundary_queries(pr, mesh, extra=[0, 1, 38, 39, 40, 1000])
    _search_all_ways(pr, ps, qs, mesh)
    dels = np.asarray(list(range(0, 40, 2)), np.int32)
    st, _, _ = sx.run_ops(
        st, jnp.full((len(dels),), sx.OP_DELETE, jnp.int32),
        jnp.asarray(dels), jnp.ones((len(dels),), bool))
    pr, _ = dix.refresh_device(st, pr, max_new=64, return_overflow=True)
    ps, _ = dix.refresh_device_sharded(st, ps, max_new=64, mesh=mesh)
    out_e = ssk.splay_search_sharded(ps, qs, mesh=mesh,
                                     return_stats=True)
    _assert_triple(out_e[:3], ssk.splay_search(pr, qs, sharded=False),
                   "empty-plane routed")
    assert int(np.asarray(out_e[3].occupancy)[1:].sum()) == 0, \
        "empty-plane queries must all route to shard 0"
    _search_all_ways(pr, ps, qs, mesh)            # all-empty plane
    st, _, _ = sx.run_ops(
        st, jnp.full((3,), sx.OP_INSERT, jnp.int32),
        jnp.asarray(np.asarray([5, 7, 11], np.int32)),
        jnp.ones((3,), bool))
    pr, _ = dix.refresh_device(st, pr, max_new=64, return_overflow=True)
    ps, _ = dix.refresh_device_sharded(st, ps, max_new=64, mesh=mesh)
    _search_all_ways(pr, ps, qs, mesh)            # refill
    print("parity transient-empty / all-empty(+routing) / refill OK")

    # indivisible width: documented gather-to-replicated fallback
    st = _seed_state([2, 4, 6], cap=64)
    p0 = dix.from_state_device(st, n_levels=6, width=62)
    qs = jnp.asarray(np.asarray([1, 2, 3, 6, 9], np.int32))
    out_f = ssk.splay_search_sharded(p0, qs, mesh=mesh)
    out_r = ssk.splay_search(p0, qs, sharded=False)
    _assert_triple(out_f, out_r, "indivisible-width fallback")
    print("parity indivisible-width fallback OK")

    # end-to-end sharded serving: contains-only epochs answered from
    # the routed sharded plane search, refreshed by the sharded refresh
    # — under both split rules
    pool = list(range(0, 300, 2))
    st = _seed_state(pool)
    pr = dix.from_state_device(st, n_levels=L, width=W)
    ps = shd.shard_index_plane(pr, mesh)
    rng = np.random.default_rng(9)
    E, B = 5, 64
    kinds = np.zeros((E, B), np.int32)
    keys = rng.choice(np.arange(0, 320), (E, B)).astype(np.int32)
    ups = rng.random((E, B)) < 0.6
    out_r = sx.run_serving(st, pr, jnp.asarray(kinds), jnp.asarray(keys),
                           jnp.asarray(ups), aggregate=True,
                           plane_search=True)
    # route_slack sized for the layout: the 150-key plane leaves the
    # 4th lane block empty, so the batch spreads over 3 live shards
    # (expected occupancy B/3, not B/4) — slack 2.5 keeps the loop
    # spill-free, which the [5] output asserts below
    out_s = sx.run_serving(st, ps, jnp.asarray(kinds), jnp.asarray(keys),
                           jnp.asarray(ups), aggregate=True,
                           plane_search=True, mesh=mesh,
                           route_slack=2.5)
    for i, name in ((2, "results"), (3, "path_len"), (4, "overflow")):
        np.testing.assert_array_equal(
            np.asarray(out_s[i]), np.asarray(out_r[i]),
            err_msg=f"serving field={name}")
    for f in CMP_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(out_s[1], f)),
            np.asarray(getattr(out_r[1], f)),
            err_msg=f"serving plane field={f}")
    # the plane answers equal the state-walk answers in steady state
    out_w = sx.run_serving(st, pr, jnp.asarray(kinds), jnp.asarray(keys),
                           jnp.asarray(ups), aggregate=True)
    np.testing.assert_array_equal(np.asarray(out_s[2]),
                                  np.asarray(out_w[2]),
                                  err_msg="plane answers vs state walk")
    # mass-split serving: answers identical, plane segmented
    out_m = sx.run_serving(st, ps, jnp.asarray(kinds), jnp.asarray(keys),
                           jnp.asarray(ups), aggregate=True,
                           plane_search=True, mesh=mesh, split="mass")
    for i, name in ((2, "results"), (3, "path_len"), (4, "overflow")):
        np.testing.assert_array_equal(
            np.asarray(out_m[i]), np.asarray(out_r[i]),
            err_msg=f"mass serving field={name}")
    _assert_bounds_monotone(out_m[1], mesh, "mass serving plane")
    # forced-spill serving: a tiny route capacity must not change any
    # answer, only the spill counter
    out_c = sx.run_serving(st, ps, jnp.asarray(kinds), jnp.asarray(keys),
                           jnp.asarray(ups), aggregate=True,
                           plane_search=True, mesh=mesh,
                           route_capacity=2)
    np.testing.assert_array_equal(np.asarray(out_c[2]),
                                  np.asarray(out_r[2]),
                                  err_msg="forced-spill serving results")
    assert int(np.asarray(out_c[5]).sum()) > 0
    assert int(np.asarray(out_s[5]).sum()) == 0
    print("parity end-to-end sharded serving (lanes + mass + "
          "forced-spill) OK")
    print("PARITY OK")


def _gini(shares: np.ndarray) -> float:
    """Gini coefficient of the per-shard load vector (0 = perfectly
    balanced, ->1 = all load on one shard)."""
    x = np.sort(np.asarray(shares, np.float64))
    n = x.size
    tot = x.sum()
    if tot == 0 or n < 2:
        return 0.0
    return float((2 * np.arange(1, n + 1) - n - 1).dot(x)
                 / (n * tot))


def _synth_state(keys: np.ndarray, rel_h: np.ndarray,
                 selfhits: np.ndarray, capacity: int,
                 max_level: int = 8) -> sx.SplayState:
    """SplayState with exactly the fields the refresh/mass-split paths
    read (key, top, selfhits, deleted, zl, n_alloc) populated at
    benchmark widths — same synthesis as ``kernels_bench`` (the probe
    stays a standalone subprocess by design)."""
    st = sx.make(capacity, max_level=max_level)
    n = len(keys)
    key = np.full((capacity,), sx.POS_INF_32, np.int32)
    key[0] = sx.NEG_INF_32
    key[2:2 + n] = keys
    top = np.zeros((capacity,), np.int32)
    top[2:2 + n] = rel_h
    top[0] = top[1] = max_level
    sh = np.ones((capacity,), np.int32)
    sh[2:2 + n] = selfhits
    return st._replace(
        key=jnp.asarray(key), top=jnp.asarray(top),
        selfhits=jnp.asarray(sh), zl=jnp.array(0, jnp.int32),
        n_alloc=jnp.array(n + 2, jnp.int32))


def run_bench(width: int = 4096, nq: int = 4096, reps: int = 4,
              routed: bool = True) -> dict:
    """Zipf query batches against a plane at 75% occupancy (serving
    planes keep insert headroom — and a *full* plane leaves the
    mass-weighted split zero freedom: every shard must then hold
    exactly W/S keys), sharded (1x4 host mesh) vs replicated tiered vs
    gather-to-replicated dispatch; asserts bit-identity on every output
    triple.  With ``routed`` the primary sharded measurement is the
    all_to_all exchange and the §5.6 routing-balance/mass-split columns
    are emitted."""
    from repro.core import workload as wl
    mesh = make_auto_mesh((1, N_DEV), ("data", "model"))
    n_levels = 8
    n_keys = int(width * 0.75)
    keys, heights, qs = wl.zipf_level_fixture(n_keys, 1.0, nq, seed=3)
    # the access counters the mass split reads: an independent warmup
    # sample of the SAME fixture (same keys/ranks, fresh Zipf draws) —
    # what the serving loop's hit counters converge to
    _, _, warm = wl.zipf_level_fixture(n_keys, 1.0, 4 * nq, seed=3)
    counts = np.zeros(n_keys, np.int64)
    np.add.at(counts, np.searchsorted(keys, warm), 1)
    st_syn = _synth_state(keys, heights,
                          np.minimum(counts, 2 ** 20).astype(np.int32),
                          capacity=n_keys + 8, max_level=n_levels)
    plane = dix.from_state_device(st_syn, n_levels=n_levels, width=width)
    plane_s = shd.shard_index_plane(plane, mesh)
    qsj = jnp.asarray(qs)
    qb = 256

    # the mass-split plane up front so every variant can be timed
    # *interleaved* (round-robin, min per variant): wall clock on this
    # class of shared host drifts by multiples between back-to-back
    # runs, and sequential min-of-reps bakes that drift into the ratios
    pm_s, ovm = dix.refresh_device_sharded(st_syn, plane_s, max_new=64,
                                           mesh=mesh, split="mass")
    assert int(ovm) == 0
    # the same segmented plane with the §5.8 residency bit cleared:
    # every descent is forced back through the per-batch local-sub-plane
    # re-assembly (the pre-§5.8 routed-body behaviour), isolating the
    # assemble overhead on otherwise identical data
    pm_stale = pm_s._replace(local_ok=jnp.zeros_like(pm_s.local_ok))

    variants = {
        "routed_mass": lambda: ssk.splay_search_sharded(
            pm_s, qsj, query_block=qb, mesh=mesh),
        "routed_mass_stale": lambda: ssk.splay_search_sharded(
            pm_stale, qsj, query_block=qb, mesh=mesh),
        "routed_lane": lambda: ssk.splay_search_sharded(
            plane_s, qsj, query_block=qb, mesh=mesh),
        "masked": lambda: ssk.splay_search_sharded(
            plane_s, qsj, query_block=qb, mesh=mesh, routed=False),
        "replicated": lambda: ssk.splay_search(
            plane, qsj, query_block=qb, sharded=False),
        "gather": lambda: ssk.splay_search(
            plane_s, qsj, query_block=qb, sharded=False),
    }
    for fn in variants.values():                       # compile
        fn()[0].block_until_ready()
    best = {k: float("inf") for k in variants}
    for _ in range(max(reps, 8)):
        for k, fn in variants.items():
            t0 = time.perf_counter()
            fn()[0].block_until_ready()
            best[k] = min(best[k], time.perf_counter() - t0)
    out_re = variants["replicated"]()
    _assert_triple(variants["routed_mass"](), out_re,
                   "bench routed-mass-vs-replicated")
    _assert_triple(variants["routed_mass_stale"](), out_re,
                   "bench routed-forced-assemble-vs-replicated")
    _assert_triple(variants["routed_lane"](), out_re,
                   "bench routed-lane-vs-replicated")
    _assert_triple(variants["masked"](), out_re,
                   "bench masked-vs-replicated")
    _assert_triple(variants["gather"](), out_re,
                   "bench gather-vs-replicated")
    # the primary "sharded" measurement: the shipped default for skewed
    # serving — routed exchange on the mass-split plane (with --routed);
    # the legacy masked trace otherwise
    t_shard = best["routed_mass"] if routed else best["masked"]
    t_repl = best["replicated"]

    # routing balance: share of the batch owned by each shard (host-side
    # mirror of the in-body suffix-min searchsorted routing)
    bot = np.asarray(plane.keys)[-1]
    wl_ = width // N_DEV
    bounds = np.asarray([bot[s * wl_] for s in range(N_DEV)], np.int64)
    bounds[0] = -(2 ** 31) + 1
    bounds = np.minimum.accumulate(bounds[::-1])[::-1]
    owner = np.searchsorted(bounds, np.asarray(qs), side="right") - 1
    hist = np.bincount(owner, minlength=N_DEV)
    itemsize = 4
    capacity = ssk.route_capacity(nq, N_DEV)
    out = {
        "mode": "zipf_search", "exec_mode": kops.exec_mode(),
        "width": width, "n_levels": n_levels,
        "shards": N_DEV, "lanes_per_shard": wl_, "nq": nq,
        "occupied_lanes": n_keys,
        "query_block": qb, "routed": bool(routed),
        "us_per_query_sharded": t_shard / nq * 1e6,
        "us_per_query_routed_lane_split": best["routed_lane"] / nq * 1e6,
        "us_per_query_masked": best["masked"] / nq * 1e6,
        "us_per_query_replicated": t_repl / nq * 1e6,
        "us_per_query_gather_dispatch": best["gather"] / nq * 1e6,
        "ratio_sharded_over_replicated": t_shard / t_repl,
        "ratio_masked_over_replicated": best["masked"] / t_repl,
        # what each shard holds/wires vs the replicated whole: resident
        # plane state shrinks [L, W] -> [L, W/S]; the routed exchange
        # wires two all_to_alls of [S, cap] + O(S^2) scalars per batch
        # (O(nq*slack), W-independent), and each shard's kernel batch
        # shrinks nq -> capacity (the masked trace keeps nq per shard)
        "replicated_resident_bytes": n_levels * width * itemsize,
        "sharded_resident_bytes_per_shard":
            n_levels * wl_ * itemsize,
        "psum_bytes_per_batch": 3 * nq * itemsize,
        # forward all_to_all ships [S, cap] int32 queries (1 word per
        # lane), the inverse ships [4, S, cap] answers+validity (4
        # words per lane)
        "exchange_bytes_per_batch":
            (1 + 4) * N_DEV * capacity * itemsize if routed else 0,
        "kernel_batch_per_shard": capacity if routed else nq,
        "routing_per_shard": [int(x) for x in hist],
        "routing_max_share": float(hist.max() / nq),
        "routing_gini": _gini(hist),
        "bit_identical": True,
    }
    if not routed:
        return out

    # routed-exchange stats straight from the shard bodies
    _, _, _, stats = ssk.splay_search_sharded(
        plane_s, qsj, query_block=qb, mesh=mesh, return_stats=True)
    occ = np.asarray(stats.occupancy)
    out.update({
        "route_capacity": capacity,
        "route_slack": ssk.DEFAULT_ROUTE_SLACK,
        "spill_count": int(stats.spill),
        "spill_rate": float(int(stats.spill) / nq),
        "occupancy_per_shard": [int(x) for x in occ],
    })

    # the mass-split (§5.6) routing balance on the same batch — the
    # primary timing above already ran on this segmented plane
    _, _, _, mstats = ssk.splay_search_sharded(
        pm_s, qsj, query_block=qb, mesh=mesh, return_stats=True)
    mocc = np.asarray(mstats.occupancy)
    out.update({
        "us_per_query_mass_split": best["routed_mass"] / nq * 1e6,
        "occupancy_per_shard_mass": [int(x) for x in mocc],
        "routing_max_share_mass": float(mocc.max() / max(mocc.sum(), 1)),
        "routing_gini_mass": _gini(mocc),
        "spill_count_mass": int(mstats.spill),
        "spill_rate_mass": float(int(mstats.spill) / nq),
    })

    # §5.8 assemble-overhead columns: the resident segmented descent vs
    # the forced per-batch re-assembly on the same plane/batch; the
    # assembled counters are the structural (noise-free) half of the gate
    _, _, _, sstats = ssk.splay_search_sharded(
        pm_stale, qsj, query_block=qb, mesh=mesh, return_stats=True)
    out.update({
        "us_per_query_routed_resident": best["routed_mass"] / nq * 1e6,
        "us_per_query_routed_forced_assemble":
            best["routed_mass_stale"] / nq * 1e6,
        "assemble_overhead_ratio":
            best["routed_mass_stale"] / best["routed_mass"],
        "assembled_resident": int(mstats.assembled),
        "assembled_forced": int(sstats.assembled),
    })
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--parity", action="store_true")
    ap.add_argument("--bench", action="store_true")
    ap.add_argument("--routed", action="store_true",
                    help="bench the routed all_to_all exchange as the "
                         "primary sharded path (+ §5.6 balance columns)")
    ap.add_argument("--width", type=int, default=4096)
    ap.add_argument("--nq", type=int, default=4096)
    args = ap.parse_args(argv)
    if args.parity:
        run_parity()
    if args.bench:
        print(json.dumps(run_bench(width=args.width, nq=args.nq,
                                   routed=args.routed)))
    if not (args.parity or args.bench):
        ap.error("pass --parity and/or --bench")


if __name__ == "__main__":
    main()

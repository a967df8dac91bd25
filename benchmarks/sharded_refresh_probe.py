"""Sharded-refresh probe: parity + race, in a forced host-device mesh.

Self-contained subprocess target (it forces
``--xla_force_host_platform_device_count`` *before* importing jax, which
cannot be done from an already-initialized parent process):

  python benchmarks/sharded_refresh_probe.py --parity   # differential
  python benchmarks/sharded_refresh_probe.py --bench    # JSON to stdout

``--parity`` drives insert/delete/height-churn operation streams through
``device_index.refresh_device_sharded`` on 1/2/4-way meshes and asserts
the plane is bit-identical to the replicated ``refresh_device`` chain on
(keys, widths, heights) every epoch — plus deep,
uneven rows on 2/4 shards, and the transient-empty, rebuild-staleness,
overflow-burst, and indivisible-width-fallback edges.  Exits nonzero on
any mismatch.

``--bench`` races the sharded refresh on a 1x4 host mesh against the
replicated refresh over membership-changing epoch streams and prints one
JSON object (consumed by ``benchmarks/kernels_bench.py`` into the
``refresh_sharded`` entry of ``BENCH_kernels.json``).  Host-mesh timings
measure the collective/composition overhead, not accelerator scaling —
the structural columns (shards, per-shard lanes, collective count) are
the part that transfers to TPU.
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
from repro.core import level_arrays as la              # noqa: E402
from repro.core import splaylist as sx                 # noqa: E402
from repro.kernels import ops as kops                  # noqa: E402
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


def _assert_equal(ps, pr, msg):
    for f in CMP_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(ps, f)), np.asarray(getattr(pr, f)),
            err_msg=f"{msg} field={f}")
    # slots: specified on live lanes only (pad lanes differ by design)
    w_bot = int(np.asarray(pr.widths)[-1])
    np.testing.assert_array_equal(
        np.asarray(ps.slots)[:w_bot], np.asarray(pr.slots)[:w_bot],
        err_msg=f"{msg} field=slots[:w_bot]")


def _built_state(keys, heights, deleted, cap, ml):
    """A state holding ``keys`` in slots ``2..`` with the given relative
    heights and delete flags: the fields the refresh reads, set
    directly, so a case can place every row's members by hand."""
    n = len(keys)
    key = np.full((cap,), sx.POS_INF_32, np.int32)
    key[0] = sx.NEG_INF_32
    key[2:2 + n] = keys
    top = np.zeros((cap,), np.int32)
    top[2:2 + n] = heights
    top[0] = top[1] = ml
    dead = np.zeros((cap,), bool)
    dead[2:2 + n] = deleted
    return sx.make(cap, max_level=ml)._replace(
        key=jnp.asarray(key), top=jnp.asarray(top),
        zl=jnp.array(0, jnp.int32),
        n_alloc=jnp.array(n + 2, jnp.int32), deleted=jnp.asarray(dead))


def _deep_uneven_epochs(L):
    """(keys, heights, deleted) of one growing slot set, three epochs,
    for ``W = 64``.  First deep, uneven rows: heights up to ``L - 1``;
    row 0 held by the keys at bottom columns 17, 20 and 25, all in one
    shard at 2 and at 4 shards; the last of 4 shards holds no key, and
    shard 2 of 4 no member of rows 0 to ``L - 5``.  Then every height
    capped at 2, which empties rows 0 to ``L - 4`` (the middle rows
    among them) while deletes and inserts churn the bottom row; then
    deep again over the churned row."""
    rng = np.random.default_rng(16)
    keys = np.arange(0, 400, 10, dtype=np.int32)     # 40 keys, cols 0..39
    h = rng.integers(0, 3, len(keys)).astype(np.int32)
    h[[17, 20, 25]] = L - 1
    h[[2, 5]] = L - 4
    h[[35, 38]] = L - 5
    dead = np.zeros(len(keys), bool)
    yield keys, h, dead
    dead = np.concatenate([dead, np.zeros(3, bool)])
    dead[10:15] = True
    keys = np.concatenate([keys, np.asarray([5, 215, 395], np.int32)])
    yield keys, np.minimum(np.concatenate([h, [2, 2, 2]]), 2), dead
    h = rng.integers(0, 2, len(keys)).astype(np.int32)
    h[[30, 31, 33]] = L - 1
    h[[0, 40]] = L - 2
    yield keys, h, dead


def _deep_uneven_case(S, L=8, W=64, cap=96):
    """The ``_deep_uneven_epochs`` stream through both refreshes on an
    ``S``-way mesh: bit-identical planes every epoch."""
    mesh = make_auto_mesh((1, S), ("data", "model"))
    epochs = list(_deep_uneven_epochs(L))
    keys, _, dead = epochs[0]
    st = _built_state(keys, np.zeros(len(keys), np.int32), dead, cap, L)
    pr = dix.from_state_device(st, n_levels=L, width=W)
    ps = shd.shard_index_plane(pr, mesh)
    for i, (keys, h, dead) in enumerate(epochs):
        st = _built_state(keys, h, dead, cap, L)
        pr, ovr = dix.refresh_device(st, pr, max_new=16,
                                     return_overflow=True)
        ps, ovs = dix.refresh_device_sharded(st, ps, max_new=16,
                                             mesh=mesh)
        assert int(ovr) == int(ovs) == 0, (int(ovr), int(ovs))
        _assert_equal(ps, pr, f"deep-uneven S={S} epoch={i}")
        yield np.asarray(pr.widths)


def _mixed_stream(rng, pool, n_ops):
    kinds, ks, ups = [], [], []
    for _ in range(n_ops):
        x = rng.random()
        if x < 0.55:
            kinds.append(sx.OP_CONTAINS); ks.append(rng.choice(pool))
        elif x < 0.75:
            kinds.append(sx.OP_INSERT); ks.append(int(rng.integers(0, 400)))
        else:
            kinds.append(sx.OP_DELETE)
            ks.append(int(rng.choice(pool + list(range(1, 400, 7)))))
        ups.append(bool(rng.random() < 0.7))
    return (jnp.asarray(np.asarray(kinds, np.int32)),
            jnp.asarray(np.asarray(ks, np.int32)),
            jnp.asarray(np.asarray(ups)))


def run_parity() -> None:
    W, L = 252, 12
    print(f"sharded refresh parity: mode={kops.exec_mode()}")
    pool = list(range(0, 160, 2))
    for S in (1, 2, 4):
        mesh = make_auto_mesh((1, S), ("data", "model"))
        st = _seed_state(pool)
        pr = dix.from_state_device(st, n_levels=L, width=W)
        ps = shd.shard_index_plane(pr, mesh)
        rng = np.random.default_rng(S)
        for epoch in range(8):
            kinds, ks, ups = _mixed_stream(rng, pool, 64)
            st, _, _ = sx.run_ops(st, kinds, ks, ups)
            pr, ovr = dix.refresh_device(st, pr, max_new=64,
                                         return_overflow=True)
            ps, ovs = dix.refresh_device_sharded(st, ps, max_new=64,
                                                 mesh=mesh)
            assert int(ovr) == int(ovs) == 0, (int(ovr), int(ovs))
            _assert_equal(ps, pr, f"S={S} epoch={epoch}")
        print(f"parity S={S}: 8 mixed epochs OK "
              f"(w_bot={int(np.asarray(pr.widths)[-1])})")

    for S in (2, 4):
        widths = list(_deep_uneven_case(S, L=8))
        # row 0 holds its three keys, then a middle row runs empty
        assert widths[0][0] == 3 and widths[1][3] == 0, widths
        print(f"parity deep-uneven S={S} OK "
              f"(widths {[w.tolist() for w in widths]})")

    mesh = make_auto_mesh((1, 4), ("data", "model"))

    # overflow burst: both paths count the same drops, identical planes
    st = _seed_state(list(range(0, 100, 2)))
    pr = dix.from_state_device(st, n_levels=L, width=W)
    ps = shd.shard_index_plane(pr, mesh)
    burst = np.arange(1, 81, 2, dtype=np.int32)
    st, _, _ = sx.run_ops(
        st, jnp.full((len(burst),), sx.OP_INSERT, jnp.int32),
        jnp.asarray(burst), jnp.ones((len(burst),), bool))
    pr, ovr = dix.refresh_device(st, pr, max_new=16, return_overflow=True)
    ps, ovs = dix.refresh_device_sharded(st, ps, max_new=16, mesh=mesh)
    assert int(ovr) == int(ovs) == len(burst) - 16, (int(ovr), int(ovs))
    for f in CMP_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(ps, f)), np.asarray(getattr(pr, f)),
            err_msg=f"overflow field={f}")
    print("parity overflow burst OK")

    # delete-heavy epoch -> splaylist.rebuild compacts slots -> both
    # paths must take the scatter fallback and agree
    st = _seed_state(list(range(0, 100, 2)))
    pr = dix.from_state_device(st, n_levels=L, width=W)
    ps = shd.shard_index_plane(pr, mesh)
    dels = np.asarray(list(range(0, 80, 2)), np.int32)
    st, _, _ = sx.run_ops(
        st, jnp.full((len(dels),), sx.OP_DELETE, jnp.int32),
        jnp.asarray(dels), jnp.ones((len(dels),), bool))
    pr, _ = dix.refresh_device(st, pr, max_new=64, return_overflow=True)
    ps, _ = dix.refresh_device_sharded(st, ps, max_new=64, mesh=mesh)
    _assert_equal(ps, pr, "rebuild-staleness")
    np.testing.assert_array_equal(
        np.asarray(ps.keys), la.from_state(st, min_levels=L, width=W).keys)
    print("parity rebuild-staleness OK")

    # transient empty (delete all) and refill out of it
    st = _seed_state(list(range(0, 40, 2)), cap=128)
    pr = dix.from_state_device(st, n_levels=L, width=124)
    ps = shd.shard_index_plane(pr, mesh)
    d = np.asarray(list(range(0, 40, 2)), np.int32)
    st, _, _ = sx.run_ops(
        st, jnp.full((len(d),), sx.OP_DELETE, jnp.int32),
        jnp.asarray(d), jnp.ones((len(d),), bool))
    pr, _ = dix.refresh_device(st, pr, max_new=64, return_overflow=True)
    ps, _ = dix.refresh_device_sharded(st, ps, max_new=64, mesh=mesh)
    for f in CMP_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(ps, f)), np.asarray(getattr(pr, f)),
            err_msg=f"transient-empty field={f}")
    st, _, _ = sx.run_ops(
        st, jnp.full((3,), sx.OP_INSERT, jnp.int32),
        jnp.asarray(np.asarray([5, 7, 11], np.int32)),
        jnp.ones((3,), bool))
    pr, _ = dix.refresh_device(st, pr, max_new=64, return_overflow=True)
    ps, _ = dix.refresh_device_sharded(st, ps, max_new=64, mesh=mesh)
    _assert_equal(ps, pr, "refill")
    print("parity transient-empty OK")

    # the search wrapper accepts the width-sharded plane directly
    # (auto-dispatching to the sharded search, DESIGN.md §5.5; the
    # dedicated battery lives in sharded_search_probe.py)
    from repro.kernels import ops, ref
    qs = jnp.asarray(np.asarray(
        list(range(0, 60, 2)) + [999, 5, 7, 11], np.int32))
    f_s, r_s, l_s = ops.splay_search(ps, qs)
    f_0, r_0, l_0 = ref.splay_search_ref(
        jnp.asarray(np.asarray(pr.keys)), qs)
    np.testing.assert_array_equal(np.asarray(f_s), np.asarray(f_0))
    np.testing.assert_array_equal(np.asarray(r_s), np.asarray(r_0))
    np.testing.assert_array_equal(np.asarray(l_s), np.asarray(l_0))
    print("parity sharded-plane search OK")

    # indivisible width: documented replicated fallback
    st = _seed_state([2, 4, 6], cap=64)
    p0 = dix.from_state_device(st, n_levels=6, width=62)
    out, _ = dix.refresh_device_sharded(st, p0, max_new=8, mesh=mesh)
    np.testing.assert_array_equal(
        np.asarray(out.keys),
        np.asarray(dix.refresh_device(st, p0, max_new=8).keys))
    print("parity indivisible-width fallback OK")
    print("PARITY OK")


def _time_min(fn, reps: int) -> float:
    fn()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run_bench(width: int = 4096, churn: int = 64, epochs: int = 4,
              reps: int = 4) -> dict:
    """Membership-changing epoch stream, sharded (1x4 host mesh) vs
    replicated refresh; asserts bit-identity on the final plane."""
    mesh = make_auto_mesh((1, N_DEV), ("data", "model"))
    rng = np.random.default_rng(7)
    n_levels, hmax = 6, 5
    n0 = int(width * 0.9)
    capacity = n0 + epochs * churn + 16
    space = rng.permutation(20 * width).astype(np.int32)
    slot_keys = space[:n0].copy()
    deleted = np.zeros(n0, bool)
    states = []
    for _ in range(epochs + 1):
        if states and churn:
            live = np.nonzero(~deleted)[0]
            deleted[rng.choice(live, churn, replace=False)] = True
            fresh = space[len(slot_keys):len(slot_keys) + churn]
            slot_keys = np.concatenate([slot_keys, fresh])
            deleted = np.concatenate([deleted, np.zeros(churn, bool)])
        h = rng.integers(0, hmax + 1, len(slot_keys)).astype(np.int32)
        states.append(_built_state(slot_keys, h, deleted, capacity, 8))

    p0 = dix.from_state_device(states[0], n_levels=n_levels, width=width)
    p0s = shd.shard_index_plane(p0, mesh)
    max_new = max(2 * churn, 64)

    def repl_fold():
        p = p0
        for st in states[1:]:
            p, _ = dix.refresh_device(st, p, max_new=max_new,
                                      return_overflow=True)
        p.keys.block_until_ready()
        return p

    def shard_fold():
        p = p0s
        for st in states[1:]:
            p, _ = dix.refresh_device_sharded(st, p, max_new=max_new,
                                              mesh=mesh)
        p.keys.block_until_ready()
        return p

    t_repl = _time_min(repl_fold, reps) / epochs
    t_shard = _time_min(shard_fold, reps) / epochs
    fr, fs = repl_fold(), shard_fold()
    for f in CMP_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(fs, f)), np.asarray(getattr(fr, f)),
            err_msg=f"bench parity field={f}")
    itemsize = 4
    return {
        "mode": "membership", "exec_mode": kops.exec_mode(),
        "width": width, "n_levels": n_levels,
        "shards": N_DEV, "lanes_per_shard": width // N_DEV,
        "churn_per_epoch": churn, "epochs": epochs,
        "us_per_epoch_replicated": t_repl * 1e6,
        "us_per_epoch_sharded": t_shard * 1e6,
        "epochs_per_sec_replicated": 1.0 / t_repl,
        "epochs_per_sec_sharded": 1.0 / t_shard,
        "ratio_sharded_over_replicated": t_shard / t_repl,
        # what each shard touches vs the replicated whole: the heavy
        # [L, W] compaction shrinks to [L, W/S]; the exchanged segments
        # are the bottom row only
        "replicated_lane_bytes": n_levels * width * itemsize,
        "sharded_lane_bytes_per_shard":
            n_levels * (width // N_DEV) * itemsize,
        "exchanged_bytes_per_shard":
            3 * (width // N_DEV + max_new) * N_DEV * itemsize,
        "bit_identical": True,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--parity", action="store_true")
    ap.add_argument("--bench", action="store_true")
    ap.add_argument("--width", type=int, default=4096)
    args = ap.parse_args(argv)
    if args.parity:
        run_parity()
    if args.bench:
        print(json.dumps(run_bench(width=args.width)))
    if not (args.parity or args.bench):
        ap.error("pass --parity and/or --bench")


if __name__ == "__main__":
    main()

"""Ordered-operation probe: the DESIGN.md §5.10 kernel suite
(predecessor/successor, rank/select, range_count/range_scan, top_k) on
the replicated and the routed mass-split sharded plane.

Self-contained subprocess target (forces
``--xla_force_host_platform_device_count`` *before* importing jax),
mirroring ``drift_probe.py``/``serving_probe.py``:

  python benchmarks/ordered_search_probe.py --parity   # CI gate battery
  python benchmarks/ordered_search_probe.py --bench    # JSON to stdout

``--parity`` asserts every ordered op bit-identical across the host
oracle (numpy on the sorted live set), the meshless device plane, and
the width-sharded plane on a forced 1x4 host mesh under BOTH boundary
splits (equal-lane and mass-weighted) — including ranges whose
endpoints sit exactly on shard boundary keys, ranges straddling
adjacent owners, int32-extreme endpoints, `select` past the live
count, and the `range_scan` truncation contract (capacity cuts are
counted, never silent).  Exits nonzero on any violation; prints
``ORDERED PARITY OK``.

``--bench`` times `range_scan` (the compound op: one batched descent
for the rank pair + the bottom-row slice gather) replicated vs sharded
and prints one JSON object with the bytes-touched race against the
naive full-gather model (ship the whole [W] bottom row per query and
filter on host) — consumed by ``benchmarks/kernels_bench.py`` into the
``search_ordered`` entry of ``BENCH_kernels.json``.
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

PAD, NEG = ssk.PAD_KEY, ssk.NEG_INF_KEY


def _seed_state(keys, cap, L):
    st = sx.make(capacity=cap, max_level=L)
    st, _, _ = sx.run_ops(
        st, jnp.full((len(keys),), sx.OP_INSERT, jnp.int32),
        jnp.asarray(np.asarray(keys, np.int32)),
        jnp.ones((len(keys),), bool))
    return st


class _Oracle:
    """numpy ordered-op oracle over the sorted live key set."""

    def __init__(self, live):
        self.live = np.asarray(live, np.int64)
        self.n = len(self.live)

    def rank(self, q):
        return int(np.searchsorted(self.live, q, side="right"))

    def pred(self, q):
        i = self.rank(q) - 1
        return (int(self.live[i]), i) if i >= 0 else (NEG, -1)

    def succ(self, q):
        i = int(np.searchsorted(self.live, q, side="left"))
        return (int(self.live[i]), i) if i < self.n else (PAD, self.n)

    def select(self, r):
        return int(self.live[r]) if 0 <= r < self.n else PAD

    def count(self, lo, hi):
        if lo > hi:
            return 0
        return int(np.searchsorted(self.live, hi, "right")
                   - np.searchsorted(self.live, lo, "left"))

    def scan(self, lo, hi, cap):
        mem = self.live[(self.live >= lo) & (self.live <= hi)]
        c = len(mem)
        row = np.full(cap, PAD, np.int64)
        row[:min(c, cap)] = mem[:cap]
        return row, c, max(c - cap, 0)


def _assert_ordered_suite(plane, oracle, qs, sel_ranks, lo, hi, hits, k,
                          tag, ref=None):
    """Run every ordered op on ``plane``; check against the numpy
    oracle, and (when ``ref`` is given) bit-compare against the
    replicated plane's outputs.  Returns the output bundle."""
    out = {
        "rank": np.asarray(kops.splay_rank(plane, jnp.asarray(qs))),
        "pred": tuple(np.asarray(a) for a in
                      kops.splay_predecessor(plane, jnp.asarray(qs))),
        "succ": tuple(np.asarray(a) for a in
                      kops.splay_successor(plane, jnp.asarray(qs))),
        "select": np.asarray(kops.splay_select(
            plane, jnp.asarray(sel_ranks))),
        "count": np.asarray(kops.splay_range_count(
            plane, jnp.asarray(lo), jnp.asarray(hi))),
        "scan": tuple(np.asarray(a) for a in kops.splay_range_scan(
            plane, jnp.asarray(lo), jnp.asarray(hi), max_range=8)),
        "topk": tuple(np.asarray(a) for a in kops.splay_top_k(
            plane, jnp.asarray(hits), k)),
    }
    np.testing.assert_array_equal(
        out["rank"], [oracle.rank(q) for q in qs],
        err_msg=f"{tag}: rank")
    exp = [oracle.pred(q) for q in qs]
    np.testing.assert_array_equal(out["pred"][0], [e[0] for e in exp],
                                  err_msg=f"{tag}: pred keys")
    np.testing.assert_array_equal(out["pred"][1], [e[1] for e in exp],
                                  err_msg=f"{tag}: pred ranks")
    exp = [oracle.succ(q) for q in qs]
    np.testing.assert_array_equal(out["succ"][0], [e[0] for e in exp],
                                  err_msg=f"{tag}: succ keys")
    np.testing.assert_array_equal(out["succ"][1], [e[1] for e in exp],
                                  err_msg=f"{tag}: succ ranks")
    np.testing.assert_array_equal(
        out["select"], [oracle.select(r) for r in sel_ranks],
        err_msg=f"{tag}: select")
    np.testing.assert_array_equal(
        out["count"], [oracle.count(l, h) for l, h in zip(lo, hi)],
        err_msg=f"{tag}: range_count")
    for i, (l, h) in enumerate(zip(lo, hi)):
        row, c, tr = oracle.scan(l, h, 8)
        np.testing.assert_array_equal(out["scan"][0][i], row,
                                      err_msg=f"{tag}: scan row {i}")
        assert int(out["scan"][1][i]) == c, f"{tag}: scan count {i}"
        assert int(out["scan"][2][i]) == tr, \
            f"{tag}: scan truncation {i} (must be counted, not dropped)"
    if ref is not None:
        for op in out:
            a = out[op] if isinstance(out[op], tuple) else (out[op],)
            b = ref[op] if isinstance(ref[op], tuple) else (ref[op],)
            for x, y in zip(a, b):
                np.testing.assert_array_equal(
                    x, y, err_msg=f"{tag}: {op} != replicated")
    return out


def run_parity(width=512, n_levels=16, seed=0) -> None:
    assert len(jax.devices()) >= N_DEV, \
        f"forced host mesh absent: {len(jax.devices())} device(s)"
    print(f"ordered parity: w={width} L={n_levels} shards={N_DEV} "
          f"mode={kops.exec_mode()}")
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.integers(0, 5000, 300)).astype(np.int32)
    st = _seed_state(keys, 1024, n_levels)
    plane = dix.from_state_device(st, n_levels=n_levels, width=width)
    live = np.sort(keys)
    oracle = _Oracle(live)
    total = len(live)
    hits = np.asarray(st.selfhits)

    # queries: members, near-misses, int32 extremes, past-the-end
    qs = np.concatenate([
        keys[:24], keys[:24] + 1, keys[-4:] - 1,
        [-2 ** 31, NEG, NEG + 1, 0, 5001, 2 ** 31 - 2, 2 ** 31 - 1],
    ]).astype(np.int32)
    sel_ranks = np.asarray(
        [-5, -1, 0, 1, total // 2, total - 1, total, total + 7, 10 ** 6],
        np.int32)
    # ranges: wide, empty, inverted, single-key, off-population, and the
    # int32-extreme corners
    lo = np.asarray([0, 100, live[10], live[10], 6000, 50,
                     2 ** 31 - 1, -2 ** 31], np.int32)
    hi = np.asarray([5000, 99, live[40], live[10], 7000, 2 ** 31 - 1,
                     2 ** 31 - 1, 2 ** 31 - 1], np.int32)

    ref = _assert_ordered_suite(plane, oracle, qs, sel_ranks, lo, hi,
                                hits, 10, "replicated")
    # replicated top_k vs oracle: descending hit mass, ties by rank
    slot_of = {int(k): i for i, k in enumerate(np.asarray(st.key))}
    lane_hits = np.array([hits[slot_of[int(k)]] for k in live])
    order = np.lexsort((np.arange(total), -lane_hits))[:10]
    np.testing.assert_array_equal(ref["topk"][0], live[order])
    np.testing.assert_array_equal(ref["topk"][1], lane_hits[order])
    np.testing.assert_array_equal(ref["topk"][2], order)
    print(f"  replicated == host oracle ({len(qs)} queries, "
          f"{len(lo)} ranges, {total} live keys)")

    mesh = make_auto_mesh((1, N_DEV), ("data", "model"))
    pl_s = shd.shard_index_plane(plane, mesh)
    for split in ("lanes", "mass"):
        ps, ovf = dix.refresh_device_sharded(st, pl_s, mesh=mesh,
                                             split=split)
        assert int(ovf) == 0, f"{split}: refresh overflow"
        # boundary-exact + straddling ranges from the *actual* shard
        # boundary keys of this split's plane
        bot = np.asarray(ps.keys)[n_levels - 1]
        wl = width // N_DEV
        bkeys = [int(bot[s * wl]) for s in range(1, N_DEV)
                 if int(bot[s * wl]) != PAD]
        blo = np.asarray(
            [b for b in bkeys] + [b - 1 for b in bkeys]
            + [bkeys[0], 0], np.int32)
        bhi = np.asarray(
            [b for b in bkeys] + [b + 1 for b in bkeys]
            + [bkeys[-1], 5000], np.int32)
        tag = f"sharded-{split}"
        _assert_ordered_suite(ps, oracle, qs, sel_ranks, lo, hi,
                              hits, 10, tag, ref=ref)
        _assert_ordered_suite(
            ps, oracle, np.asarray(bkeys, np.int32),
            sel_ranks, blo, bhi, hits, 10, tag + "-boundary")
        print(f"  {tag}: suite == replicated == oracle "
              f"({len(bkeys)} boundary keys straddled)")
    print("ORDERED PARITY OK")


def run_bench(width=2048, nq=2048, max_range=64, reps=3,
              seed=0) -> dict:
    assert len(jax.devices()) >= N_DEV
    n_levels = 14
    rng = np.random.default_rng(seed)
    n_keys = int(width * 0.75)
    keys = rng.choice(np.arange(0, width * 4, dtype=np.int32),
                      n_keys, replace=False)
    st = _seed_state(keys, width + 2, n_levels)
    plane = dix.from_state_device(st, n_levels=n_levels, width=width)
    live = np.sort(keys)

    # hot-Zipf range anchors (the serving shape: most scans hit a few
    # hot id neighborhoods); spans are drawn in *rank* space — member
    # counts up to 4*max_range regardless of key sparsity — so a
    # majority of scans exercise the counted-truncation path
    zipf = np.minimum(rng.zipf(1.4, nq) - 1, len(live) - 1)
    lo = live[zipf].astype(np.int32)
    span = rng.integers(1, 4 * max_range, nq)
    hi = live[np.minimum(zipf + span, len(live) - 1)].astype(np.int32)

    mesh = make_auto_mesh((1, N_DEV), ("data", "model"))
    pl_s = shd.shard_index_plane(plane, mesh)
    pl_s, ovf = dix.refresh_device_sharded(st, pl_s, mesh=mesh,
                                           split="mass")
    assert int(ovf) == 0

    def scan_repl():
        out = kops.splay_range_scan(plane, jnp.asarray(lo),
                                    jnp.asarray(hi), max_range)
        return jax.block_until_ready(out)

    def scan_shard():
        out = kops.splay_range_scan(pl_s, jnp.asarray(lo),
                                    jnp.asarray(hi), max_range)
        return jax.block_until_ready(out)

    def _time_min(fn):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    kr, cr, tr = scan_repl()                      # also warms the jit
    ks_, cs, ts = scan_shard()
    np.testing.assert_array_equal(np.asarray(kr), np.asarray(ks_))
    np.testing.assert_array_equal(np.asarray(cr), np.asarray(cs))
    np.testing.assert_array_equal(np.asarray(tr), np.asarray(ts))
    t_repl = _time_min(scan_repl)
    t_shard = _time_min(scan_shard)

    # bytes-touched race, per query (itemsize 4):
    #   naive full-gather: ship the whole [W] bottom row and filter on
    #     host — W*4 bytes regardless of the range population;
    #   ours: the rank-pair descent streams 2 rows per live level per
    #     query *block* of the doubled (lo++hi) batch, then gathers
    #     exactly max_range bottom-row lanes per query.
    itemsize = 4
    qb = 256
    live_levels = int((np.asarray(plane.widths) > 0).sum())
    q_blocks = max((2 * nq) // qb, 1)
    descent_bytes = q_blocks * live_levels * 2 * width * itemsize
    ours_per_query = descent_bytes / nq + max_range * itemsize
    naive_per_query = width * itemsize
    trunc = int(np.asarray(tr).astype(np.int64).sum())
    out = {
        "mode": "range_scan", "exec_mode": kops.exec_mode(),
        "width": width, "n_levels": n_levels, "live_levels": live_levels,
        "shards": N_DEV, "nq": nq, "max_range": max_range,
        "occupied_lanes": n_keys,
        "us_per_scan_replicated": t_repl / nq * 1e6,
        "us_per_scan_sharded": t_shard / nq * 1e6,
        "ratio_sharded_over_replicated": t_shard / t_repl,
        "bytes_per_query_ours": round(ours_per_query, 1),
        "bytes_per_query_naive_full_gather": naive_per_query,
        "bytes_ratio_ours_over_naive":
            round(ours_per_query / naive_per_query, 4),
        "scans_truncated": int((np.asarray(tr) > 0).sum()),
        "members_truncated": trunc,
        "bit_identical": True,
    }
    print(f"# range_scan: repl {out['us_per_scan_replicated']:.1f}us "
          f"shard {out['us_per_scan_sharded']:.1f}us "
          f"bytes ratio {out['bytes_ratio_ours_over_naive']:.3f} "
          f"truncated {out['scans_truncated']}/{nq}",
          file=sys.stderr)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--parity", action="store_true")
    ap.add_argument("--bench", action="store_true")
    ap.add_argument("--width", type=int, default=2048)
    ap.add_argument("--nq", type=int, default=2048)
    ap.add_argument("--max-range", type=int, default=64)
    args = ap.parse_args(argv)
    if args.parity:
        run_parity()
    if args.bench:
        print(json.dumps(run_bench(width=args.width, nq=args.nq,
                                   max_range=args.max_range)))
    if not (args.parity or args.bench):
        ap.error("pass --parity and/or --bench")


if __name__ == "__main__":
    main()

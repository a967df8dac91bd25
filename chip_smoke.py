#!/usr/bin/env python3
"""Smoke run of the splay index's main path on a TPU.

One process drives the index the way a user does, at the paper's size:

  1. load   — the n-r-x-y-s workload of Appendix C.3 (n = 10^5,
              r = 0.98, x-y = 90-10, s = 0.25, p = 0.01; the settings of
              ``benchmarks/general_workloads.py``): its ~9*10^4
              ``populate`` keys go in through ``splaylist.run_ops`` and
              ``device_index.from_state_device`` derives the
              ``L = 17``, ``W = 131072`` index plane on the device;
  2. serve  — ``splaylist.run_serving`` over mixed epochs of
              ``B = 1024`` ops (contains / insert / delete), then over
              read-only epochs answered by the compiled descent
              (``aggregate``, ``plane_search``, ``ordered``: contains,
              predecessor and prefix-range lanes);
  3. query  — ``kernels.ops.splay_search`` / ``splay_predecessor`` /
              ``splay_range_scan`` straight on the refreshed plane;
  4. check  — every answer against the host oracle
              ``core/ref_py.SplayList`` replaying the same ops (verdicts,
              predecessor keys, range counts and members, level of the
              first hit, final key set), and ``plane_check.audit_plane``.

``--four-chip`` runs only the width-sharded path instead: the loaded
plane is laid out over a ``(1, 4)`` mesh (``shard_index_plane``) and
served by the routed sharded search with the mass split; its answers
are compared with the replicated loop and with the oracle, and each
device must hold its ``W/4`` share of the plane.  It loads the same
workload at n = 2*10^4 (~1.8*10^4 keys) into the same ``C``/``W``: the
load is a chain of sequential inserts on one device (about 3 ms each
on a v5e), which the sharded path does not exercise.

The compile cache is ``JAX_COMPILATION_CACHE_DIR`` where that is set,
else ``<repo>/.jax_cache``.  Timings printed are of this one run: smoke
facts, not benchmark numbers.

Exit status: 0 only when every check passed, the last line of stdout
then being ``{"ok": true, "device": {...}}``; 2 when JAX finds no TPU
(or the kernels would not compile for it); 1 on any mismatch or error.
``--rehearse`` is the CPU rehearsal: tiny sizes, interpret-mode
kernels, no TPU required (set ``JAX_PLATFORMS=cpu``, and
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` with
``--four-chip``).

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chip     # a 2x2 v5e host
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

# (populate size n, state capacity, levels, plane width)
FULL = (100_000, 131_074, 17, 131_072)
FOUR = (20_000, 131_074, 17, 131_072)
TINY = (2_000, 4_098, 17, 4_096)
BATCH = 1024
MIX_EPOCHS = 4
READ_EPOCHS = 4
MAX_RANGE = 16
SEED = 21


class SmokeFailure(Exception):
    pass


def check(cond, what: str):
    if not cond:
        raise SmokeFailure(what)


def compile_meter(jax):
    """Backend compile seconds and persistent-cache hits/misses of this
    process, from JAX's own monitoring events."""
    stats = {"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            stats["compile_s"] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            stats["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            stats["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return stats


class Phase:
    """Wall seconds and compile seconds spent inside a ``with`` block."""

    def __init__(self, meter):
        self.meter = meter

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.meter["compile_s"]
        return self

    def __exit__(self, *exc):
        self.wall = time.perf_counter() - self.t0
        self.compile = self.meter["compile_s"] - self.c0


class Oracle:
    """``ref_py.SplayList`` plus the ordered answers read off its live
    key set (the oracle has no ordered operations of its own)."""

    def __init__(self, max_level: int):
        from repro.core.ref_py import SplayList
        self.sl = SplayList(max_level=max_level, p=0.01)

    def apply(self, kind: int, key: int, upd: bool) -> int:
        op = (self.sl.contains, self.sl.insert, self.sl.delete)[kind]
        return int(op(key, upd=upd))

    def live(self):
        return [n.key for n in self.sl.items() if not n.deleted]

    @staticmethod
    def answers(live, kinds, keys, neg_inf):
        """Expected run_serving results of read-only lanes: contains
        verdict, predecessor key, or prefix-range count."""
        members = set(live)
        out = []
        for kd, k in zip(kinds.tolist(), keys.tolist()):
            i = bisect.bisect_right(live, k)
            if kd == 0:
                out.append(int(k in members))
            elif kd == 3:
                out.append(live[i - 1] if i else neg_inf)
            else:
                out.append(i)
        return out


def read_batch(np, w, n: int, epochs: int, seed: int):
    """Read-only ``[epochs, BATCH]`` lanes: half contains, a quarter
    each predecessor and prefix range, over the workload's read keys
    plus absent and out-of-range keys."""
    rng = np.random.default_rng(seed)
    shape = (epochs, BATCH)
    keys = rng.choice(w.keys, shape)
    absent = rng.integers(-8, 2 * n + 8, shape)
    keys = np.where(rng.random(shape) < 0.2, absent, keys).astype(np.int32)
    kinds = rng.choice(np.asarray([0, 3, 4], np.int32), shape,
                       p=[0.5, 0.25, 0.25]).astype(np.int32)
    upd = rng.random(shape) < 0.01
    return kinds, keys, upd


def load(ctx, size):
    """Insert the workload's populate set and derive the plane."""
    np, jnp, sx, dix, wl = ctx["np"], ctx["jnp"], ctx["sx"], ctx["dix"], \
        ctx["wl"]
    n, capacity, levels, width = size
    w = wl.general_workload(n, 0.98, 0.9, 0.1, 0.25,
                            (MIX_EPOCHS + READ_EPOCHS) * BATCH, p=0.01,
                            seed=SEED)
    pop = w.populate
    with Phase(ctx["meter"]) as ph:
        st = sx.make(capacity=capacity, max_level=levels)
        st, res, _ = sx.run_ops(
            st, jnp.full(pop.shape, sx.OP_INSERT, jnp.int32),
            jnp.asarray(pop), jnp.ones(pop.shape, bool))
        plane = dix.from_state_device(st, n_levels=levels, width=width)
        plane.keys.block_until_ready()
    check((np.asarray(res) == 1).all(), "a populate insert was refused")
    oracle = Oracle(levels)
    for k in pop.tolist():
        oracle.apply(sx.OP_INSERT, k, True)
    live = oracle.live()
    check(np.asarray(plane.keys)[levels - 1, :len(live)].tolist() == live,
          "loaded plane bottom row != oracle key set")
    print(f"load: {len(pop)} keys into capacity {capacity}, plane "
          f"L={levels} W={width}: {ph.wall} s "
          f"(compile {ph.compile} s of it)")
    return w, st, plane, oracle


def serve_twice(ctx, args, kwargs, epochs: int, label: str):
    """``run_serving`` cold (compile included) and again warm on the
    same inputs; the two must agree bit for bit."""
    np, sx = ctx["np"], ctx["sx"]
    with Phase(ctx["meter"]) as cold:
        out = sx.run_serving(*args, **kwargs)
        out[2].block_until_ready()
    with Phase(ctx["meter"]) as warm:
        again = sx.run_serving(*args, **kwargs)
        again[2].block_until_ready()
    for a, b in zip(out[2:], again[2:]):
        check((np.asarray(a) == np.asarray(b)).all(),
              f"{label}: a second identical call answered differently")
    print(f"{label}: {epochs} epochs x {BATCH} ops: first call "
          f"{cold.wall} s (compile {cold.compile} s), warm "
          f"{warm.wall / epochs} s per epoch")
    return out


def _live_keys(np, sx, st):
    """The state's live keys, sorted (allocated, unmarked, no sentinel)."""
    key = np.asarray(st.key)
    idx = np.arange(key.shape[0])
    alive = ((idx >= 2) & (idx < int(st.n_alloc))
             & ~np.asarray(st.deleted) & (key < sx.POS_INF_32))
    return np.sort(key[alive]).tolist()


def _kernel_answers_ok(np, sx, live, rows, q, hi, got) -> bool:
    """The direct kernel calls against the oracle's sorted live keys,
    and ``level_found`` against the plane's own rows."""
    f, r, lv, pk, pr, rk, rc, rt = (np.asarray(x) for x in got)
    pad = sx.POS_INF_32
    live = np.asarray(live, np.int64)
    n = len(live)
    a = np.searchsorted(live, q, side="left")
    j = np.searchsorted(live, q, side="right")
    b = np.searchsorted(live, hi, side="right")
    found = (a < n) & (live[np.minimum(a, n - 1)] == q)
    level = np.full(q.shape, rows.shape[0])
    for t in reversed(range(rows.shape[0])):
        row = rows[t][rows[t] != pad]
        pos = np.minimum(np.searchsorted(row, q), max(len(row) - 1, 0))
        hit = (len(row) > 0) & (row[pos] == q) if len(row) else False
        level = np.where(hit, t, level)
    pred = np.where(j > 0, live[np.maximum(j - 1, 0)], sx.NEG_INF_32)
    count = np.maximum(b - a, 0)
    lane = np.arange(rk.shape[1])
    take = lane[None, :] < np.minimum(count, rk.shape[1])[:, None]
    members = np.where(take, live[np.minimum(a[:, None] + lane, n - 1)],
                       pad)
    return bool((f == found).all() and (r == j - 1).all()
                and (lv == level).all() and (pk == pred).all()
                and (pr == j - 1).all() and (rc == count).all()
                and (rt == np.maximum(count - rk.shape[1], 0)).all()
                and (rk == members).all())


def one_chip(ctx, size):
    np, jnp, sx, kops, pc = ctx["np"], ctx["jnp"], ctx["sx"], \
        ctx["kops"], ctx["pc"]
    n, _, levels, width = size
    w, st, plane, oracle = load(ctx, size)

    # ---- mixed epochs: the state walk answers, the plane refreshes ----
    m = MIX_EPOCHS * BATCH
    kinds = w.kinds[:m].reshape(MIX_EPOCHS, BATCH)
    keys = w.keys[:m].reshape(MIX_EPOCHS, BATCH)
    upd = w.upd[:m].reshape(MIX_EPOCHS, BATCH)
    st, plane, res, _, ovf, _, _ = serve_twice(
        ctx, (st, plane, jnp.asarray(kinds), jnp.asarray(keys),
              jnp.asarray(upd)), {}, MIX_EPOCHS, "serve mixed")
    want = [oracle.apply(kd, k, u) for kd, k, u in
            zip(kinds.ravel().tolist(), keys.ravel().tolist(),
                upd.ravel().tolist())]
    check(np.asarray(res).ravel().tolist() == want,
          "mixed-epoch verdicts != oracle")
    check((np.asarray(ovf) == 0).all(), "mixed epochs overflowed")

    # ---- read-only epochs answered by the compiled descent ------------
    kinds, keys, upd = read_batch(np, w, n, READ_EPOCHS, SEED + 1)
    st, plane, res, _, ovf, _, _ = serve_twice(
        ctx, (st, plane, jnp.asarray(kinds), jnp.asarray(keys),
              jnp.asarray(upd)),
        dict(aggregate=True, plane_search=True, ordered=True),
        READ_EPOCHS, "serve plane-search")
    live = oracle.live()
    want = Oracle.answers(live, kinds.ravel(), keys.ravel(),
                          sx.NEG_INF_32)
    check(np.asarray(res).ravel().tolist() == want,
          "plane-search answers (contains/pred/range) != oracle")

    # ---- the kernels straight on the refreshed plane ------------------
    rng = np.random.default_rng(SEED + 2)
    q = np.where(rng.random(BATCH) < 0.5, rng.choice(live, BATCH),
                 rng.integers(-8, 2 * n + 8, BATCH)).astype(np.int32)
    hi = (q + rng.integers(0, 64, BATCH)).astype(np.int32)
    with Phase(ctx["meter"]) as ph:
        f, r, lv = kops.splay_search(plane, jnp.asarray(q))
        pk, pr = kops.splay_predecessor(plane, jnp.asarray(q))
        rk, rc, rt = kops.splay_range_scan(plane, jnp.asarray(q),
                                           jnp.asarray(hi), MAX_RANGE)
        rk.block_until_ready()
    check(_kernel_answers_ok(np, sx, live, np.asarray(plane.keys), q, hi,
                             (f, r, lv, pk, pr, rk, rc, rt)),
          "a kernel answer (search / predecessor / range scan) != oracle")
    print(f"query: splay_search + splay_predecessor + splay_range_scan on "
          f"{BATCH} keys: {ph.wall} s (compile {ph.compile} s)")

    # ---- final state and plane ----------------------------------------
    check(_live_keys(np, sx, st) == live, "final key set != oracle")
    audit = pc.audit_plane(st, plane)
    print(f"plane {pc.audit_summary(audit)}")
    check(pc.audit_ok(audit), "plane audit failed")
    print(f"bit-identical to the oracle: {MIX_EPOCHS * BATCH} mixed ops, "
          f"{READ_EPOCHS * BATCH} plane-search ops, {BATCH} x 3 kernel "
          f"queries; final key set {len(live)} keys")


def four_chip(ctx, size):
    np, jnp, sx, pc, jax = ctx["np"], ctx["jnp"], ctx["sx"], ctx["pc"], \
        ctx["jax"]
    from repro.launch.mesh import make_auto_mesh
    from repro.parallel import sharding as shd
    n, _, levels, width = size
    check(len(jax.devices()) >= 4,
          f"--four-chip needs 4 devices, found {len(jax.devices())}")
    mesh = make_auto_mesh((1, 4), ("data", "model"))
    w, st, plane, oracle = load(ctx, size)
    kinds, keys, upd = read_batch(np, w, n, READ_EPOCHS, SEED + 1)
    args = (jnp.asarray(kinds), jnp.asarray(keys), jnp.asarray(upd))
    common = dict(aggregate=True, plane_search=True, ordered=True)
    st_r, _, res_r, plen_r, _, _, _ = serve_twice(
        ctx, (st, plane) + args, common, READ_EPOCHS, "serve replicated")
    plane_s = shd.shard_index_plane(plane, mesh)
    st_s, pl_s, res_s, plen_s, ovf, spill, _ = serve_twice(
        ctx, (st, plane_s) + args, dict(common, mesh=mesh, split="mass"),
        READ_EPOCHS, "serve sharded (1x4, routed, mass split)")
    check((np.asarray(res_s) == np.asarray(res_r)).all()
          and (np.asarray(plen_s) == np.asarray(plen_r)).all(),
          "sharded answers != replicated loop")
    check(all((np.asarray(a) == np.asarray(b)).all()
              for a, b in zip(st_s, st_r)),
          "sharded epochs left a different state than the replicated loop")
    want = Oracle.answers(oracle.live(), kinds.ravel(), keys.ravel(),
                          sx.NEG_INF_32)
    check(np.asarray(res_s).ravel().tolist() == want,
          "sharded answers != oracle")
    check((np.asarray(ovf) == 0).all(), "sharded epochs overflowed")
    shards = pl_s.keys.addressable_shards
    check(len({s.device for s in shards}) == 4
          and all(s.data.shape == (levels, width // 4) for s in shards),
          "plane.keys is not split W/4 per device")
    audit = pc.audit_plane(st_s, pl_s)
    print(f"sharded plane {pc.audit_summary(audit)}; each of 4 devices "
          f"holds [{levels}, {width // 4}] of keys; "
          f"routed spill {int(np.asarray(spill).sum())}")
    check(pc.audit_ok(audit), "sharded plane audit failed")
    print(f"bit-identical: sharded == replicated == oracle on "
          f"{READ_EPOCHS * BATCH} plane-search ops")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the width-sharded path on a (1, 4) "
                         "mesh, against the replicated loop")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes (interpret-mode "
                         "kernels); never a chip result")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import device_index as dix
    from repro.core import plane_check as pc
    from repro.core import splaylist as sx
    from repro.core import workload as wl
    from repro.kernels import ops as kops

    dev = jax.devices()[0]
    if not args.rehearse and (dev.platform != "tpu"
                              or kops.exec_mode() != "compiled-tpu"):
        print(f"chip_smoke: no TPU (devices[0].platform={dev.platform}, "
              f"kernels {kops.exec_mode()}); refusing to run",
              file=sys.stderr)
        return 2
    size = TINY if args.rehearse else FOUR if args.four_chip else FULL
    ctx = dict(jax=jax, jnp=jnp, np=np, dix=dix, pc=pc, sx=sx, wl=wl,
               kops=kops, meter=compile_meter(jax))
    print(f"smoke timings of one run, not benchmark numbers; device "
          f"{dev.device_kind} x{len(jax.devices())}, jax {jax.__version__}, "
          f"kernels {kops.exec_mode()}, descent tiered, "
          f"compile cache {cache_dir}")
    try:
        (four_chip if args.four_chip else one_chip)(ctx, size)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    m = ctx["meter"]
    print(f"compile: {m['compile_s']} s in all; persistent cache "
          f"{m['cache_hits']} hits, {m['cache_misses']} misses")
    result = {"ok": True,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices())}}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

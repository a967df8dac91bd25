"""Serving driver: continuous batching with the splay-adaptive engine.

  PYTHONPATH=src python -m repro.launch.serve --arch minitron-8b --smoke

``--splay-demo`` instead drives the ordered-map serving substrate
directly (DESIGN.md §5.3–§5.4): build a splay-list state and its
device-resident index plane, run jitted serving epochs
(``splaylist.run_serving`` — op batches + incremental plane refresh with
the overflow/rebuild state machine), and, when the runtime exposes
multiple devices (e.g. ``XLA_FLAGS=--xla_force_host_platform_device_count=4``),
run the serving loop sharded end-to-end over the model axis — the
*routed* sharded plane search (all_to_all query exchange) answering
the batches plus sharded refresh, under both the equal-lane and the
mass-weighted boundary splits (DESIGN.md §5.5–§5.6) — and verify every
piece bit-identical against the replicated loop.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import registry
from repro.core import workload
from repro.models import model_zoo as zoo
from repro.serve.engine import Engine, Request
from repro.launch import compile_cache
from repro.launch.mesh import make_auto_mesh


def splay_demo(args) -> dict:
    """The build plane -> run_serving -> read results loop, plus the
    sharded-refresh cross-check (the launch-layer face of DESIGN.md
    §5.4)."""
    import jax.numpy as jnp
    from repro.core import device_index as dix
    from repro.core import plane_check as pc
    from repro.core import splaylist as sx
    from repro.kernels import ops as kops
    from repro.parallel import sharding as shd

    print(f"splay demo: mode={kops.exec_mode()}")
    rng = np.random.default_rng(args.seed)
    cap, L = 2050, 16
    W = cap - 2                      # 2048: divides 2/4/8-way meshes
    st = sx.make(capacity=cap, max_level=L)
    pool = np.arange(0, 2000, 2, dtype=np.int32)
    st, _, _ = sx.run_ops(
        st, jnp.full((len(pool),), sx.OP_INSERT, jnp.int32),
        jnp.asarray(pool), jnp.ones((len(pool),), bool))
    plane = dix.from_state_device(st, n_levels=L, width=W)
    # plane fsck (DESIGN.md §5.11) at every refresh boundary: a clean
    # plane prints exactly "audit OK"
    print(f"build {pc.audit_summary(pc.audit_plane(st, plane))}")

    E, B = args.epochs, args.batch
    hot = rng.choice(pool, max(B // 16, 1))
    kinds = rng.choice([sx.OP_CONTAINS, sx.OP_CONTAINS, sx.OP_INSERT],
                       (E, B)).astype(np.int32)
    keys = np.where(rng.random((E, B)) < 0.8,
                    rng.choice(hot, (E, B)),
                    rng.integers(0, 4000, (E, B))).astype(np.int32)
    ups = rng.random((E, B)) < 0.5

    st2, plane2, res, plen, ovf, _, _ = sx.run_serving(
        st, plane, jnp.asarray(kinds), jnp.asarray(keys),
        jnp.asarray(ups))
    out = {
        "epochs": E, "batch": B, "exec_mode": kops.exec_mode(),
        "hit_rate": float(np.asarray(res).mean()),
        "mean_path": float(np.asarray(plen).mean()),
        "overflow_epochs": int((np.asarray(ovf) > 0).sum()),
        "alive": int(st2.size),
    }
    print(f"splay serving: {E} epochs x {B} ops, hit rate "
          f"{out['hit_rate']:.2f}, mean path {out['mean_path']:.1f}, "
          f"overflow epochs {out['overflow_epochs']}, "
          f"alive {out['alive']}/{W}")
    out["audit"] = pc.audit_summary(pc.audit_plane(st2, plane2))
    print(f"serving {out['audit']}")

    n_dev = len(jax.devices())
    if n_dev > 1 and W % n_dev == 0:
        from repro.kernels import ops as kops
        mesh = make_auto_mesh((1, n_dev), ("data", "model"))
        plane_s = shd.shard_index_plane(plane, mesh)

        # end-to-end sharded serving (DESIGN.md §5.5–§5.6):
        # contains-only aggregate epochs answered from the *routed*
        # sharded plane search (all_to_all query exchange), refreshed
        # by the *sharded* refresh — vs the replicated loop
        ck = np.zeros_like(kinds)
        st_r, pl_r, res_r, plen_r, _, _, _ = sx.run_serving(
            st, plane, jnp.asarray(ck), jnp.asarray(keys),
            jnp.asarray(ups), aggregate=True, plane_search=True)
        st_s, pl_s, res_s, plen_s, _, spill_s, occ_s = sx.run_serving(
            st, plane_s, jnp.asarray(ck), jnp.asarray(keys),
            jnp.asarray(ups), aggregate=True, plane_search=True,
            mesh=mesh)
        serve_match = (
            (np.asarray(res_s) == np.asarray(res_r)).all()
            and (np.asarray(plen_s) == np.asarray(plen_r)).all()
            and all((np.asarray(getattr(pl_s, f))
                     == np.asarray(getattr(pl_r, f))).all()
                    for f in ("keys", "widths", "heights")))

        # the same loop under the mass-weighted re-split (§5.6): the
        # plane goes segmented, so only the answers — not the layout —
        # are compared against the replicated loop
        st_m, _, res_m, plen_m, _, spill_m, occ_m = sx.run_serving(
            st, plane_s, jnp.asarray(ck), jnp.asarray(keys),
            jnp.asarray(ups), aggregate=True, plane_search=True,
            mesh=mesh, split="mass")
        mass_match = (
            (np.asarray(res_m) == np.asarray(res_r)).all()
            and (np.asarray(plen_m) == np.asarray(plen_r)).all()
            and (np.asarray(st_m.key) == np.asarray(st_r.key)).all())

        # routing balance per epoch (DESIGN.md §5.6–§5.7): spill alone
        # hides a skewed-but-under-capacity exchange — print the
        # occupancy-derived max-share and gini so drift is visible
        # straight from the demo
        from repro.core import route_controller as rc
        for e in range(E):
            print(f"  epoch {e}: spill {int(np.asarray(spill_s)[e]):4d}"
                  f"/{int(np.asarray(spill_m)[e]):4d} (lanes/mass), "
                  f"max-share "
                  f"{rc.max_share(np.asarray(occ_s)[e]):.2f}/"
                  f"{rc.max_share(np.asarray(occ_m)[e]):.2f}, "
                  f"gini {rc.routing_gini(np.asarray(occ_s)[e]):.2f}/"
                  f"{rc.routing_gini(np.asarray(occ_m)[e]):.2f}")

        # the search alone, sharded vs gather-to-replicated dispatch
        qs = jnp.asarray(keys[0])
        f_s, r_s, l_s = kops.splay_search_sharded(pl_s, qs, mesh=mesh)
        f_g, r_g, l_g = kops.splay_search(pl_s, qs, sharded=False)
        search_match = bool(
            (np.asarray(f_s) == np.asarray(f_g)).all()
            and (np.asarray(r_s) == np.asarray(r_g)).all()
            and (np.asarray(l_s) == np.asarray(l_g)).all())

        # one mixed op batch, then refresh sharded vs replicated
        st3, _, _ = sx.run_ops(
            st, jnp.asarray(kinds[0]), jnp.asarray(keys[0]),
            jnp.asarray(ups[0]))
        ps, ov_s = dix.refresh_device_sharded(st3, plane_s, max_new=B,
                                              mesh=mesh)
        pr, ov_r = dix.refresh_device(st3, plane, max_new=B,
                                      return_overflow=True)
        refresh_match = all(
            (np.asarray(getattr(ps, f)) == np.asarray(getattr(pr, f))).all()
            for f in ("keys", "widths", "heights"))
        print(f"sharded refresh "
              f"{pc.audit_summary(pc.audit_plane(st3, ps))}")

        # the closed loop (DESIGN.md §5.7): the routing controller
        # steering slack/split/rebuild from the spill+occupancy
        # feedback, bit-identical answers to the replicated loop
        cfg, c0 = rc.init_controller(n_dev)
        st_c, _, res_c, plen_c, _, spl_c, occ_c, cstates = \
            rc.run_serving_controlled(
                st, plane_s, jnp.asarray(ck), jnp.asarray(keys),
                jnp.asarray(ups), aggregate=True, plane_search=True,
                mesh=mesh, cfg=cfg, state=c0)
        ctrl_match = (
            (np.asarray(res_c) == np.asarray(res_r)).all()
            and (np.asarray(plen_c) == np.asarray(plen_r)).all())
        cfin = cstates[-1]
        print(f"controller: bit_identical={bool(ctrl_match)}, "
              f"slack {c0.slack_of(cfg)} -> {cfin.slack_of(cfg)}, "
              f"split -> {cfin.split}, retraces {cfin.retraces}, "
              f"escalations {cfin.escalations}, "
              f"spill {int(np.asarray(spl_c).sum())}, "
              f"final max-share {cfin.last_share:.2f}, "
              f"gini {cfin.last_gini:.2f}")
        out["sharded"] = {
            "shards": n_dev,
            "serving_bit_identical": bool(serve_match),
            "mass_split_bit_identical": bool(mass_match),
            "search_bit_identical": search_match,
            "refresh_bit_identical": bool(refresh_match),
            "overflow": int(ov_s),
            "routed_spill": int(np.asarray(spill_s).sum()),
            "routed_spill_mass": int(np.asarray(spill_m).sum()),
            "max_share_lanes": rc.max_share(np.asarray(occ_s).sum(0)),
            "max_share_mass": rc.max_share(np.asarray(occ_m).sum(0)),
            "routing_gini_lanes": rc.routing_gini(
                np.asarray(occ_s).sum(0)),
            "routing_gini_mass": rc.routing_gini(
                np.asarray(occ_m).sum(0)),
            "controller_bit_identical": bool(ctrl_match),
            "controller_retraces": int(cfin.retraces),
            "controller_escalations": int(cfin.escalations),
            "controller_spill": int(np.asarray(spl_c).sum())}
        print(f"sharded serving on {n_dev} shards: "
              f"epochs bit_identical={serve_match}, "
              f"mass-split bit_identical={mass_match}, "
              f"search bit_identical={search_match}, "
              f"refresh bit_identical={refresh_match}, "
              f"overflow={int(ov_s)} (replicated {int(ov_r)}), "
              f"spill={int(np.asarray(spill_s).sum())}"
              f"/{int(np.asarray(spill_m).sum())} (lanes/mass)")
    else:
        print(f"sharded serving skipped ({n_dev} device(s); set "
              f"XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minitron-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--splay-demo", action="store_true",
                    help="drive the splay index-plane serving loop "
                         "instead of the LM engine")
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--device-index", action="store_true",
                    help="answer session lookups from the device index "
                         "plane (run_epoch plane_search) instead of the "
                         "host reference splay-list")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate in requests per decode "
                         "step (0 = the legacy burst-at-zero queue)")
    ap.add_argument("--snapshot-dir", default=None,
                    help="publish a crash-consistent serving snapshot "
                         "(pool + index + controller + engine queue) "
                         "here after the run")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest snapshot from "
                         "--snapshot-dir before serving (auto-resume; "
                         "a fresh start if the directory is empty)")
    ap.add_argument("--audit-every", type=int, default=0,
                    help="run the plane fsck every K lookup epochs on "
                         "the device index (0 = off)")
    args = ap.parse_args(argv)
    compile_cache.enable()

    if args.splay_demo:
        return splay_demo(args)

    cfg = (registry.get_smoke(args.arch) if args.smoke
           else registry.get(args.arch))
    params, _ = zoo.build_params(cfg, jax.random.PRNGKey(args.seed))
    eng = Engine(cfg, params, max_batch=args.max_batch, max_seq=128,
                 device_index=args.device_index,
                 audit_every=args.audit_every)
    mgr = None
    if args.snapshot_dir:
        from repro.serve import snapshot as snap
        from repro.train.checkpoint import CheckpointManager
        mgr = CheckpointManager(args.snapshot_dir)
        if args.resume and mgr.latest_step() is not None:
            pool, eng_state, summary = snap.restore_serving_snapshot(
                mgr, audit_every=args.audit_every or None)
            eng.pool = pool
            snap.apply_engine_state(eng, eng_state)
            print(summary)
    arrivals = workload.poisson_zipf_arrivals(
        args.requests, args.rate if args.rate > 0 else float("inf"),
        cfg.vocab, prompt_len=(2, 7), max_new=args.max_new,
        seed=args.seed)
    for i in range(args.requests):
        L = int(arrivals.prompt_lens[i])
        eng.submit(Request(
            seq_id=int(arrivals.seq_ids[i]),
            prompt=arrivals.prompts[i, :L].copy(),
            max_new=int(arrivals.max_new[i]),
            arrival=int(arrivals.arrival[i])))
    results = eng.run()
    for sid in sorted(results):
        print(f"seq {sid}: {results[sid]}")
    lat = sorted(eng.latencies.values())
    p50 = lat[len(lat) // 2] if lat else 0
    print(f"served {len(results)} sequences; pool util "
          f"{eng.pool.utilization:.2f}; p50 latency {p50} steps; "
          f"stalls {eng.stalls}; preemptions {eng.preemptions}; "
          f"degraded retries {eng.degraded_retries}")
    if eng.pool.device and args.audit_every:
        from repro.core import plane_check as pc
        print(pc.audit_summary(eng.pool.audit()))
    if mgr is not None:
        from repro.serve import snapshot as snap
        snap.save_serving_snapshot(mgr, eng.clock, eng.pool, engine=eng)
        print(f"saved serving snapshot step {eng.clock} "
              f"to {args.snapshot_dir}")
    return results


if __name__ == "__main__":
    main()

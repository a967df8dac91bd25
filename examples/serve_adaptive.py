"""Serving example: continuous batching with the splay-indexed page pool
and the adaptive hot-vocab tier, then the routed width-sharded serving
loop (DESIGN.md §5.6) end-to-end on a forced host mesh.

Run:  PYTHONPATH=src python examples/serve_adaptive.py

The second half shards the splay index plane over SERVE_SHARDS host
devices (default 4; the forced device count must be set before jax
initializes, which is why it happens at the top of this file), serves
contains-only epochs answered by the *routed* sharded plane search —
owner-bucketed all_to_all query exchange, O(B/S) kernel work per shard
— refreshed by the sharded refresh under the mass-weighted boundary
re-split, and prints the spill/occupancy picture next to the answers.
"""

import os

N_SHARDS = int(os.environ.get("SERVE_SHARDS", "4"))
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        f"{_flags} --xla_force_host_platform_device_count"
        f"={N_SHARDS}").strip()

import numpy as np                                      # noqa: E402
import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

from repro.configs import registry                      # noqa: E402
from repro.models import model_zoo as zoo               # noqa: E402
from repro.serve.engine import Engine, Request          # noqa: E402
from repro.launch.mesh import make_auto_mesh           # noqa: E402


def engine_demo():
    cfg = registry.get_smoke("minitron-8b")
    params, _ = zoo.build_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, max_batch=4, max_seq=64)

    rng = np.random.default_rng(0)
    for i in range(8):
        prompt = rng.integers(1, cfg.vocab, rng.integers(2, 6))
        eng.submit(Request(seq_id=i, prompt=prompt, max_new=8))
    results = eng.run()
    for sid, toks in sorted(results.items()):
        print(f"seq {sid}: generated {toks}")
    print(f"page pool utilization after drain: {eng.pool.utilization:.2f}")
    if eng.vocab_cache is not None:
        print(f"vocab cache: m={eng.vocab_cache.m}, "
              f"hot={len(eng.vocab_cache.hot_ids)} ids")


def routed_sharded_serving_demo():
    """The §5.6 loop: splay state -> width-sharded plane -> epochs of
    Zipf-skewed contains batches answered by the routed sharded search,
    refreshed with the mass-weighted boundary re-split."""
    from repro.core import device_index as dix
    from repro.core import plane_check as pc
    from repro.core import route_controller as rc
    from repro.core import splaylist as sx
    from repro.kernels import splay_search as ssk
    from repro.parallel import sharding as shd

    n_dev = len(jax.devices())
    cap, L = 1026, 12
    W = cap - 2                                   # 1024: divides 2/4/8
    if n_dev < 2 or W % n_dev:
        print(f"routed sharded serving skipped ({n_dev} device(s))")
        return

    rng = np.random.default_rng(0)
    pool = np.sort(rng.choice(20 * W, int(W * 0.75),
                              replace=False)).astype(np.int32)
    st = sx.make(capacity=cap, max_level=L)
    st, _, _ = sx.run_ops(
        st, jnp.full((len(pool),), sx.OP_INSERT, jnp.int32),
        jnp.asarray(pool), jnp.ones((len(pool),), bool))

    mesh = make_auto_mesh((1, n_dev), ("data", "model"))
    plane = dix.from_state_device(st, n_levels=L, width=W)
    plane_s = shd.shard_index_plane(plane, mesh)
    # plane fsck (DESIGN.md §5.11) at each refresh boundary: the
    # auditor re-derives every invariant the search kernels assume;
    # clean planes print exactly "audit OK"
    print(f"build {pc.audit_summary(pc.audit_plane(st, plane))}")

    # Zipf-skewed contains epochs: hot keys get hammered, so the hit
    # counters skew and the mass re-split has something to balance.
    # Hotness is scattered across the keyspace (ranks permuted — the
    # realistic case for hash-like key ids; hotness clustered at one
    # end of the keyspace is the adversarial case, where the per-shard
    # lane capacity bounds how far the mass split can move — see
    # DESIGN.md §5.6).  Volume matters too: the mass formula floors
    # every key at 1 (so cold planes split evenly), and the re-split
    # only beats the equal-lane boundaries once accumulated hits
    # outweigh that floor — a few epochs of real traffic, as in
    # production
    E, B = 8, 512
    ranks = rng.permutation(len(pool))
    p = 1.0 / (1 + ranks) ** 1.0
    p /= p.sum()
    keys = rng.choice(pool, (E, B), p=p).astype(np.int32)
    kinds = np.zeros((E, B), np.int32)            # contains-only
    ups = rng.random((E, B)) < 0.7

    st2, plane2, res, plen, ovf, spill, occ_e = sx.run_serving(
        st, plane_s, jnp.asarray(kinds), jnp.asarray(keys),
        jnp.asarray(ups), aggregate=True, plane_search=True,
        mesh=mesh, split="mass")
    nseg = n_dev if dix.plane_is_segmented(plane2) else 1
    print(f"serving {pc.audit_summary(pc.audit_plane(st2, plane2, n_segments=nseg))}")

    # the routed exchange's balance on the final (re-split) plane
    _, _, _, stats = ssk.splay_search_sharded(
        plane2, jnp.asarray(keys[-1]), mesh=mesh, return_stats=True)
    occ = np.asarray(stats.occupancy)
    print(f"routed sharded serving on {n_dev} shards: {E} epochs x {B} "
          f"contains, hit rate {float(np.asarray(res).mean()):.2f}, "
          f"mean level-found {float(np.asarray(plen).mean()):.1f}")
    print(f"  overflow epochs {int((np.asarray(ovf) > 0).sum())}, "
          f"spill per epoch {np.asarray(spill).tolist()} "
          f"(capacity {ssk.route_capacity(B, n_dev)}/shard — watch it "
          f"fall as the re-split adapts)")
    for e in range(E):
        o = np.asarray(occ_e)[e]
        print(f"  epoch {e}: spill {int(np.asarray(spill)[e]):3d}, "
              f"max-share {rc.max_share(o):.2f}, "
              f"gini {rc.routing_gini(o):.2f}")
    print(f"  post-re-split occupancy per shard: {occ.tolist()} "
          f"(max share {occ.max() / max(occ.sum(), 1):.2f}, "
          f"ideal {1 / n_dev:.2f})")
    # the adaptivity contract, asserted rather than eyeballed: once the
    # mass re-split has had epochs of hit counters to work with, the
    # exchange fits in capacity again — spill back under 1% of the batch
    tail = np.asarray(spill)[E // 2:] / B
    assert (tail <= 0.01).all(), \
        f"mass re-split failed to absorb the skew: tail spill {tail}"
    print(f"  re-split recovery: tail spill rate "
          f"{float(tail.max()):.4f} <= 0.01 ✓")


def controlled_serving_demo():
    """The closed loop (DESIGN.md §5.7): the same Zipf stream with its
    hot set MIGRATING mid-run, steered by the routing controller —
    slack ladder + lanes->mass escalation driven by the spill/occupancy
    feedback, recovery asserted."""
    from repro.core import device_index as dix
    from repro.core import route_controller as rc
    from repro.core import splaylist as sx
    from repro.core import workload as wl
    from repro.parallel import sharding as shd

    n_dev = len(jax.devices())
    cap, L = 1026, 12
    W = cap - 2
    if n_dev < 2 or W % n_dev:
        print(f"controlled serving skipped ({n_dev} device(s))")
        return

    E, B = 10, 512
    drift = wl.rotating_hotset_workload(int(W * 0.75), E, B, period=5,
                                        seed=3)
    st = sx.make(capacity=cap, max_level=L)
    st, _, _ = sx.run_ops(
        st, jnp.full((len(drift.populate),), sx.OP_INSERT, jnp.int32),
        jnp.asarray(drift.populate), jnp.ones((len(drift.populate),),
                                              bool))
    mesh = make_auto_mesh((1, n_dev), ("data", "model"))
    plane_s = shd.shard_index_plane(
        dix.from_state_device(st, n_levels=L, width=W), mesh)

    cfg, c0 = rc.init_controller(n_dev)
    _, _, res, _, _, spl, occ, states = rc.run_serving_controlled(
        st, plane_s, jnp.asarray(drift.kinds), jnp.asarray(drift.keys),
        jnp.asarray(drift.upd), aggregate=True, plane_search=True,
        mesh=mesh, cfg=cfg, state=c0)
    print(f"controlled serving on {n_dev} shards: {E} epochs x {B}, "
          f"hot set migrates at {list(drift.transitions)}, hit rate "
          f"{float(np.asarray(res).mean()):.2f}")
    for e, s in enumerate(states):
        mark = " <- transition" if e in drift.transitions else ""
        print(f"  epoch {e}: spill {int(np.asarray(spl)[e]):3d}, "
              f"max-share {rc.max_share(np.asarray(occ)[e]):.2f}, "
              f"slack {s.slack_of(cfg)}, split {s.split}{mark}")
    # recovery contract: within the ladder-length bound of each
    # migration, spill is back under 1% of the batch
    k = len(cfg.slack_ladder)
    sr = np.asarray(spl) / B
    for t in drift.transitions:
        win = sr[t:min(t + k + 1, E)]
        assert (win <= 0.01).any(), \
            f"no recovery within {k} epochs of transition {t}: {sr}"
    print(f"  controller recovery: <=1% spill within {k} epochs of "
          f"every migration ✓ (retraces {states[-1].retraces}, "
          f"escalations {states[-1].escalations})")


def main():
    engine_demo()
    routed_sharded_serving_demo()
    controlled_serving_demo()


if __name__ == "__main__":
    main()

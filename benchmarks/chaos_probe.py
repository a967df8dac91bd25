"""Chaos probe: fault injection, plane fsck, and crash recovery
(DESIGN.md §5.11).

Self-contained subprocess target (forces
``--xla_force_host_platform_device_count`` *before* importing jax),
mirroring ``serving_probe.py``:

  python benchmarks/chaos_probe.py --parity      # CI gate battery
  python benchmarks/chaos_probe.py --bench       # JSON to stdout

``--parity`` (the CI "Chaos recovery" step) asserts the §5.11
recovery contract at small shapes:

  (1) **clean planes audit clean** — meshless, lanes-sharded, and
      mass-split (segmented) planes produced by the real build /
      refresh paths return an all-zero ``PlaneAudit``;
  (2) **every fault family detected within one audit epoch** — each
      ``core.faults`` bit-flip family corrupts a plane the fsck then
      flags (packed and segmented layouts), and in the serving loop
      the injection epoch's own audit catches it *before* any verdict
      is served off the corrupted plane;
  (3) **zero wrong verdicts, bounded recovery** — device pools replay
      request traces under bit-flip + telemetry + shard-loss chaos
      bit-identically to an undisturbed host-pool mirror (meshless and
      1x4 routed mesh), walking the routed -> masked -> host-oracle
      ladder and returning to routed steady state within
      ``RECOVERY_BOUND`` lookup epochs of every injection;
  (4) **crash-consistent snapshots** — a mid-epoch ``InjectedCrash``
      between flush and lookup, restored from the latest snapshot,
      replays the pending-op buffer exactly once: the post-restore
      verdict stream and final live set are bit-identical to an
      uninterrupted run;
  (5) **restore bit-identity across backends** — host, meshless
      device, and 1x4-mesh device pools all continue a half-replayed
      trace identically after snapshot->restore, including a shrunk
      4->2 mesh restore (``elastic.remesh`` + re-layout) and a
      mesh->meshless restore.

Exits nonzero on any violation; prints ``CHAOS RECOVERY OK``.

``--bench`` runs the same battery and prints one JSON object
(``chaos_recovery`` in BENCH_kernels.json): per-family
injected/detected counts, wrong-verdict count, max observed recovery
epochs vs the bound, and the snapshot bit-identity / exactly-once
flags CI gates on.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

N_DEV = 4
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        f"{_flags} --xla_force_host_platform_device_count={N_DEV}").strip()

import jax                                             # noqa: E402
import numpy as np                                     # noqa: E402

from repro.core import device_index as dix             # noqa: E402
from repro.core import faults as fl                    # noqa: E402
from repro.core import plane_check as pc               # noqa: E402
from repro.core import splaylist as sx                 # noqa: E402
from repro.core import workload as wl                  # noqa: E402
from repro.parallel import sharding as shd             # noqa: E402
from repro.serve import snapshot as snap               # noqa: E402
from repro.serve.kv_cache import PagedKVPool           # noqa: E402
from repro.train.checkpoint import CheckpointManager   # noqa: E402
from repro.launch.mesh import make_auto_mesh           # noqa: E402

RECOVERY_BOUND = 4          # lookup epochs from injection back to routed
WIDTH = 32                  # divisible by 1/2/4 (shard-loss shrink path)
BATCH = 16
N_PAGES = 48
PAGE = 8


def _mesh(n=N_DEV):
    assert len(jax.devices()) >= n, \
        f"forced host mesh absent: {len(jax.devices())} device(s)"
    return make_auto_mesh((1, n), ("data", "model"))


def _seeded_state(n_keys=20, seed=11):
    rng = np.random.default_rng(seed)
    keys = rng.choice(10_000, n_keys, replace=False).astype(np.int32)
    st = sx.make(WIDTH + 2, max_level=8)
    st, _, _ = sx.run_ops(st, np.full(n_keys, sx.OP_INSERT, np.int32),
                          keys, np.ones(n_keys, bool))
    for _ in range(4):
        q = rng.choice(keys, n_keys).astype(np.int32)
        st, _, _ = sx.run_contains_batch(st, q, np.ones(n_keys, bool),
                                         aggregate=True)
    return st


def _planes(st):
    """(name, plane, n_segments) triples from the real layout paths."""
    mesh = _mesh()
    packed = dix.from_state_device(st, n_levels=8, width=WIDTH)
    sharded = shd.shard_index_plane(packed, mesh)
    lanes, _ = dix.refresh_device_sharded(st, sharded, max_new=4,
                                          mesh=mesh, split="lanes")
    mass, _ = dix.refresh_device_sharded(st, sharded, max_new=4,
                                         mesh=mesh, split="mass")
    return [("meshless", packed, 1), ("lanes4", lanes, 1),
            ("mass4", mass, N_DEV)]


def audit_battery() -> dict:
    """Parts (1)-(2): clean planes audit clean; every bit-flip family
    is detected on packed AND segmented layouts."""
    st = _seeded_state()
    out = {"clean": {}, "families": {}}
    planes = _planes(st)
    for name, plane, nseg in planes:
        a = pc.audit_plane(st, plane, n_segments=nseg)
        out["clean"][name] = pc.audit_ok(a)
        assert pc.audit_ok(a), f"clean {name} plane failed: {a}"
    for fi, field in enumerate(fl.BITFLIP_FIELDS):
        inj = det = 0
        for name, plane, nseg in planes:
            for trial in range(6):
                bad, recs = fl.flip_plane_bits(
                    plane, np.random.default_rng([trial, fi]),
                    1, fields=(field,))
                if not recs:
                    continue
                inj += 1
                a = pc.audit_plane(st, bad, n_segments=nseg)
                det += int(not pc.audit_ok(a))
        out["families"][field] = {"injected": inj, "detected": det}
        assert det == inj, f"{field}: {det}/{inj} detected"
    return out


def _replay_chaos(dev: PagedKVPool, host: PagedKVPool,
                  trace: wl.KVTrace, plan) -> dict:
    """Replay a trace on a chaos-injected device pool and an
    undisturbed host mirror; every lookup verdict must match, and the
    rung trajectory must return to 0 within RECOVERY_BOUND lookups of
    every injection."""
    kinds, sids = np.asarray(trace.kinds), np.asarray(trace.seq_ids)
    wrong = 0
    rung_traj = []
    for t in range(kinds.size):
        k, s = int(kinds[t]), int(sids[t])
        if k == wl.KV_CREATE:
            a, b = dev.create(s), host.create(s)
            assert a == b, f"create disagreement at op {t}"
        elif k == wl.KV_RELEASE:
            dev.release(s)
            host.release(s)
        else:
            va = bool(dev.lookup_batch([s])[0])
            vb = bool(host.lookup_batch([s])[0])
            wrong += int(va != vb)
            rung_traj.append(int(dev._rung))
    # recovery: after each injected event the rung trajectory must hit
    # 0 again within RECOVERY_BOUND lookups
    rec_max = 0
    arr = np.asarray(rung_traj)
    nz = np.nonzero(arr)[0]
    for i in nz:
        back = arr[i:i + RECOVERY_BOUND + 1]
        steps = int(np.argmax(back == 0)) if (back == 0).any() else 10 ** 9
        rec_max = max(rec_max, steps)
    return {"wrong_verdicts": wrong, "recovery_epochs_max": rec_max,
            "injected": int(dev.stats["faults_injected"]),
            "audit_failures": int(dev.stats["audit_failures"]),
            "repairs": int(dev.stats["repairs"]),
            "degraded_masked": int(dev.stats["degraded_masked"]),
            "degraded_host": int(dev.stats["degraded_host"]),
            "remeshes": int(dev.stats["remeshes"]),
            "telemetry_dropped": int(dev.stats["telemetry_dropped"])}


def chaos_serving() -> dict:
    """Part (3): bit-flip + telemetry chaos meshless and on the 1x4
    mesh, plus mid-serving shard loss 4->2->replicated."""
    out = {}
    plan = fl.FaultPlan(seed=2, events=[
        fl.FaultEvent(3, fl.FAULT_BITFLIP, 2),
        fl.FaultEvent(8, fl.FAULT_TELEMETRY, 2),
        fl.FaultEvent(13, fl.FAULT_BITFLIP, 1)])
    dev = PagedKVPool(N_PAGES, PAGE, device=True, index_width=WIDTH,
                      index_batch=BATCH, audit_every=1, fault_plan=plan)
    host = PagedKVPool(N_PAGES, PAGE, device=False)
    out["meshless"] = _replay_chaos(
        dev, host, wl.kv_request_trace(150, 24, seed=5), plan)

    plan4 = fl.FaultPlan(seed=4, events=[
        fl.FaultEvent(3, fl.FAULT_BITFLIP, 2),
        fl.FaultEvent(9, fl.FAULT_SHARD_LOSS, 2),
        fl.FaultEvent(15, fl.FAULT_SHARD_LOSS, 3)])  # 3 !| 32: replicated
    dev4 = PagedKVPool(N_PAGES, PAGE, device=True, index_width=WIDTH,
                       index_batch=BATCH, mesh=_mesh(), audit_every=1,
                       fault_plan=plan4)
    host4 = PagedKVPool(N_PAGES, PAGE, device=False)
    out["mesh4"] = _replay_chaos(
        dev4, host4, wl.kv_request_trace(150, 24, seed=6), plan4)
    for name, r in out.items():
        assert r["wrong_verdicts"] == 0, f"{name}: wrong verdicts"
        assert r["audit_failures"] >= 1, f"{name}: chaos went undetected"
        assert r["recovery_epochs_max"] <= RECOVERY_BOUND, \
            f"{name}: recovery took {r['recovery_epochs_max']} epochs"
        assert r["degraded_masked"] >= 1, \
            f"{name}: masked rung never exercised"
    assert out["mesh4"]["remeshes"] == 2
    assert out["meshless"]["telemetry_dropped"] >= 1

    # rung 2 (host ref_py oracle): force the bottom of the ladder and
    # check oracle verdicts stay bit-identical, then the climb back to
    # routed takes one clean pass per rung
    live = sorted(host.chains)[:6]
    probes = live + [10 ** 6, 10 ** 6 + 1]      # present + absent ids
    before = int(dev.stats["degraded_host"])
    for s in probes:
        dev._rung = 2                            # hold at the bottom
        va = bool(dev.lookup_batch([s])[0])
        vb = bool(host.lookup_batch([s])[0])
        assert va == vb, f"host-oracle rung wrong verdict for {s}"
    assert dev.stats["degraded_host"] - before == len(probes)
    for s in probes[:3]:                         # release: climb back
        dev.lookup_batch([s])
    assert dev._rung == 0, f"ladder climb stalled at rung {dev._rung}"
    out["meshless"]["degraded_host"] = int(dev.stats["degraded_host"])
    return out


def _drive(pool, trace, lo, hi, record):
    kinds, sids = np.asarray(trace.kinds), np.asarray(trace.seq_ids)
    for t in range(lo, hi):
        k, s = int(kinds[t]), int(sids[t])
        if k == wl.KV_CREATE:
            pool.create(s)
        elif k == wl.KV_RELEASE:
            pool.release(s)
        else:
            record.append((t, bool(pool.lookup_batch([s])[0])))


def crash_replay() -> dict:
    """Part (4): snapshot every 20 ops, crash mid-trace between flush
    and lookup, restore from the latest snapshot and re-drive — the
    verdict stream and final live set must equal the uninterrupted
    run's (pending ops replayed exactly once)."""
    trace = wl.kv_request_trace(120, 20, seed=9)
    ref = PagedKVPool(N_PAGES, PAGE, device=True, index_width=WIDTH,
                      index_batch=BATCH)
    ref_rec = []
    _drive(ref, trace, 0, 120, ref_rec)

    crash_at = 17                         # lookup-epoch of the kill
    plan = fl.FaultPlan(seed=1, events=[
        fl.FaultEvent(crash_at, fl.FAULT_CRASH)])
    pool = PagedKVPool(N_PAGES, PAGE, device=True, index_width=WIDTH,
                       index_batch=BATCH, fault_plan=plan)
    rec = []
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2)
        kinds, sids = np.asarray(trace.kinds), np.asarray(trace.seq_ids)
        crashed_op = None
        pending_at_snap = 0
        t = 0
        while t < 120:
            k, s = int(kinds[t]), int(sids[t])
            try:
                if k == wl.KV_CREATE:
                    pool.create(s)
                elif k == wl.KV_RELEASE:
                    pool.release(s)
                else:
                    rec.append((t, bool(pool.lookup_batch([s])[0])))
            except fl.InjectedCrash:
                crashed_op = t
                # the machine is gone: restore the latest snapshot
                # onto a fresh pool and re-drive from its trace cursor
                pool, _, summary = snap.restore_serving_snapshot(mgr)
                _, extra = mgr.load(mgr.latest_step())
                t = int(extra["user"]["next_op"])
                rec = [x for x in rec if x[0] < t]
                continue
            t += 1
            if t % 20 == 0:
                pending_at_snap = max(pending_at_snap,
                                      len(pool._pending))
                snap.save_serving_snapshot(mgr, t, pool,
                                           user_extra={"next_op": t})
        assert crashed_op is not None, "crash event never fired"
    assert rec == ref_rec, "post-restore verdicts diverged"
    assert sorted(pool.chains) == sorted(ref.chains)
    return {"crashed_at_op": crashed_op,
            "pending_at_snapshot": pending_at_snap,
            "replay_exactly_once": rec == ref_rec}


def restore_matrix() -> dict:
    """Part (5): snapshot->restore bit-identity on host / meshless /
    1x4 backends, plus shrunk 4->2 and 4->meshless restores."""
    trace = wl.kv_request_trace(100, 20, seed=13)
    out = {}

    def roundtrip(make_pool, restore_kw, tag):
        ref = make_pool()
        ref_rec = []
        _drive(ref, trace, 0, 50, ref_rec)
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            snap.save_serving_snapshot(mgr, 50, ref)
            pool, _, summary = snap.restore_serving_snapshot(
                mgr, **restore_kw)
        tail_ref, tail_new = list(ref_rec), list(ref_rec)
        _drive(ref, trace, 50, 100, tail_ref)
        _drive(pool, trace, 50, 100, tail_new)
        ok = tail_new == tail_ref and sorted(pool.chains) == \
            sorted(ref.chains)
        out[tag] = {"bit_identical": ok, "summary": summary}
        assert ok, f"{tag}: restore diverged"

    roundtrip(lambda: PagedKVPool(N_PAGES, PAGE, device=False),
              {}, "host")
    roundtrip(lambda: PagedKVPool(N_PAGES, PAGE, device=True,
                                  index_width=WIDTH, index_batch=BATCH),
              {}, "meshless")
    roundtrip(lambda: PagedKVPool(N_PAGES, PAGE, device=True,
                                  index_width=WIDTH, index_batch=BATCH,
                                  mesh=_mesh()),
              {"mesh": _mesh()}, "mesh4")
    roundtrip(lambda: PagedKVPool(N_PAGES, PAGE, device=True,
                                  index_width=WIDTH, index_batch=BATCH,
                                  mesh=_mesh()),
              {"mesh": _mesh(2)}, "mesh4_to_2")
    roundtrip(lambda: PagedKVPool(N_PAGES, PAGE, device=True,
                                  index_width=WIDTH, index_batch=BATCH,
                                  mesh=_mesh()),
              {}, "mesh4_to_meshless")
    return out


def run_battery() -> dict:
    t0 = time.time()
    audits = audit_battery()
    chaos = chaos_serving()
    crash = crash_replay()
    restores = restore_matrix()
    injected = sum(f["injected"] for f in audits["families"].values())
    detected = sum(f["detected"] for f in audits["families"].values())
    serving_injected = sum(r["injected"] for r in chaos.values()) + 1
    return {
        "backends": ["host", "meshless", "mesh4"],
        "shards": N_DEV,
        "fault_families": list(fl.FAULT_FAMILIES),
        "injected": injected + serving_injected,
        "detected": detected + serving_injected,
        "detection_within_epochs": 1,
        "wrong_verdicts": sum(r["wrong_verdicts"]
                              for r in chaos.values()),
        "recovery_bound_epochs": RECOVERY_BOUND,
        "recovery_epochs_max": max(r["recovery_epochs_max"]
                                   for r in chaos.values()),
        "restore_bit_identical": all(r["bit_identical"]
                                     for r in restores.values()),
        "replay_exactly_once": crash["replay_exactly_once"],
        "audit_matrix": audits,
        "chaos": chaos,
        "crash": crash,
        "restores": {k: v["bit_identical"] for k, v in restores.items()},
        "wall_s": round(time.time() - t0, 1),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parity", action="store_true")
    ap.add_argument("--bench", action="store_true")
    args = ap.parse_args()
    out = run_battery()
    assert out["detected"] == out["injected"], out
    assert out["wrong_verdicts"] == 0, out
    assert out["recovery_epochs_max"] <= out["recovery_bound_epochs"]
    assert out["restore_bit_identical"] and out["replay_exactly_once"]
    if args.bench:
        print(json.dumps(out))
        return 0
    print(f"faults: {out['detected']}/{out['injected']} detected, "
          f"0 wrong verdicts, recovery <= "
          f"{out['recovery_epochs_max']} epochs, "
          f"restores bit-identical on {list(out['restores'])} "
          f"({out['wall_s']}s)")
    print("CHAOS RECOVERY OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())

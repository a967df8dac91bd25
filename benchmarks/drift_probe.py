"""Drift probe: the routing controller raced through distribution shifts.

Self-contained subprocess target (forces
``--xla_force_host_platform_device_count`` *before* importing jax),
mirroring ``sharded_search_probe.py``:

  python benchmarks/drift_probe.py --parity        # recovery battery
  python benchmarks/drift_probe.py --bench         # JSON to stdout

``--parity`` (the CI "Drift recovery" step, small shapes) drives the
closed-loop serving loop (``core.route_controller.run_serving_controlled``,
DESIGN.md §5.7) through the three drift scenarios
(``core.workload.DRIFT_SCENARIOS``) on a forced 1x4 host mesh and
asserts, for each: (1) every answer bit-identical to the meshless
replicated ``run_serving`` — the controller only moves queries between
routing paths, never changes answers; (2) post-transition spill returns
to <= 1% of the batch within K epochs (K = the slack-ladder length: the
structural recovery bound — the top rung clamps capacity at q, where
spill is impossible); (3) the static controller-off baseline does NOT
recover within K on at least one transition (the scenarios are real
adversaries, not strawmen); (4) a drift-free balanced stream never
actuates (zero retraces/escalations — the hysteresis band holds).
Exits nonzero on any violation.

``--bench`` races controller-on vs controller-off (static lanes,
default slack) vs static-mass through each scenario at the acceptance
shape (w4096/q8192, 4 shards) and prints one JSON object with the
per-epoch spill/max-share/gini trajectories, per-transition
time-to-recover, peak spill, and post-transition peak max-share —
consumed by ``benchmarks/kernels_bench.py`` into the
``routing_controller`` entry of ``BENCH_kernels.json``.
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
from repro.core import route_controller as rc          # noqa: E402
from repro.core import splaylist as sx                 # noqa: E402
from repro.core import workload as wl                  # noqa: E402
from repro.kernels import ops as kops                  # noqa: E402
from repro.kernels import splay_search as ssk          # noqa: E402
from repro.parallel import sharding as shd             # noqa: E402
from repro.launch.mesh import make_auto_mesh           # noqa: E402

SPILL_OK = 0.01          # "recovered" = spill rate at or below this


def _seed(pool: np.ndarray, cap: int, max_level: int):
    st = sx.make(capacity=cap, max_level=max_level)
    st, _, _ = sx.run_ops(
        st, jnp.full((len(pool),), sx.OP_INSERT, jnp.int32),
        jnp.asarray(pool), jnp.ones((len(pool),), bool))
    return st


def _scenarios(n: int, epochs: int, batch: int, seed: int):
    """The three drift adversaries at a shared pool size; transition
    cadence sized so each regime holds long enough to recover in."""
    return [
        wl.rotating_hotset_workload(n, epochs, batch, period=5,
                                    seed=seed),
        wl.flash_crowd_workload(n, epochs, batch, onset=3, duration=5,
                                seed=seed),
        wl.diurnal_zipf_workload(n, epochs, batch, period=8, seed=seed),
    ]


def _recover_windows(transitions, epochs):
    """(transition, window-end) pairs: recovery is judged inside each
    regime, before the next shift re-perturbs the loop."""
    ts = [t for t in transitions if t < epochs]
    return [(t, (ts[i + 1] if i + 1 < len(ts) else epochs))
            for i, t in enumerate(ts)]


def _time_to_recover(spill_rate, t, end, k):
    """Epochs from transition ``t`` until spill first returns under
    ``SPILL_OK`` (capped at ``min(end, t+k+1)``); -1 = did not."""
    for e in range(t, min(end, t + k + 1)):
        if spill_rate[e] <= SPILL_OK:
            return e - t
    return -1


def _traj(spl, occ, batch):
    spill_rate = (np.asarray(spl) / batch).tolist()
    shares = [rc.max_share(o) for o in np.asarray(occ)]
    ginis = [rc.routing_gini(o) for o in np.asarray(occ)]
    return spill_rate, shares, ginis


def _run_variants(drift, st, plane_r, plane_s, mesh, controller_only=False):
    """Race the three routing policies over one drift stream; every
    variant starts from the same state/plane."""
    kd = jnp.asarray(drift.kinds)
    ks = jnp.asarray(drift.keys)
    up = jnp.asarray(drift.upd)
    common = dict(aggregate=True, plane_search=True)
    cfg, c0 = rc.init_controller(N_DEV)
    t0 = time.perf_counter()
    _, _, res_on, plen_on, _, spl_on, occ_on, states = \
        rc.run_serving_controlled(st, plane_s, kd, ks, up, mesh=mesh,
                                  cfg=cfg, state=c0, **common)
    on = dict(spl=spl_on, occ=occ_on, res=res_on, plen=plen_on,
              state=states[-1], states=states, cfg=cfg,
              wall_s=time.perf_counter() - t0)
    if controller_only:
        return on, None, None
    t0 = time.perf_counter()
    out_l = sx.run_serving(st, plane_s, kd, ks, up, mesh=mesh,
                           split="lanes", **common)
    off = dict(spl=out_l[5], occ=out_l[6], res=out_l[2], plen=out_l[3],
               wall_s=time.perf_counter() - t0)
    t0 = time.perf_counter()
    out_m = sx.run_serving(st, plane_s, kd, ks, up, mesh=mesh,
                           split="mass", **common)
    mass = dict(spl=out_m[5], occ=out_m[6], res=out_m[2], plen=out_m[3],
                wall_s=time.perf_counter() - t0)
    return on, off, mass


# ---------------------------------------------------------------------------
# --parity: the recovery battery (CI gate)
# ---------------------------------------------------------------------------

def run_parity(width=1024, batch=512, epochs=12, seed=7):
    n = int(width * 0.75)
    cap, L = width + 2, 12
    assert len(jax.devices()) >= N_DEV, \
        f"forced host mesh absent: {len(jax.devices())} device(s)"
    mesh = make_auto_mesh((1, N_DEV), ("data", "model"))
    k_bound = len(rc.default_slack_ladder(N_DEV))
    print(f"drift parity: w={width} B={batch} E={epochs} shards={N_DEV} "
          f"recovery bound K={k_bound} mode={kops.exec_mode()}")

    for drift in _scenarios(n, epochs, batch, seed):
        st = _seed(drift.populate, cap, L)
        plane_r = dix.from_state_device(st, n_levels=L, width=width)
        plane_s = shd.shard_index_plane(plane_r, mesh)
        on, off, _ = _run_variants(drift, st, plane_r, plane_s, mesh)

        # (1) bit-identity with the meshless replicated loop
        ref = sx.run_serving(st, plane_r, jnp.asarray(drift.kinds),
                             jnp.asarray(drift.keys),
                             jnp.asarray(drift.upd),
                             aggregate=True, plane_search=True)
        assert (np.asarray(on["res"]) == np.asarray(ref[2])).all(), \
            f"{drift.name}: controlled results diverged from replicated"
        assert (np.asarray(on["plen"]) == np.asarray(ref[3])).all(), \
            f"{drift.name}: controlled path lengths diverged"

        sr_on, sh_on, _ = _traj(on["spl"], on["occ"], batch)
        sr_off, _, _ = _traj(off["spl"], off["occ"], batch)
        wins = _recover_windows(drift.transitions, epochs) or \
            [(0, epochs)]
        ttr_on = [_time_to_recover(sr_on, t, e, k_bound)
                  for t, e in wins]
        ttr_off = [_time_to_recover(sr_off, t, e, k_bound)
                   for t, e in wins]
        # (2) controller recovers inside the structural bound, always
        assert all(0 <= d <= k_bound for d in ttr_on), \
            f"{drift.name}: controller-on missed the recovery bound " \
            f"(ttr={ttr_on}, spill={sr_on})"
        # (3) the static baseline genuinely fails somewhere
        assert any(d < 0 for d in ttr_off), \
            f"{drift.name}: controller-off also recovered everywhere " \
            f"(ttr={ttr_off}) — scenario is not an adversary"
        print(f"  {drift.name:16s} ttr on={ttr_on} off={ttr_off} "
              f"peak_share={max(sh_on):.2f} "
              f"retraces={on['state'].retraces} "
              f"escalations={on['state'].escalations}")

    # (4) hysteresis: a drift-free balanced stream never actuates.
    # NOTE the pool must FILL the plane width: a partially-occupied
    # packed plane leaves the last equal-lane shard mostly pads, which
    # is a genuine imbalance (one shard idle) the controller rightly
    # escalates on — balance here means balanced lanes, not just a
    # balanced key distribution
    rng = np.random.default_rng(seed)
    n_full = width
    pool = np.sort(rng.choice(4 * n_full, n_full,
                              replace=False)).astype(np.int32)
    st = _seed(pool, cap, L)
    plane_r = dix.from_state_device(st, n_levels=L, width=width)
    plane_s = shd.shard_index_plane(plane_r, mesh)
    E = 6
    keys = pool[rng.integers(0, n_full, (E, batch))].astype(np.int32)
    calm = wl.DriftStream(np.zeros((E, batch), np.int32), keys,
                          rng.random((E, batch)) < 0.1, pool, (), "calm")
    on, _, _ = _run_variants(calm, st, plane_r, plane_s, mesh,
                             controller_only=True)
    assert on["state"].retraces == 0 and on["state"].escalations == 0, \
        f"steady state actuated: {on['state']}"
    assert int(np.asarray(on["spl"]).sum()) == 0
    print(f"  steady-state: 0 retraces, 0 escalations over {E} epochs")
    print("drift parity OK")


# ---------------------------------------------------------------------------
# --bench: acceptance-shape race -> BENCH_kernels.json
# ---------------------------------------------------------------------------

def run_bench(width=4096, nq=8192, epochs=10, seed=7):
    n = int(width * 0.75)
    cap, L = width + 2, 14
    mesh = make_auto_mesh((1, N_DEV), ("data", "model"))
    k_bound = len(rc.default_slack_ladder(N_DEV))
    out = {"width": width, "batch": nq, "epochs": epochs,
           "shards": N_DEV, "recovery_bound_epochs": k_bound,
           "spill_ok": SPILL_OK, "exec_mode": kops.exec_mode(),
           "scenarios": {}}
    for drift in _scenarios(n, epochs, nq, seed):
        st = _seed(drift.populate, cap, L)
        plane_r = dix.from_state_device(st, n_levels=L, width=width)
        plane_s = shd.shard_index_plane(plane_r, mesh)
        on, off, mass = _run_variants(drift, st, plane_r, plane_s, mesh)
        wins = _recover_windows(drift.transitions, epochs) or \
            [(0, epochs)]
        row = {"transitions": list(drift.transitions)}
        for tag, v in (("controller", on), ("static_lanes", off),
                       ("static_mass", mass)):
            sr, sh, gi = _traj(v["spl"], v["occ"], nq)
            row[tag] = {
                "spill_rate": [round(x, 5) for x in sr],
                "max_share": [round(x, 4) for x in sh],
                "gini": [round(x, 4) for x in gi],
                "time_to_recover": [
                    _time_to_recover(sr, t, e, k_bound)
                    for t, e in wins],
                "peak_spill_rate": round(max(sr), 5),
                # transition epoch itself spikes identically for every
                # policy (the shock lands before anyone can act); judge
                # balance from the first epoch a policy could respond
                "peak_share_post": round(max(
                    (sh[e] for t, end in wins
                     for e in range(min(t + 1, end), end)),
                    default=max(sh)), 4),
                "wall_s": round(v["wall_s"], 2),
            }
        row["controller"]["retraces"] = on["state"].retraces
        row["controller"]["escalations"] = on["state"].escalations
        row["controller"]["final_slack"] = \
            on["state"].slack_of(on["cfg"])
        row["controller"]["final_split"] = on["state"].split
        out["scenarios"][drift.name] = row
        print(f"# {drift.name}: on ttr={row['controller']['time_to_recover']} "
              f"off ttr={row['static_lanes']['time_to_recover']} "
              f"share on/off/mass="
              f"{row['controller']['peak_share_post']:.2f}/"
              f"{row['static_lanes']['peak_share_post']:.2f}/"
              f"{row['static_mass']['peak_share_post']:.2f}",
              file=sys.stderr)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--parity", action="store_true")
    ap.add_argument("--bench", action="store_true")
    ap.add_argument("--width", type=int, default=4096)
    ap.add_argument("--nq", type=int, default=8192)
    ap.add_argument("--epochs", type=int, default=10)
    args = ap.parse_args(argv)
    if args.parity:
        run_parity()
    if args.bench:
        print(json.dumps(run_bench(width=args.width, nq=args.nq,
                                   epochs=args.epochs)))
    if not (args.parity or args.bench):
        ap.error("pass --parity and/or --bench")


if __name__ == "__main__":
    main()

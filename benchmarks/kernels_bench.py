"""Kernel micro-benchmarks (CPU: interpret-mode correctness path; the
derived columns carry the structural metrics that transfer to TPU).

Times the tiered splay-search pipeline (per-row streaming +
two-stage compare-and-count, DESIGN.md §5.2) on Zipf query batches,
checked against the ``kernels/ref.py`` oracle, measures the
batched-update aggregation (one weighted fold per unique key), and races
the refresh paths (DESIGN.md §5.3): host ``level_arrays.refresh`` (state
download + numpy argsort + plane re-upload) vs the device-resident
``device_index.refresh_device`` (searchsorted merge, zero host bytes) on
membership-changing and height-only epochs, plus the width-sharded
refresh (``refresh_device_sharded``) against the replicated one on a
forced 1x4 host mesh (subprocess probe, DESIGN.md §5.4) and the
routed width-sharded search (``splay_search_sharded`` — the all_to_all
query exchange on the mass-split plane, plus the replicate-and-mask
trace) against the replicated tiered search and the
gather-to-replicated dispatch on the same mesh (subprocess probe,
DESIGN.md §5.5–§5.6).

Emits the usual CSV lines AND returns a machine-readable payload which
``benchmarks/run.py`` writes to ``BENCH_kernels.json`` (op/s, per-level
bytes-touched model, config) so the perf trajectory is tracked across
PRs.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core import device_index as dix
from repro.core import level_arrays as la
from repro.core import splaylist as sx
from repro.core import workload as wl
from repro.kernels import ops
from repro.kernels.ref import splay_search_ref

ALPHAS = (0.6, 1.0, 1.4)


def _zipf_case(width: int, alpha: float, nq: int, seed: int = 0):
    keys, heights, qs = wl.zipf_level_fixture(width, alpha, nq, seed)
    return la.build(keys, heights, min_levels=6), qs


def _time(fn, reps: int) -> float:
    out = fn()
    out[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
        out[0].block_until_ready()
    return (time.perf_counter() - t0) / reps


def _bytes_model(L: la.LevelArrays, query_block: int, nq: int) -> dict:
    """Per-level bytes-touched estimate for one full batch of nq queries.

    tiered kernel: one level row (padded to whole 128-lane chunks)
    streams per (query block, live level); statically-empty rows are
    aliased away by the fetch schedule; a query costs W/128 sample
    compares, a one-hot chunk fetch and 128 chunk compares per row.
    """
    from repro.kernels import splay_search as ssk
    n_levels, width = L.keys.shape
    itemsize = 4
    q_blocks = max(nq // query_block, 1)
    live = int((L.widths > 0).sum())
    per_level_bytes = [int(width * itemsize) for _ in range(n_levels)]
    tiered_streamed = q_blocks * ssk.tiered_row_bytes(live, width)
    return {
        "n_levels": n_levels,
        "width": width,
        "live_levels": live,
        "per_level_row_bytes": per_level_bytes,
        "tiered_vmem_resident_bytes": ssk.tiered_row_bytes(1, width),
        "tiered_streamed_bytes_per_batch": tiered_streamed,
        "tiered_compares_per_query":
            int(n_levels * (-(-width // ssk.CHUNK) + ssk.CHUNK)),
    }


def _aggregation_case(quick: bool) -> dict:
    """Duplicate-heavy batch through run_contains_batch with and without
    aggregation: folds collapse to the unique-key count, results match."""
    rng = np.random.default_rng(1)
    n_keys = 64 if quick else 256
    B = 512 if quick else 2048
    pool = np.arange(0, 2 * n_keys, 2, dtype=np.int32)
    st = sx.make(capacity=2 * n_keys + 8, max_level=16)
    st, _, _ = sx.run_ops(
        st, jnp.full((n_keys,), sx.OP_INSERT, jnp.int32),
        jnp.asarray(pool), jnp.ones((n_keys,), bool))
    hot = pool[: max(n_keys // 16, 1)]
    qs = np.where(rng.random(B) < 0.8, rng.choice(hot, B),
                  rng.choice(pool, B)).astype(np.int32)
    coins = rng.random(B) < 0.75
    n_folds_serial = int(coins.sum())
    n_folds_agg = len(np.unique(qs[coins]))

    t_ser = _time(lambda: sx.run_contains_batch(
        st, jnp.asarray(qs), jnp.asarray(coins))[1:], reps=3)
    t_agg = _time(lambda: sx.run_contains_batch(
        st, jnp.asarray(qs), jnp.asarray(coins), aggregate=True)[1:],
        reps=3)
    _, res_s, _ = sx.run_contains_batch(st, jnp.asarray(qs),
                                        jnp.asarray(coins))
    _, res_a, _ = sx.run_contains_batch(st, jnp.asarray(qs),
                                        jnp.asarray(coins), aggregate=True)
    assert (np.asarray(res_s) == np.asarray(res_a)).all()
    emit("batch_update_aggregation", t_agg / B * 1e6,
         f"folds_serial={n_folds_serial};folds_agg={n_folds_agg};"
         f"speedup={t_ser / t_agg:.2f}")
    return {
        "batch": B,
        "unique_update_keys": n_folds_agg,
        "folds_serialized": n_folds_serial,
        "folds_aggregated": n_folds_agg,
        "us_per_op_serialized": t_ser / B * 1e6,
        "us_per_op_aggregated": t_agg / B * 1e6,
        "speedup": t_ser / t_agg,
    }


def _synth_state(keys: np.ndarray, rel_h: np.ndarray, capacity: int,
                 max_level: int = 8) -> sx.SplayState:
    """SplayState with exactly the fields the refresh paths read (key,
    top, deleted, zl, n_alloc) populated — the list links/counters are
    irrelevant to the index plane, so epochs can be synthesized directly
    at benchmark widths instead of replaying op streams."""
    st = sx.make(capacity, max_level=max_level)
    n = len(keys)
    key = np.full((capacity,), sx.POS_INF_32, np.int32)
    key[0] = sx.NEG_INF_32
    key[2:2 + n] = keys
    top = np.zeros((capacity,), np.int32)
    top[2:2 + n] = rel_h
    top[0] = top[1] = max_level
    return st._replace(
        key=jnp.asarray(key), top=jnp.asarray(top),
        zl=jnp.array(0, jnp.int32),
        n_alloc=jnp.array(n + 2, jnp.int32))


def _time_min(fn, reps: int) -> float:
    """Min-of-reps wall clock (the refresh race runs at millisecond
    scale where scheduler noise dominates a mean)."""
    fn()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _refresh_case(width: int, churn: int, epochs: int, reps: int,
                  seed: int = 2) -> dict:
    """Race the refresh paths over a stream of rebalance epochs.

    ``churn`` keys are deleted and ``churn`` inserted per epoch (the
    membership-changing case: host pays ``to_numpy`` + argsort + a full
    rectangle re-upload; the device path folds the change with a
    top_k/searchsorted merge).  ``churn=0`` is the height-only epoch
    (host has its permuted fast path — the device path's merge
    degenerates to the identity).  Epochs evolve ONE state the way the
    engine does — mark-delete in place, bump-allocate inserts — so the
    plane's slot map stays live-valid across epochs, as in serving
    (only ``rebuild`` compacts slots).  Both paths are asserted
    bit-identical on the final plane."""
    rng = np.random.default_rng(seed)
    n_levels, hmax = 6, 5
    n0 = int(width * 0.9)
    capacity = n0 + epochs * churn + 16
    space = rng.permutation(20 * width).astype(np.int32)
    slot_keys = space[:n0].copy()          # key of slot 2 + i (bump order)
    deleted = np.zeros(n0, bool)
    states = []
    for _ in range(epochs + 1):
        if states and churn:               # epoch 0 is the base state
            live = np.nonzero(~deleted)[0]
            deleted[rng.choice(live, churn, replace=False)] = True
            fresh = space[len(slot_keys):len(slot_keys) + churn]
            slot_keys = np.concatenate([slot_keys, fresh])
            deleted = np.concatenate([deleted, np.zeros(churn, bool)])
        h = rng.integers(0, hmax + 1, len(slot_keys)).astype(np.int32)
        st = _synth_state(slot_keys, h, capacity)
        st = st._replace(deleted=jnp.asarray(
            np.concatenate([np.zeros(2, bool), deleted,
                            np.zeros(capacity - 2 - len(deleted), bool)])))
        states.append(st)

    prev_h0 = la.from_state(states[0], min_levels=n_levels, width=width)
    prev_d0 = dix.from_state_device(states[0], n_levels=n_levels,
                                    width=width)
    max_new = max(2 * churn, 64)

    def host_fold():
        prev = prev_h0
        up = None
        for st in states[1:]:
            prev = la.refresh(st, prev)
            # the serving loop consumes the plane on device: include the
            # re-upload the host path forces every epoch
            up = tuple(jnp.asarray(x) for x in
                       (prev.keys, prev.widths, prev.heights))
        up[0].block_until_ready()
        return up

    def dev_fold():
        p = prev_d0
        for st in states[1:]:
            p = dix.refresh_device(st, p, max_new=max_new)
        p.keys.block_until_ready()
        return p

    t_host = _time_min(host_fold, reps) / epochs
    t_dev = _time_min(dev_fold, reps) / epochs

    # correctness: final planes bit-identical (device vs host vs scratch)
    final_h = host_fold()
    final_d = dev_fold()
    ref = la.from_state(states[-1], min_levels=n_levels, width=width)
    assert (np.asarray(final_d.keys) == ref.keys).all()
    assert (np.asarray(final_d.widths) == ref.widths).all()
    assert (np.asarray(final_h[0]) == np.asarray(final_d.keys)).all()

    itemsize = 4
    C, L1 = states[0].key.shape[0], states[0].max_level + 1
    state_download = (2 * L1 * C + 5 * C) * itemsize   # to_numpy: all fields
    plane_upload = (n_levels * width + width + n_levels) * itemsize
    mode = "membership" if churn else "height_only"
    emit(f"refresh_{mode}_w{width}", t_dev * 1e6,
         f"host_us={t_host * 1e6:.1f};speedup={t_host / t_dev:.2f};"
         f"churn={churn}")
    return {
        "mode": mode, "width": width, "n_levels": n_levels,
        "churn_per_epoch": int(churn), "epochs": epochs,
        "epochs_per_sec_host": 1.0 / t_host,
        "epochs_per_sec_device": 1.0 / t_dev,
        "us_per_epoch_host": t_host * 1e6,
        "us_per_epoch_device": t_dev * 1e6,
        "speedup_device_over_host": t_host / t_dev,
        "host_bytes_moved_per_epoch": state_download + plane_upload,
        "device_bytes_moved_per_epoch": 0,
    }


def _sharded_search_case(width: int, nq: int) -> dict:
    """Sharded-vs-replicated search race on a forced host mesh
    (DESIGN.md §5.5–§5.6).  Same subprocess pattern as the refresh race
    (``benchmarks/sharded_search_probe.py --bench --routed`` asserts
    bit-identity across the dispatch seam — routed exchange, masked
    trace, gather dispatch, mass-split plane — and prints one JSON
    object).  The primary sharded number is the routed all_to_all
    exchange on the mass-split plane (the shipped default for skewed
    serving); host-mesh wall clock measures collective/dispatch
    overhead, and the structural columns (per-shard resident bytes,
    O(nq·slack) exchange wire, routing balance, spill rate) are what
    transfers."""
    env = _probe_env()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "benchmarks/sharded_search_probe.py",
         "--bench", "--routed", "--width", str(width), "--nq", str(nq)],
        capture_output=True, text=True, env=env, cwd=repo, timeout=1200)
    assert r.returncode == 0, f"probe failed:\n{r.stdout}\n{r.stderr}"
    out = json.loads(r.stdout.strip().splitlines()[-1])
    emit(f"search_sharded_w{width}", out["us_per_query_sharded"],
         f"replicated_us={out['us_per_query_replicated']:.3f};"
         f"shards={out['shards']};bit_identical={out['bit_identical']};"
         f"spill_rate={out['spill_rate_mass']:.3f};"
         f"max_share={out['routing_max_share']:.2f}"
         f"->{out['routing_max_share_mass']:.2f}(mass)")
    return out


def _ordered_case(width: int, nq: int) -> dict:
    """Ordered-operation race (DESIGN.md §5.10): ``range_scan`` on the
    replicated vs the routed mass-split sharded plane, and its
    bytes-touched model (rank-pair descent + ``max_range`` gathered
    lanes) against the naive full-gather baseline (ship the whole [W]
    bottom row per query).  Same subprocess pattern as the other mesh
    probes (``benchmarks/ordered_search_probe.py --bench`` asserts
    replicated/sharded bit-identity and prints one JSON object)."""
    env = _probe_env()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "benchmarks/ordered_search_probe.py",
         "--bench", "--width", str(width), "--nq", str(nq)],
        capture_output=True, text=True, env=env, cwd=repo, timeout=1200)
    assert r.returncode == 0, f"probe failed:\n{r.stdout}\n{r.stderr}"
    out = json.loads(r.stdout.strip().splitlines()[-1])
    emit(f"search_ordered_w{width}", out["us_per_scan_sharded"],
         f"replicated_us={out['us_per_scan_replicated']:.3f};"
         f"bytes_ratio={out['bytes_ratio_ours_over_naive']:.3f};"
         f"truncated={out['scans_truncated']};"
         f"bit_identical={out['bit_identical']}")
    return out


def _probe_env() -> dict:
    """Environment of a child probe: the CPU host-mesh batteries force
    their own device count, and ``JAX_PLATFORMS=cpu`` keeps them off the
    chip — this process may hold it, and a chip serves one process."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


def _drift_case(width: int, nq: int, epochs: int = 10) -> dict:
    """Routing-controller drift race (DESIGN.md §5.7): controller-on vs
    static-lanes vs static-mass through the three drift scenarios
    (rotating hot set, flash crowd, diurnal Zipf mixture) at the
    acceptance shape, 1x4 host mesh.  The probe
    (``benchmarks/drift_probe.py --bench``) prints one JSON object with
    per-epoch spill/max-share/gini trajectories and per-transition
    time-to-recover; the headline per scenario is the controller's
    worst recovery time against the static baseline's."""
    env = _probe_env()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "benchmarks/drift_probe.py", "--bench",
         "--width", str(width), "--nq", str(nq),
         "--epochs", str(epochs)],
        capture_output=True, text=True, env=env, cwd=repo, timeout=3600)
    assert r.returncode == 0, f"drift probe failed:\n{r.stdout[-2000:]}" \
                              f"\n{r.stderr[-2000:]}"
    out = json.loads(r.stdout.strip().splitlines()[-1])
    for name, row in out["scenarios"].items():
        ttr_on = row["controller"]["time_to_recover"]
        ttr_off = row["static_lanes"]["time_to_recover"]
        emit(f"drift_{name}", max(ttr_on, default=0),
             f"ttr_static={ttr_off};"
             f"share_on={row['controller']['peak_share_post']:.2f};"
             f"share_static={row['static_lanes']['peak_share_post']:.2f};"
             f"retraces={row['controller']['retraces']}")
    return out


def _serving_case(n_requests: int) -> dict:
    """Serving engine end-to-end on the device index plane (DESIGN.md
    §5.9): the offered-load sweep (``benchmarks/serving_probe.py
    --bench``, 1x4 host mesh) — Poisson/Zipf arrivals through the
    continuous-batching engine with the routed sharded search answering
    session lookups and the route controller in the loop.  Prints one
    JSON object with p50/p99 request latency (decode-step units),
    tokens/sec, index-plane query share, steady-state spill rate, the
    backpressure counters, and the host-vs-device bit-identity flag CI
    gates on."""
    env = _probe_env()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "benchmarks/serving_probe.py", "--bench",
         "--requests", str(n_requests)],
        capture_output=True, text=True, env=env, cwd=repo, timeout=3600)
    assert r.returncode == 0, f"serving probe failed:" \
                              f"\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    out = json.loads(r.stdout.strip().splitlines()[-1])
    emit("serving_engine", out["p99_latency_steps"],
         f"p50={out['p50_latency_steps']};"
         f"tok_s={out['tokens_per_sec']};"
         f"plane_share={out['index_plane_share']:.2f};"
         f"spill={out['steady_state_spill_rate']:.4f};"
         f"parity={out['parity_bit_identical']}")
    return out


def _chaos_case() -> dict:
    """Chaos-injection recovery battery (DESIGN.md §5.11):
    ``benchmarks/chaos_probe.py --bench`` in a subprocess (forced 1x4
    host mesh) — plane-fsck detection per fault family, zero-wrong-
    verdict degraded serving with bounded recovery, crash-consistent
    snapshot replay, and cross-backend restore bit-identity.  CI gates
    on detected==injected, wrong_verdicts==0, recovery within bound,
    and the restore/replay flags."""
    env = _probe_env()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "benchmarks/chaos_probe.py", "--bench"],
        capture_output=True, text=True, env=env, cwd=repo, timeout=3600)
    assert r.returncode == 0, f"chaos probe failed:" \
                              f"\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    out = json.loads(r.stdout.strip().splitlines()[-1])
    emit("chaos_recovery", out["recovery_epochs_max"],
         f"detected={out['detected']}/{out['injected']};"
         f"wrong={out['wrong_verdicts']};"
         f"restore_ok={out['restore_bit_identical']};"
         f"replay_once={out['replay_exactly_once']}")
    return out


def _sharded_refresh_case(width: int) -> dict:
    """Sharded-vs-replicated refresh race on a forced host mesh
    (DESIGN.md §5.4).  The mesh needs
    ``--xla_force_host_platform_device_count`` before jax initializes,
    so the race runs in a subprocess
    (``benchmarks/sharded_refresh_probe.py --bench``) that asserts
    bit-identity and prints one JSON object.  Host-mesh wall clock
    measures collective overhead, not accelerator scaling — the
    structural columns (per-shard lanes/bytes) are what transfers."""
    env = _probe_env()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "benchmarks/sharded_refresh_probe.py",
         "--bench", "--width", str(width)],
        capture_output=True, text=True, env=env, cwd=repo, timeout=1200)
    assert r.returncode == 0, f"probe failed:\n{r.stdout}\n{r.stderr}"
    out = json.loads(r.stdout.strip().splitlines()[-1])
    emit(f"refresh_sharded_w{width}", out["us_per_epoch_sharded"],
         f"replicated_us={out['us_per_epoch_replicated']:.1f};"
         f"shards={out['shards']};bit_identical={out['bit_identical']}")
    return out


def run(quick: bool = False) -> dict:
    width = 4096 if quick else 8192
    nq = 1024 if quick else 4096
    qb = 256
    reps = 3 if quick else 5

    # the execution-mode label follows the actual backend (the kernels
    # run compiled on TPU, interpret elsewhere) — shared helper so every
    # probe derives it the same way
    mode = ops.exec_mode()
    payload = {
        "bench": "kernels",
        "config": {"width": width, "nq": nq, "query_block": qb,
                   "alphas": list(ALPHAS), "quick": quick,
                   "mode": mode},
        "zipf_search": [],
    }
    for alpha in ALPHAS:
        L, qs = _zipf_case(width, alpha, nq, seed=int(alpha * 10))
        lvk = jnp.asarray(L.keys)
        w = jnp.asarray(L.widths)
        qsj = jnp.asarray(qs)
        dt_tier = _time(lambda: ops.splay_search(
            lvk, qsj, query_block=qb, widths=w), reps)
        out_t = ops.splay_search(lvk, qsj, query_block=qb, widths=w)
        for a, b in zip(out_t, splay_search_ref(lvk, qsj)):
            assert (np.asarray(a) == np.asarray(b)).all()
        _, _, lv = out_t
        mean_lv = float(jnp.mean(lv))
        emit(f"kernel_splay_search_tiered_a{alpha}", dt_tier / nq * 1e6,
             f"mean_level={mean_lv:.2f}")
        payload["zipf_search"].append({
            "alpha": alpha,
            "ops_per_sec_tiered": nq / dt_tier,
            "us_per_query_tiered": dt_tier / nq * 1e6,
            "mean_level_found": mean_lv,
        })
    payload["bytes_model"] = _bytes_model(L, qb, nq)
    payload["aggregation"] = _aggregation_case(quick)
    # refresh-path race (DESIGN.md §5.3): membership-changing epochs are
    # the acceptance case (device merge vs host argsort + round-trip);
    # height-only epochs race the two fast paths.  Always measured at
    # width 4096 (the acceptance point); full mode adds the wide pair.
    r_epochs = 4 if quick else 8
    r_reps = 6 if quick else 8
    payload["refresh_path"] = [
        _refresh_case(4096, churn=64, epochs=r_epochs, reps=r_reps),
        _refresh_case(4096, churn=0, epochs=r_epochs, reps=r_reps),
    ]
    if not quick:
        payload["refresh_path"] += [
            _refresh_case(width, churn=64, epochs=r_epochs, reps=r_reps),
            _refresh_case(width, churn=0, epochs=r_epochs, reps=r_reps),
        ]
    # sharded-vs-replicated refresh race (DESIGN.md §5.4), 1x4 host mesh
    payload["refresh_sharded"] = _sharded_refresh_case(
        1024 if quick else 4096)
    # routed sharded-vs-replicated search race (DESIGN.md §5.5–§5.6),
    # 1x4 host mesh — always at the acceptance point (width 4096,
    # nq 8192: the batch must be large enough to amortize the host
    # mesh's fixed per-collective overhead, or the ratio gate in CI
    # measures dispatch noise instead of the exchange)
    payload["search_sharded"] = _sharded_search_case(4096, 8192)
    # ordered-op suite (DESIGN.md §5.10): range_scan replicated vs
    # routed mass-split sharded + the bytes race against the naive
    # full-gather model — gated in CI from this entry
    payload["search_ordered"] = _ordered_case(
        1024 if quick else 2048, 1024 if quick else 2048)
    # closed-loop routing controller through the drift scenarios
    # (DESIGN.md §5.7), also at the acceptance point — the recovery
    # bound (<=1% spill within K epochs of every transition) is gated
    # in CI against this entry
    payload["routing_controller"] = _drift_case(4096, 8192)
    # the serving engine end-to-end on the routed device plane
    # (DESIGN.md §5.9): request-level latency under offered load, with
    # the parity flag and steady-state spill gated in CI
    payload["serving_engine"] = _serving_case(8 if quick else 16)
    # fault-injection recovery (DESIGN.md §5.11): fsck detection,
    # zero-wrong-verdict degradation, crash-consistent restore — the
    # CI "Chaos recovery" gate reads this entry
    payload["chaos_recovery"] = _chaos_case()

    # hot_gather: bytes-touched model (hot hits avoid HBM entirely); the
    # hot set comes from observed counts, as the splay heights do
    rng = np.random.default_rng(0)
    v, h, d = width, 2048, 512
    from repro.core.workload import zipf_token_ids
    warm = zipf_token_ids(rng, v, (8 * nq,))
    counts = np.bincount(warm.ravel(), minlength=v)
    hot_rank = np.full(v, -1, np.int32)
    hot_ids = np.argsort(-counts)[:h]
    hot_rank[hot_ids] = np.arange(h)
    ids = zipf_token_ids(rng, v, (nq,))
    hit = float(np.mean(hot_rank[ids] >= 0))
    emit("kernel_hot_gather_model", 0.0,
         f"zipf_hot_hit={hit:.2f};hbm_bytes_saved={hit:.2f}")
    payload["hot_gather_model"] = {
        "vocab": v, "hot_rows": h, "dim": d, "zipf_hot_hit": hit,
        "hbm_bytes_flat": nq * d * 2,
        "hbm_bytes_tiered": int((1 - hit) * nq * d * 2),
    }
    return payload


if __name__ == "__main__":
    out = run(quick=True)
    with open("BENCH_kernels.json", "w") as fh:
        json.dump(out, fh, indent=2)
    print(json.dumps(out["zipf_search"], indent=2))

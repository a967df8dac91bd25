"""Serving probe: the splay engine end-to-end on the device index plane.

Self-contained subprocess target (forces
``--xla_force_host_platform_device_count`` *before* importing jax),
mirroring ``drift_probe.py``:

  python benchmarks/serving_probe.py --parity      # CI gate battery
  python benchmarks/serving_probe.py --bench       # JSON to stdout

``--parity`` (the CI "Serving parity + bench" step) asserts the
DESIGN.md §5.9 exactness contract at small shapes:

  (1) **pool trace differential** — the device-indexed
      :class:`PagedKVPool` replays a recorded request trace
      (``core.workload.kv_request_trace``: create/lookup/release
      interleavings with re-used seq_ids, double-creates, and absent
      lookups/releases) bit-identically to the host ``SplayList`` pool,
      meshless AND on a forced 1x4 host mesh (routed sharded search,
      route controller in the loop);
  (2) **engine end-to-end bit-identity** — host-indexed vs
      device-indexed (meshless and 1x4 mesh) ``Engine`` runs on the
      same Poisson/Zipf arrival stream produce identical outputs,
      latencies, admission stalls, and preemptions (greedy decode makes
      the whole serving trajectory deterministic);
  (3) **page-exhaustion backpressure** — a pool sized below the offered
      load forces admission stalls and mid-decode preemptions, which
      must fire identically in both index modes and every preempted
      request must still complete.

Exits nonzero on any violation; prints ``SERVING PARITY OK``.

``--bench`` sweeps offered load (Poisson arrival rates) through the
device-indexed engine on the 1x4 mesh and prints one JSON object with
p50/p99 request latency (virtual decode-step units), wall-clock
tokens/sec, the index-plane query share, the spill/occupancy
trajectory, steady-state spill rate, and the backpressure counters —
consumed by ``benchmarks/kernels_bench.py`` into the ``serving_engine``
entry of ``BENCH_kernels.json``.
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
import numpy as np                                     # noqa: E402

from repro.configs import registry                     # noqa: E402
from repro.core import workload as wl                  # noqa: E402
from repro.kernels import ops as kops                  # noqa: E402
from repro.models import model_zoo as zoo              # noqa: E402
from repro.serve.engine import Engine, Request         # noqa: E402
from repro.serve.kv_cache import PagedKVPool           # noqa: E402
from repro.launch.mesh import make_auto_mesh           # noqa: E402

SPILL_OK = 0.01
ARCH = "qwen2-0.5b"


def _mesh():
    assert len(jax.devices()) >= N_DEV, \
        f"forced host mesh absent: {len(jax.devices())} device(s)"
    return make_auto_mesh((1, N_DEV), ("data", "model"))


def _replay_trace(pool: PagedKVPool, trace: wl.KVTrace,
                  max_range: int = 6):
    """Replay a recorded request trace; returns the full observable
    record (per-op verdicts + pool accounting) for differential
    comparison.  Scan-flavored traces (``core.workload.kv_scan_trace``)
    add ordered queries: ``KV_SCAN`` session-range lookups
    (``pool.lookup_range`` — ids, full count, counted truncation) and
    ``KV_PRED`` predecessor queries, exercising the pool as an ordered
    index (DESIGN.md §5.10)."""
    log = []
    for t in range(len(trace.kinds)):
        k, s = int(trace.kinds[t]), int(trace.seq_ids[t])
        if k == wl.KV_CREATE:
            ok = pool.create(s)
            if ok:
                ok = pool.append_tokens(s, 3) and ok
            log.append(("c", s, ok))
        elif k == wl.KV_LOOKUP:
            chain = pool.lookup(s)
            log.append(("l", s, None if chain is None else tuple(chain)))
        elif k == wl.KV_SCAN:
            hi = int(trace.hi_ids[t])
            ids, cnt, tr = pool.lookup_range(s, hi, max_range=max_range)
            log.append(("s", s, hi, tuple(ids.tolist()), cnt, tr))
        elif k == wl.KV_PRED:
            log.append(("p", s, pool.predecessor(s)))
        else:
            pool.release(s)
            log.append(("r", s, pool.utilization))
    live = sorted(pool.chains)
    verdicts = pool.lookup_batch(live + [10 ** 6, 10 ** 6 + 1]).tolist()
    return log, live, verdicts, pool.utilization


def _build_engine(cfg, params, device, mesh=None, n_pages=64,
                  page_size=4, max_batch=4, index_width=64):
    return Engine(cfg, params, max_batch=max_batch, max_seq=64,
                  n_pages=n_pages, page_size=page_size,
                  device_index=device, index_batch=8,
                  index_width=index_width, mesh=mesh, stream_epochs=2)


def _submit(engine: Engine, arr: wl.ArrivalStream) -> None:
    for i in range(len(arr.seq_ids)):
        L = int(arr.prompt_lens[i])
        engine.submit(Request(
            seq_id=int(arr.seq_ids[i]), prompt=arr.prompts[i, :L].copy(),
            max_new=int(arr.max_new[i]), arrival=int(arr.arrival[i])))


def _engine_record(engine: Engine):
    t0 = time.perf_counter()
    results = engine.run()
    wall = time.perf_counter() - t0
    return {
        "results": {k: tuple(v) for k, v in results.items()},
        "latencies": dict(engine.latencies),
        "stalls": engine.stalls, "preemptions": engine.preemptions,
        "tokens_out": engine.tokens_out, "wall_s": wall,
        "pool_stats": dict(engine.pool.stats),
    }


# ---------------------------------------------------------------------------
# --parity: the exactness battery (CI gate)
# ---------------------------------------------------------------------------

def run_parity(seed=7):
    mesh = _mesh()
    print(f"  mode={kops.exec_mode()}")

    # (1) pool trace differential: host vs device, meshless + 1x4 mesh.
    # The scan-flavored traces interleave KV_SCAN/KV_PRED ordered
    # queries with the create/lookup/release churn, so the ordered-op
    # plane paths (OP_PRED epochs, range_scan gathers) replay against
    # the host oracle on the same mutating stream.
    traces = [wl.kv_request_trace(200, 24, seed=seed),
              wl.kv_request_trace(120, 6, seed=seed + 1),
              wl.kv_scan_trace(200, 24, seed=seed + 2),
              wl.kv_scan_trace(140, 8, seed=seed + 3, p_scan=0.4,
                               span=16)]
    for trace in traces:
        ref = _replay_trace(PagedKVPool(32, 4), trace)
        truncs = 0
        for tag, kw in (("meshless", {}), ("1x4-mesh", {"mesh": mesh})):
            pool = PagedKVPool(32, 4, device=True, index_width=64,
                               index_batch=8, **kw)
            got = _replay_trace(pool, trace)
            truncs = pool.stats["range_truncated"]
            if got != ref:
                diff = next(((a, b) for a, b in zip(ref[0], got[0])
                             if a != b), (ref[1:], got[1:]))
                raise AssertionError(
                    f"pool trace diverged ({trace.name} {tag}): "
                    f"first diff {diff}")
        n_ord = int(((trace.kinds == wl.KV_SCAN)
                     | (trace.kinds == wl.KV_PRED)).sum())
        extra = (f", {n_ord} ordered ops, truncated={truncs}"
                 if n_ord else "")
        print(f"  pool trace {trace.name}: host == device(meshless) "
              f"== device(1x4){extra}")
        if trace.name.startswith("kv_scan"):
            assert n_ord > 0, f"{trace.name} carried no ordered ops"

    # pool-level page exhaustion: partial reservation rolls nothing over
    tiny = PagedKVPool(2, 4, device=True, index_width=8, index_batch=4)
    assert tiny.create(0) and tiny.append_tokens(0, 8)   # both pages
    assert tiny.create(1)
    assert not tiny.append_tokens(1, 1), "expected page exhaustion"
    assert tiny.lookup_batch([0, 1, 2]).tolist() == [True, True, False]
    tiny.release(0)
    assert tiny.append_tokens(1, 1), "freed pages not reclaimed"
    print("  pool exhaustion + reclaim: OK")

    # (2)+(3) engine end-to-end: ample pool (no backpressure) and tight
    # pool (stalls + preemptions forced) — bit-identical across index
    # modes either way
    cfg = registry.get_smoke(ARCH)
    params, _ = zoo.build_params(cfg, jax.random.PRNGKey(0))
    arr = wl.poisson_zipf_arrivals(10, 0.4, cfg.vocab_padded,
                                   prompt_len=(2, 6), max_new=(3, 6),
                                   seed=seed)
    for label, n_pages in (("ample", 64), ("tight", 7)):
        recs = {}
        for tag, device, m in (("host", False, None),
                               ("dev", True, None),
                               ("dev-1x4", True, mesh)):
            e = _build_engine(cfg, params, device, mesh=m,
                              n_pages=n_pages)
            _submit(e, arr)
            recs[tag] = _engine_record(e)
            if tag != "host":
                st = recs[tag]["pool_stats"]
                assert st["plane_queries"] > 0, st
        for tag in ("dev", "dev-1x4"):
            for k in ("results", "latencies", "stalls", "preemptions",
                      "tokens_out"):
                assert recs[tag][k] == recs["host"][k], (
                    f"{label}/{tag} diverged on {k}: "
                    f"{recs[tag][k]} != {recs['host'][k]}")
        r = recs["host"]
        assert len(r["results"]) == 10, "requests lost"
        if label == "tight":
            assert r["stalls"] + r["preemptions"] > 0, \
                "tight pool exercised no backpressure"
        print(f"  engine {label:5s} (pages={n_pages}): host == dev == "
              f"dev-1x4; stalls={r['stalls']} "
              f"preemptions={r['preemptions']} "
              f"served={len(r['results'])}")

    print("SERVING PARITY OK")


# ---------------------------------------------------------------------------
# --bench: offered-load sweep -> BENCH_kernels.json
# ---------------------------------------------------------------------------

def run_bench(n_requests=12, rates=(0.15, 0.4, 1.0), seed=7):
    mesh = _mesh()
    cfg = registry.get_smoke(ARCH)
    params, _ = zoo.build_params(cfg, jax.random.PRNGKey(0))
    out = {"arch": ARCH, "shards": N_DEV, "n_requests": n_requests,
           "spill_ok": SPILL_OK, "exec_mode": kops.exec_mode(),
           "rates": {}}

    parity_ok = True
    for rate in rates:
        arr = wl.poisson_zipf_arrivals(n_requests, rate,
                                       cfg.vocab_padded,
                                       prompt_len=(2, 6),
                                       max_new=(4, 8), seed=seed)
        e = _build_engine(cfg, params, True, mesh=mesh, n_pages=10)
        _submit(e, arr)
        rec = _engine_record(e)
        pool = e.pool
        lat = np.sort(np.fromiter(rec["latencies"].values(), np.int64))
        spill = np.asarray(pool.spill_traj, np.float64)
        share = np.asarray(pool.share_traj, np.float64)
        tail = max(len(spill) // 2, 1)        # steady state = last half
        pq = max(rec["pool_stats"]["plane_queries"], 1)
        row = {
            "rate": rate,
            "served": len(rec["results"]),
            "p50_latency_steps": int(lat[len(lat) // 2]),
            "p99_latency_steps": int(lat[min(len(lat) - 1,
                                             int(len(lat) * 0.99))]),
            "tokens_per_sec": round(rec["tokens_out"] / rec["wall_s"], 2),
            "wall_s": round(rec["wall_s"], 2),
            "index_plane_share": round(
                rec["pool_stats"]["plane_queries"]
                / max(rec["pool_stats"]["lookups"], 1), 4),
            "spill_rate": round(float(spill.sum()) / pq, 5),
            "steady_state_spill_rate": round(
                float(spill[-tail:].sum())
                / max(pool.index_batch * tail, 1), 5),
            "max_share_mean": round(float(share.mean()), 4)
            if share.size else 0.0,
            "stalls": rec["stalls"], "preemptions": rec["preemptions"],
            "rebuilds": rec["pool_stats"]["rebuilds"],
        }
        out["rates"][str(rate)] = row
        print(f"# rate={rate}: p50={row['p50_latency_steps']} "
              f"p99={row['p99_latency_steps']} tok/s="
              f"{row['tokens_per_sec']} stalls={row['stalls']} "
              f"preempt={row['preemptions']}", file=sys.stderr)

    # the gate columns: parity re-checked at the middle rate, tail
    # metrics reported from the highest offered load
    mid = rates[len(rates) // 2]
    arr = wl.poisson_zipf_arrivals(n_requests, mid, cfg.vocab_padded,
                                   prompt_len=(2, 6), max_new=(4, 8),
                                   seed=seed)
    eh = _build_engine(cfg, params, False, n_pages=10)
    ed = _build_engine(cfg, params, True, mesh=mesh, n_pages=10)
    _submit(eh, arr)
    _submit(ed, arr)
    rh, rd = _engine_record(eh), _engine_record(ed)
    parity_ok = all(rh[k] == rd[k] for k in
                    ("results", "latencies", "stalls", "preemptions"))
    hi = out["rates"][str(rates[-1])]
    out.update({
        "parity_bit_identical": bool(parity_ok),
        "p50_latency_steps": hi["p50_latency_steps"],
        "p99_latency_steps": hi["p99_latency_steps"],
        "tokens_per_sec": hi["tokens_per_sec"],
        "index_plane_share": hi["index_plane_share"],
        "steady_state_spill_rate": hi["steady_state_spill_rate"],
        "backpressure_stalls": sum(r["stalls"]
                                   for r in out["rates"].values()),
        "backpressure_preemptions": sum(r["preemptions"]
                                        for r in out["rates"].values()),
    })
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--parity", action="store_true")
    ap.add_argument("--bench", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    args = ap.parse_args(argv)
    if args.parity:
        run_parity()
    if args.bench:
        print(json.dumps(run_bench(n_requests=args.requests)))
    if not (args.parity or args.bench):
        ap.error("pass --parity and/or --bench")


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""The splay index's chip benchmark: one cell, one seed, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration
(``bench/configs/<config>.json``: the deployment's key set and index
shape) and a traffic mix (``bench/traffic/<traffic>.json``: the op mix,
which generator ``bench/gen/<generator>.py`` draws it, and the serving
path flags).  One process does the whole run:

  1. set-up: the compile cache at a fixed path in the checkout, the key
     set and a prior read history from ``--seed``, the bulk load
     (``bench/load.py``), and a few untimed batches of the cell's own
     traffic, so that every program the window runs is compiled;
  2. window: a closed loop of ``B`` clients, each with one op
     outstanding.  Each batch of ``B`` ops goes as host arrays
     ``[1, B]`` to ``splaylist.run_serving``; its answers are read back
     on the host; ``state`` and ``plane`` carry into the next call;
  3. check: every answer, the final live key set, the live keys' hit
     counters and the plane's bottom row against ``bench/reference.py``,
     after the window;
  4. output: the numbers compared, each beside its limit, as the last
     lines of stderr; the result as one JSON line, last on stdout.

``--trace 0`` reports the cell's end-to-end metrics.  ``--trace 1``
runs the window under the profiler, for at most ``TRACED_BATCHES``
batches, and reports the per-layer metrics, each read by
``bench/metrics/<name>.py`` from the reduced trace
(``bench/trace_reduce.py``) and the run's own counts.

Without a TPU, with fewer chips than the cell asks for, with Pallas
kernels not compiled for the TPU, or with a device kind missing from
``bench/peaks.json``, the run prints no result and exits 2.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import load, reference, trace_reduce  # noqa: E402
from repro.core import splaylist as sx  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402

WARMUP_BATCHES = 2
# The traced run's window ends after this many batches (or --seconds),
# so that the trace stays small; the plane entering each traced batch is
# kept for descent_hbm_roofline.
TRACED_BATCHES = 8
# Answers, final key set, hit counters and bottom row are exact: any
# mismatch fails.
LIMITS = {"answer_mismatches": 0, "key_set_mismatches": 0,
          "hit_count_mismatches": 0, "bottom_row_mismatches": 0,
          "overflow_batches": 0}


class NoChip(Exception):
    """The run cannot measure here: no result is printed."""


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(spec: dict, name: str):
    """``(workload entry, configuration, traffic)`` of cell ``name``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = read_json(ROOT, configs[cell["config"]]["file"])
    traffic = read_json(BENCH, "traffic", cell["traffic"] + ".json")
    return cell, config, traffic


def compile_meter():
    """Backend compile seconds and count, and persistent-cache hits and
    misses of this process, from JAX's own monitoring events."""
    stats = {"compile_s": 0.0, "compiles": 0, "cache_hits": 0,
             "cache_misses": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            stats["compile_s"] += duration
            stats["compiles"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            stats["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            stats["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return stats


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` where that
    is set, else at the fixed ``<checkout>/.jax_cache``; every program
    is written, however fast it compiled."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def check_device(chips: int, peaks: dict):
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" or kops.exec_mode() != "compiled-tpu":
        raise NoChip(f"no TPU: devices[0].platform={dev.platform}, "
                     f"kernels {kops.exec_mode()}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    if dev.device_kind not in peaks:
        raise NoChip(f"device kind {dev.device_kind!r} is not in "
                     f"bench/peaks.json")
    return dev


class Run:
    """What one run keeps: the served batches, their answers, times and
    counts, for the check and the metric readers after the window."""

    def __init__(self):
        self.batches = []        # (kinds, keys, upd) per served batch
        self.answers = []        # int32 [B] per served batch
        self.path_len = []       # int32 [B] per served batch
        self.overflow = []       # int per served batch
        self.latency_s = []      # per window batch
        self.window_first = 0    # index of the first window batch
        self.planes_in = []      # (batch index, plane keys, widths) kept
        self.window_s = 0.0


def serve_batch(serve, state, plane, batch, flags, run: Run):
    """Hand one batch over, wait for its answers, keep what the check
    and the readers need.  Each step is a host span of its own."""
    kinds, keys, upd = batch
    with jax.profiler.TraceAnnotation("bench.dispatch"):
        out = serve(state, plane, kinds[None], keys[None], upd[None],
                    **flags)
    state, plane, res, plen, ovf = out[:5]
    with jax.profiler.TraceAnnotation("bench.wait"):
        res = np.asarray(res)[0]
    with jax.profiler.TraceAnnotation("bench.answers"):
        run.batches.append(batch)
        run.answers.append(res)
        run.path_len.append(np.asarray(plen)[0])
        run.overflow.append(int(np.asarray(ovf)[0]))
    return state, plane


def live_keys(state):
    """The state's live keys, ascending, and their hit counters
    (``selfhits``): allocated, unmarked, no sentinel."""
    key = np.asarray(state.key).astype(np.int64)
    idx = np.arange(key.shape[0])
    alive = ((idx >= 2) & (idx < int(state.n_alloc))
             & ~np.asarray(state.deleted) & (key < 2 ** 31 - 1))
    order = np.argsort(key[alive])
    return (key[alive][order],
            np.asarray(state.selfhits).astype(np.int64)[alive][order])


def check(run: Run, initial_keys, initial_hits, final, bottom_row,
          pad_key: int):
    """The numbers compared against the reference, by name, and the
    count of wrong answers in the window's batches.  ``final`` is the
    state's ``(live keys, hit counters)`` after the last batch."""
    ref = reference.KeySet(initial_keys, initial_hits)
    bad = [int((ref.apply(kinds, keys, upd) != got).sum())
           for (kinds, keys, upd), got in zip(run.batches, run.answers)]
    want = ref.sorted_keys()
    final_keys, final_hits = final
    key_set_bad = len(np.setxor1d(want, final_keys))
    settled, settled_hits = ref.settled_hits()
    got_hits = dict(zip(final_keys.tolist(), final_hits.tolist()))
    hits_bad = sum(got_hits.get(k) != h for k, h in
                   zip(settled.tolist(), settled_hits.tolist()))
    n = len(want)
    bottom_row = np.asarray(bottom_row, np.int64)
    bottom_bad = (int((bottom_row[:n] != want[:len(bottom_row)]).sum())
                  + max(n - len(bottom_row), 0)
                  + int((bottom_row[n:] != pad_key).sum()))
    return {"answer_mismatches": sum(bad),
            "key_set_mismatches": key_set_bad,
            "hit_count_mismatches": hits_bad,
            "bottom_row_mismatches": bottom_bad,
            "overflow_batches": sum(o > 0 for o in run.overflow)}, \
        sum(bad[run.window_first:])


def run_cell(config: dict, traffic: dict, seed: int,
             seconds: float, trace: bool, *, serve=None,
             metric_specs=(), peaks=None, meter=None, keep_trace=None):
    """One run of a cell; returns the result dict (without ``device``).
    ``serve`` stands in for ``splaylist.run_serving`` (the tests break
    the timed path through it)."""
    serve = serve or sx.run_serving
    rng = np.random.default_rng(seed % 2 ** 64)
    gen = load_module(os.path.join(BENCH, "gen",
                                   traffic["generator"] + ".py"),
                      "bench_gen_" + traffic["generator"])
    stream = gen.Stream(config, traffic, rng)
    initial = stream.keys.copy()
    hits = 1 + load.prior_hits(stream, initial, int(config["history_reads"]),
                               float(config["p"]), rng)
    state, plane = load.bulk_load(initial, hits, int(config["capacity"]),
                                  int(config["levels"]),
                                  int(config["width"]))
    flags = dict(traffic.get("serving", {}))
    run = Run()
    for _ in range(WARMUP_BATCHES):
        state, plane = serve_batch(serve, state, plane,
                                   stream.next_batch(), flags, run)
    run.window_first = len(run.batches)
    pending = stream.next_batch()
    compiles0 = meter["compiles"] if meter else 0
    setup_s = time.perf_counter() - T_START

    trace_dir = None
    if trace:
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="bench-trace-")
    if trace:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # bench.* spans only
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("bench.batch"):
            if trace:
                run.planes_in.append((len(run.batches), plane.keys,
                                      plane.widths))
            tb = time.perf_counter()
            state, plane = serve_batch(serve, state, plane, pending, flags,
                                       run)
            t1 = time.perf_counter()
        run.latency_s.append(t1 - tb)
        if t1 - t0 >= seconds or (trace and len(run.latency_s)
                                  >= TRACED_BATCHES):
            break
        with jax.profiler.TraceAnnotation("bench.generate"):
            pending = stream.next_batch()
    run.window_s = t1 - t0
    if trace:
        jax.profiler.stop_trace()
    window_compiles = (meter["compiles"] - compiles0) if meter else 0

    memory_peak = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use", 0)
    final = live_keys(state)
    bottom_row = np.asarray(plane.keys)[-1]
    planes_in = [(i, np.asarray(k), np.asarray(w))
                 for i, k, w in run.planes_in]
    run.planes_in = planes_in
    del state, plane
    compared, failed = check(run, initial, hits, final, bottom_row,
                             sx.POS_INF_32)
    correct = all(compared[k] <= LIMITS[k] for k in LIMITS)

    n_window = len(run.latency_s)
    ops = n_window * stream.batch_size
    result = {"correct": bool(correct), "attempted": ops,
              "failed": failed, "metrics": {}}
    info = {"batches": n_window, "window_s": run.window_s,
            "window_compiles": window_compiles, "setup_s": setup_s,
            "memory_peak_bytes": memory_peak}
    if trace:
        try:
            red = trace_reduce.reduce_dir(trace_dir)
        finally:
            if not keep_trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = MetricContext(run, red, peaks, flags)
        for m in metric_specs:
            mod = load_module(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"),
                              "bench_metric_" + m["name"])
            value = mod.read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = red.breakdown()
        info["busy_s"] = red.busy_s
        info["trace_window_s"] = red.window_s
    else:
        lat = np.asarray(run.latency_s)
        result["metrics"] = {
            "ops_per_s": {"value": ops / run.window_s, "unit": "ops/s"},
            "batch_p90_ms": {"value": float(np.percentile(lat, 90)) * 1e3,
                             "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    result["compared"] = {k: {"value": compared[k], "limit": LIMITS[k]}
                          for k in LIMITS}
    return result, info


class MetricContext:
    """What a per-layer metric reader may read: the reduced trace, the
    run's kept batches and counts, and the peak table row."""

    def __init__(self, run: Run, trace, peaks, serving: dict):
        if len(trace.batches) != len(run.latency_s):
            raise ValueError(f"the trace holds {len(trace.batches)} batch "
                             f"spans, the window {len(run.latency_s)}")
        self.run = run
        self.trace = trace
        self.peaks = peaks
        self.serving = serving

    def window_batches(self):
        """``(kinds, keys, upd, path_len)`` of each window batch."""
        r = self.run
        for i in range(r.window_first, len(r.batches)):
            yield (*r.batches[i], r.path_len[i])


def rehearse(workload: str, seed: int, seconds: float, trace: bool,
             resize: dict, serve=None):
    """A run of ``workload`` on whatever JAX finds, at the sizes that
    ``resize`` sets over its configuration and traffic (``{"config":
    {...}, "traffic": {...}}``), with no look for a chip: the CPU
    rehearsal of the tests, with Pallas kernels interpreted.  The result
    is marked ``"rehearsal": true`` and is never a chip result."""
    spec = read_json(ROOT, "BENCHMARK.json")
    cell, config, traffic = cell_spec(spec, workload)
    config = {**config, **resize.get("config", {})}
    traffic = {**traffic, **resize.get("traffic", {})}
    metric_specs = [m for m in spec["per_layer"]
                    if workload in m.get("workloads", [workload])]
    peaks = next(iter(read_json(BENCH, "peaks.json")["devices"].values()))
    result, info = run_cell(config, traffic, seed, seconds, trace,
                            serve=serve, metric_specs=metric_specs,
                            peaks=peaks, meter=compile_meter())
    dev = jax.devices()[0]
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices()),
                        "kernels": kops.exec_mode()}
    result["rehearsal"] = True
    return result, info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="write the --trace 1 profile under DIR and keep it")
    args = ap.parse_args(argv)

    spec = read_json(ROOT, "BENCHMARK.json")
    cell, config, traffic = cell_spec(spec, args.workload)
    peaks = read_json(BENCH, "peaks.json")["devices"]
    try:
        dev = check_device(int(cell["chips"]), peaks)
    except NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 2
    meter = compile_meter()
    cache_dir = enable_compile_cache()
    if args.trace:
        metric_specs = [m for m in spec["per_layer"]
                        if args.workload in m.get("workloads",
                                                  [args.workload])]
    else:
        metric_specs = []
    result, info = run_cell(config, traffic, args.seed, args.seconds,
                            bool(args.trace), metric_specs=metric_specs,
                            peaks=peaks[dev.device_kind], meter=meter,
                            keep_trace=args.keep_trace)
    missing = [m["name"] for m in metric_specs
               if m["name"] not in result["metrics"]]
    if missing:
        # a layer the trace could not attribute reads nothing: that is a
        # broken yardstick, never a metric left out
        print(f"bench: the trace gave nothing to read for {missing}; "
              f"no result", file=sys.stderr)
        return 3
    print(f"bench: {args.workload} seed {args.seed}: {info['batches']} "
          f"batches in {info['window_s']} s, set-up {info['setup_s']} s; "
          f"compile cache {cache_dir}: {meter['cache_hits']} hits, "
          f"{meter['cache_misses']} misses, {meter['compile_s']} s "
          f"compiling; compilations inside the window: "
          f"{info['window_compiles']}")
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices()),
                        "memory_peak_bytes": info["memory_peak_bytes"]}
    if args.trace:
        result["device"]["busy_s"] = info["busy_s"]
        result["device"]["window_s"] = info["trace_window_s"]
    compared = result.pop("compared")
    for k, v in compared.items():
        print(f"{k} {v['value']} limit {v['limit']}", file=sys.stderr)
    result["compared"] = compared
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The four-chip cell ``paper4m-ro-99-1`` on the CPU: its configuration
holds every key the harness and the generator read, its two exchange
metrics read the collectives of a synthetic trace, and ``bench/run.py``
runs it end to end on a forced four-device host, at a tiny size with the
one-device descent limit lowered so that the plane is served
width-sharded, as the full-size plane is on four v5e chips.

The four-device rehearsal needs the device count before JAX starts: it
runs this file as a script in a subprocess, which prints its findings
as one JSON line."""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import run as harness
from bench import trace_reduce as tr
from bench.metrics import exchange_ms, refresh_exchange_ms

CELL = "paper4m-ro-99-1"
TINY = {"config": {"n": 2000, "key_space": 4000, "capacity": 2050,
                   "width": 2048, "levels": 12, "history_reads": 20000},
        "traffic": {"batch": 256}}
SEED = 2 ** 31 + 4242


def _spec():
    return harness.read_json(harness.ROOT, "BENCHMARK.json")


def test_config_holds_what_the_harness_and_generator_read():
    spec = _spec()
    cell, config, traffic = harness.cell_spec(spec, CELL)
    assert cell["chips"] == 4 and cell["traffic"] == "ro-99-1"
    paper = harness.read_json(harness.ROOT, "bench/configs/paper-n1e5.json")
    # the same deployment at another scale: the same keys, the same
    # guarantees, nothing reduced
    assert set(config) == set(paper)
    assert config["guarantees"] == paper["guarantees"]
    assert config["reduced"] == []
    for key in ("prepopulate", "p", "key_bits"):
        assert config[key] == paper[key]
    n, width = config["n"], config["width"]
    assert config["key_space"] == 2 * n
    assert config["capacity"] == width + 2
    assert config["levels"] == int(math.log2(width))
    assert n * config["prepopulate"] < width
    assert config["history_reads"] == 10 * n
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    assert entry["file"] == "bench/configs/paper-n4e6.json"
    # one device's descent cannot take the plane; four shards can
    from repro.kernels import splay_search as ssk
    from repro.parallel import sharding as shd
    assert width > ssk.MAX_DESCENT_WIDTH
    assert shd.width_shards(width, 4, ssk.MAX_DESCENT_WIDTH) == 4
    metrics = [m["name"] for m in spec["per_layer"]
               if CELL in m.get("workloads", [CELL])]
    assert metrics == ["exchange_ms", "refresh_exchange_ms"]


def _reduction():
    """Two batches on two devices: collectives in and out of the
    refresh layer, other ops, and a collective outside every batch."""
    ms = 1e6
    ops = []
    for dev in ("/device:TPU:0", "/device:TPU:1"):
        for b0 in (0.0, 100 * ms):
            ops += [
                tr.Op(dev, "other", "all-to-all.3", b0 + 1 * ms, 2 * ms),
                tr.Op(dev, "other", "all-gather-start.1", b0 + 4 * ms,
                      1 * ms),
                tr.Op(dev, "descent", "splay_search_tiered", b0 + 6 * ms,
                      5 * ms),
                tr.Op(dev, "refresh", "all-gather.7", b0 + 20 * ms,
                      3 * ms),
                tr.Op(dev, "refresh", "psum.2", b0 + 30 * ms, 1 * ms),
                tr.Op(dev, "refresh", "fusion.12", b0 + 40 * ms, 9 * ms),
                tr.Op(dev, "fold", "while.4", b0 + 50 * ms, 4 * ms),
            ]
        ops.append(tr.Op(dev, "other", "all-reduce.1", 92 * ms, 5 * ms))
    spans = [tr.Span("bench.batch", 0.0, 90 * ms),
             tr.Span("bench.batch", 100 * ms, 190 * ms)]
    return tr.Reduction(ops, spans)


def test_exchange_metrics_on_a_synthetic_trace():
    ctx = type("Ctx", (), {"trace": _reduction()})()
    # per batch and device: 2 + 1 ms outside the refresh, 3 + 1 inside
    assert exchange_ms.read(ctx) == pytest.approx(3.0)
    assert refresh_exchange_ms.read(ctx) == pytest.approx(4.0)


def test_exchange_metrics_read_nothing_without_collectives():
    ms = 1e6
    ops = [tr.Op("/device:TPU:0", "refresh", "fusion.1", 1 * ms, 2 * ms),
           tr.Op("/device:TPU:0", "descent", "splay_search_tiered",
                 4 * ms, 1 * ms)]
    red = tr.Reduction(ops, [tr.Span("bench.batch", 0.0, 10 * ms)])
    ctx = type("Ctx", (), {"trace": red})()
    assert exchange_ms.read(ctx) is None
    assert refresh_exchange_ms.read(ctx) is None


def child() -> dict:
    """The rehearsal on four devices, the plane's 2048 lanes over a
    one-device limit of 512."""
    import jax
    from repro.core import splaylist as sx
    from repro.kernels import splay_search as ssk
    from repro.parallel import sharding as shd

    assert len(jax.devices()) == 4, jax.devices()
    ssk.MAX_DESCENT_WIDTH = TINY["config"]["width"] // 4
    shards = []

    def serve(st, plane, *args, **kw):
        mesh = shd.plane_width_mesh(plane)
        shards.append(0 if mesh is None else mesh.shape["model"])
        return sx.run_serving(st, plane, *args, **kw)

    result, info = harness.rehearse(CELL, SEED, 0.5, False, TINY,
                                    serve=serve)
    return {"correct": result["correct"], "compared": result["compared"],
            "failed": result["failed"], "batches": info["batches"],
            "window_compiles": info["window_compiles"],
            "shards_in": shards, "devices": result["device"]["count"]}


def _four_devices() -> dict:
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(["src", "."]),
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       capture_output=True, text=True, env=env,
                       cwd=harness.ROOT, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_rehearsal_on_four_devices_is_correct_and_width_sharded():
    got = _four_devices()
    assert got["devices"] == 4
    assert got["correct"] is True, got["compared"]
    assert all(v["value"] == 0 for v in got["compared"].values())
    assert got["failed"] == 0 and got["batches"] > 0
    assert got["window_compiles"] == 0
    # the loaded plane enters the first warm-up batch whole; every
    # batch after it gets the four-shard plane the first one laid out
    assert got["shards_in"][0] == 0
    assert got["shards_in"][1:] == [4] * (len(got["shards_in"]) - 1)


if __name__ == "__main__":
    print(json.dumps(child(), default=lambda x: np.asarray(x).tolist()))

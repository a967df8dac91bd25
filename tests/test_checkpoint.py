"""Fault-tolerance tests: checkpoint atomicity, integrity, resume, GC,
elastic re-sharding, straggler monitor."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import checkpoint as ck
from repro.train import elastic
from repro.train import straggler
from repro.launch.mesh import make_auto_mesh


def _tree():
    return {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.ones((5,), jnp.float32)}}


def test_save_load_roundtrip(tmp_path):
    mgr = ck.CheckpointManager(str(tmp_path))
    t = _tree()
    mgr.save(10, t, extra={"data_step": 10}, blocking=True)
    flat, extra = mgr.load()
    assert extra["data_step"] == 10
    np.testing.assert_array_equal(flat["params/a"], np.asarray(t["a"]))
    np.testing.assert_array_equal(flat["params/b/c"],
                                  np.asarray(t["b"]["c"]))
    rebuilt = ck.unflatten_into(
        {k: v for k, v in flat.items() if k.startswith("params/")}, t)
    np.testing.assert_array_equal(np.asarray(rebuilt["a"]),
                                  np.asarray(t["a"]))


def test_integrity_check_detects_corruption(tmp_path):
    mgr = ck.CheckpointManager(str(tmp_path))
    mgr.save(1, _tree(), blocking=True)
    d = os.path.join(str(tmp_path), "step_0000000001")
    victim = [f for f in os.listdir(d) if f.endswith(".npy")][0]
    with open(os.path.join(d, victim), "r+b") as f:
        f.seek(-1, os.SEEK_END)
        f.write(b"\x13")
    with pytest.raises(IOError):
        mgr.load()


def test_atomicity_partial_write_invisible(tmp_path):
    mgr = ck.CheckpointManager(str(tmp_path))
    mgr.save(1, _tree(), blocking=True)
    # simulate a crash mid-write: stray tmp dir must be ignored
    os.makedirs(os.path.join(str(tmp_path), "step_0000000002.tmp"))
    assert mgr.latest_step() == 1
    flat, _ = mgr.load()
    assert "params/a" in flat


def test_gc_keeps_last_k(tmp_path):
    mgr = ck.CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(), blocking=True)
    assert mgr.steps() == [3, 4]


def test_idempotent_resave(tmp_path):
    mgr = ck.CheckpointManager(str(tmp_path))
    mgr.save(5, _tree(), blocking=True)
    mgr.save(5, _tree(), blocking=True)   # must not raise
    assert mgr.latest_step() == 5


def test_elastic_grid_and_microbatch():
    assert elastic.viable_grid(256, 16) == (16, 16)
    assert elastic.viable_grid(512, 16, multi_pod=True) == (2, 16, 16)
    assert elastic.viable_grid(240, 16) == (15, 16)   # one host lost
    assert elastic.viable_grid(8, 16) is None
    assert elastic.scale_microbatch(256, 16, 15, 1) == 2
    assert elastic.scale_microbatch(256, 16, 16, 1) == 1


def test_elastic_reshard_roundtrip():
    mesh = make_auto_mesh((1, 1), ("data", "model"))
    flat = {"params/a": np.arange(16.0).reshape(4, 4)}
    specs = {"params/a": jax.sharding.PartitionSpec("data", None)}
    out = elastic.reshard(flat, specs, mesh)
    np.testing.assert_array_equal(np.asarray(out["params/a"]),
                                  flat["params/a"])


def test_straggler_monitor_flags_slow_host():
    mon = straggler.StragglerMonitor(threshold=2.0, patience=3)
    for _ in range(20):
        mon.record(0, 1.0)
    flagged = False
    for _ in range(4):
        flagged = mon.check(7, 5.0)
    assert flagged
    assert not mon.check(1, 1.1)


def test_verify_error_names_array_and_path(tmp_path):
    """A checksum failure must say WHICH array at WHICH path broke —
    'IOError' alone is useless on a 1000-array snapshot."""
    mgr = ck.CheckpointManager(str(tmp_path))
    mgr.save(4, _tree(), blocking=True)
    d = os.path.join(str(tmp_path), "step_0000000004")
    victim = "params__b__c.npy"
    with open(os.path.join(d, victim), "r+b") as f:
        f.seek(-1, os.SEEK_END)
        f.write(b"\x13")
    with pytest.raises(IOError, match=r"params/b/c.*step 4.*"
                                      r"params__b__c\.npy"):
        mgr.load()


def test_rapid_saves_serialize_and_all_publish(tmp_path):
    """Back-to-back non-blocking saves must join the in-flight writer
    before spawning the next (the background-thread race): every step
    publishes completely and loads clean."""
    import threading

    mgr = ck.CheckpointManager(str(tmp_path), keep=32)
    ts = [threading.Thread(
        target=mgr.save, args=(s, {"a": np.full((64, 64), float(s))}),
        kwargs={"blocking": False}) for s in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    mgr.wait()
    assert mgr.steps() == list(range(8))
    assert not [d for d in os.listdir(str(tmp_path))
                if d.endswith(".tmp")]
    for s in range(8):
        flat, _ = mgr.load(s)            # verify=True: checksums hold
        assert float(flat["params/a"][0, 0]) == float(s)

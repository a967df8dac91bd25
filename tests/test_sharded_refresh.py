"""Sharded-parity acceptance (DESIGN.md §5.4): the width-sharded refresh
on a forced host-device mesh is bit-identical to the replicated refresh.

The mesh needs ``--xla_force_host_platform_device_count`` set *before*
jax initializes, so the differential streams run in a subprocess
(``benchmarks/sharded_refresh_probe.py --parity``): 1/2/4-way meshes
over insert/delete/height-churn streams, the transient-empty level case,
the rebuild-staleness scatter fallback, the overflow burst, and the
indivisible-width replicated fallback.

The in-process tests below cover the pieces that do not need a multi-
device runtime: the no-mesh/1-way fallback contract and the sharded
layout helpers.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import device_index as dix
from repro.core import splaylist as sx
from repro.parallel import sharding as shd

from conftest import seed_splay_state as _seed_state  # noqa: E402
from repro.launch.mesh import make_auto_mesh           # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_sharded_parity_on_host_mesh():
    """The full differential battery on 1/2/4 shards (subprocess — the
    forced device count must precede jax init)."""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)            # probe sets its own
    r = subprocess.run(
        [sys.executable, "benchmarks/sharded_refresh_probe.py",
         "--parity"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=1200)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "PARITY OK" in r.stdout


def test_no_mesh_falls_back_to_replicated():
    """Without a mesh the sharded entry point IS the replicated refresh
    (same values, same overflow), so callers can use one code path."""
    st = _seed_state(list(range(0, 80, 2)))
    plane = dix.from_state_device(st, n_levels=12, width=254)
    ins = np.asarray([1, 3, 5], np.int32)
    st, _, _ = sx.run_ops(
        st, jnp.full((3,), sx.OP_INSERT, jnp.int32), jnp.asarray(ins),
        jnp.ones((3,), bool))
    p_s, ovf = dix.refresh_device_sharded(st, plane, max_new=8)
    p_r, ovf_r = dix.refresh_device(st, plane, max_new=8,
                                    return_overflow=True)
    assert int(ovf) == int(ovf_r) == 0
    for f in ("keys", "widths", "heights"):
        np.testing.assert_array_equal(
            np.asarray(getattr(p_s, f)), np.asarray(getattr(p_r, f)))


def test_index_plane_specs_and_shard_helper():
    from jax.sharding import PartitionSpec as P
    specs = shd.index_plane_specs(dix.DeviceLevelArrays, "model")
    assert specs.keys == P(None, "model")
    assert specs.widths == P()
    assert specs.heights == specs.slots == P("model")
    # single-device mesh: helper round-trips values; indivisible width
    # returns the plane unchanged
    mesh = make_auto_mesh((1, 1), ("data", "model"))
    plane = dix.build_device(
        jnp.asarray(np.arange(0, 128, 2, dtype=np.int32)),
        jnp.asarray(np.zeros(64, np.int32)), n_levels=3)
    out = shd.shard_index_plane(plane, mesh)
    np.testing.assert_array_equal(np.asarray(out.keys),
                                  np.asarray(plane.keys))
    assert shd.shard_index_plane(plane, None) is plane

"""Sharded-search acceptance (DESIGN.md §5.5): the width-sharded tiered
search on a forced host-device mesh is bit-identical to the replicated
tiered search, across the whole wrapper-dispatch seam.

The mesh needs ``--xla_force_host_platform_device_count`` set *before*
jax initializes, so the differential battery runs in a subprocess
(``benchmarks/sharded_search_probe.py --parity``): 1/2/4-way meshes,
sharded plane + sharded search vs sharded plane + gather-to-replicated
vs fully replicated plane, boundary-straddling rank windows, boundary
keys and cross-boundary-gap misses, transient-empty rows / the
all-empty plane / refill, membership-churn epochs interleaving sharded
refresh and sharded search, the indivisible-width fallback, and the
end-to-end sharded serving loop.

The in-process tests below cover the pieces that do not need a multi-
device runtime: the no-mesh fallback contract, the dispatch-detection
helper, the forced-gather seam, empty query batches, and the
plane-search serving mode against the state-walk answers.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import device_index as dix
from repro.core import splaylist as sx
from repro.kernels import splay_search as ssk
from repro.parallel import sharding as shd

from conftest import seed_splay_state as _seed_state  # noqa: E402
from repro.launch.mesh import make_auto_mesh           # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _plane(pool, n_levels=12, width=252, cap=512):
    return (dix.from_state_device(_seed_state(pool, cap=cap),
                                  n_levels=n_levels, width=width))


def test_sharded_parity_on_host_mesh():
    """The full differential battery on 1/2/4 shards (subprocess — the
    forced device count must precede jax init)."""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)            # probe sets its own
    r = subprocess.run(
        [sys.executable, "benchmarks/sharded_search_probe.py",
         "--parity"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=1200)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "PARITY OK" in r.stdout


def test_no_mesh_falls_back_to_replicated():
    """Without a resolvable mesh the sharded entry point IS the
    replicated search (same values), so callers keep one code path."""
    plane = _plane(list(range(0, 160, 2)))
    qs = jnp.asarray(np.asarray([0, 1, 2, 77, 158, 300, -4], np.int32))
    out_s = ssk.splay_search_sharded(plane, qs)
    out_r = ssk.splay_search(plane, qs, sharded=False)
    for a, b in zip(out_s, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sharded_true_without_mesh_degrades():
    """``sharded=True`` with no mesh anywhere degrades to the gathered
    path instead of raising."""
    plane = _plane(list(range(0, 80, 2)))
    qs = jnp.asarray(np.asarray([0, 3, 78], np.int32))
    out_f = ssk.splay_search(plane, qs, sharded=True)
    out_r = ssk.splay_search(plane, qs, sharded=False)
    for a, b in zip(out_f, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_plane_width_mesh_detection():
    """The dispatch seam's detector: None for replicated planes,
    tracers, single-shard meshes; the mesh for the sharded layout."""
    plane = _plane(list(range(0, 80, 2)))
    assert shd.plane_width_mesh(plane) is None
    mesh1 = make_auto_mesh((1, 1), ("data", "model"))
    assert shd.plane_width_mesh(
        shd.shard_index_plane(plane, mesh1)) is None   # 1 shard

    seen = []

    @jax.jit
    def probe(p):
        seen.append(shd.plane_width_mesh(p))
        return p.keys

    probe(plane)
    assert seen == [None]                              # tracer -> None


def test_sharded_search_empty_queries():
    plane = _plane(list(range(0, 40, 2)), width=124, cap=128)
    f, r, lv = ssk.splay_search_sharded(plane, jnp.zeros((0,), jnp.int32))
    assert f.shape == r.shape == lv.shape == (0,)


def test_plane_search_serving_matches_state_walk():
    """``run_serving(plane_search=True)`` answers from the plane; in
    steady state (no overflow) the verdicts are bit-identical to the
    state-walk answers and ``path_len`` becomes the level-found depth."""
    L, W = 12, 254
    st = _seed_state(list(range(0, 200, 2)))
    plane = dix.from_state_device(st, n_levels=L, width=W)
    rng = np.random.default_rng(3)
    E, B = 4, 48
    kinds = np.zeros((E, B), np.int32)
    keys = rng.choice(np.arange(0, 220), (E, B)).astype(np.int32)
    ups = rng.random((E, B)) < 0.5
    out_p = sx.run_serving(st, plane, jnp.asarray(kinds),
                           jnp.asarray(keys), jnp.asarray(ups),
                           aggregate=True, plane_search=True)
    out_w = sx.run_serving(st, plane, jnp.asarray(kinds),
                           jnp.asarray(keys), jnp.asarray(ups),
                           aggregate=True)
    np.testing.assert_array_equal(np.asarray(out_p[2]),
                                  np.asarray(out_w[2]))
    assert int(np.asarray(out_p[4]).sum()) == 0
    assert int(np.asarray(out_p[5]).sum()) == 0     # no routed spill
    assert int(np.asarray(out_p[3]).max()) <= L
    # the states evolve identically (the rebalance fold runs either way)
    np.testing.assert_array_equal(np.asarray(out_p[0].key),
                                  np.asarray(out_w[0].key))


def test_plane_search_requires_aggregate():
    st = _seed_state([2, 4, 6], cap=64)
    plane = dix.from_state_device(st, n_levels=6, width=62)
    B = 8
    try:
        sx.run_epoch(st, plane, jnp.zeros((B,), jnp.int32),
                     jnp.zeros((B,), jnp.int32), jnp.ones((B,), bool),
                     plane_search=True)
    except ValueError as e:
        assert "aggregate" in str(e)
    else:
        raise AssertionError("plane_search without aggregate must raise")

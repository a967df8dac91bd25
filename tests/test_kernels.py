"""Pallas kernel sweeps: shapes/dtypes vs the pure-jnp oracles
(interpret mode on CPU per the assignment)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import level_arrays as la
from repro.core import workload as wl
from repro.kernels import ref, ops
from repro.kernels import hot_gather as hg
from repro.kernels import splay_search as ssk


@pytest.mark.parametrize("n,levels,nq,qb", [
    (128, 2, 64, 32),
    (1000, 4, 256, 64),
    (5000, 6, 512, 256),
    (777, 3, 130, 64),          # non-divisible query count (padding)
])
def test_splay_search_sweep(n, levels, nq, qb):
    rng = np.random.default_rng(n + levels)
    keys = np.sort(rng.choice(10 * n, n, replace=False)).astype(np.int32)
    heights = rng.integers(0, levels, n).astype(np.int32)
    L = la.build(keys, heights, min_levels=levels)
    qs = np.concatenate([
        rng.choice(keys, nq // 2),
        rng.integers(0, 10 * n, nq - nq // 2)]).astype(np.int32)
    f, r, lv = ops.splay_search(jnp.asarray(L.keys), jnp.asarray(qs),
                                query_block=qb)
    f0, r0, lv0 = ref.splay_search_ref(jnp.asarray(L.keys),
                                       jnp.asarray(qs))
    np.testing.assert_array_equal(np.asarray(f), np.asarray(f0))
    np.testing.assert_array_equal(np.asarray(r), np.asarray(r0))
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(lv0))


def _zipf_fixture(width, alpha, nq, seed=0):
    """Shared splay-shaped Zipf fixture (same builder the benchmark
    races), plus a sprinkle of absent keys so found=False paths are
    exercised too."""
    keys, heights, qs = wl.zipf_level_fixture(width, alpha, nq, seed)
    rng = np.random.default_rng(seed + 1)
    qs[:: 17] = rng.integers(0, 20 * width,
                             len(qs[:: 17])).astype(np.int32)
    return la.build(keys, heights, min_levels=6), qs


@pytest.mark.parametrize("alpha", [0.6, 1.0, 1.4])
@pytest.mark.parametrize("nq", [512, 333])   # block multiple and not
def test_splay_search_zipf_wide(alpha, nq):
    """Acceptance: per-row/windowed kernel identical to kernels/ref.py at
    width >= 4096 under skewed (Zipf) query batches, including
    non-block-multiple query counts (internal padding)."""
    L, qs = _zipf_fixture(4096, alpha, nq, seed=int(alpha * 10) + nq)
    lvk = jnp.asarray(L.keys)
    f, r, lv = ops.splay_search(lvk, jnp.asarray(qs),
                                widths=jnp.asarray(L.widths))
    f0, r0, lv0 = ref.splay_search_ref(lvk, jnp.asarray(qs))
    np.testing.assert_array_equal(np.asarray(f), np.asarray(f0))
    np.testing.assert_array_equal(np.asarray(r), np.asarray(r0))
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(lv0))


def test_tiered_matches_seed_baseline():
    """The tiered kernel agrees bit-for-bit with the oracle the seed
    kernel was checked against (``kernels/ref.py``), on the host plane
    struct and an unpadded query count."""
    L, qs = _zipf_fixture(4096, 1.0, 300, seed=5)
    out_t = ops.splay_search(L, jnp.asarray(qs))
    out_f = ref.splay_search_ref(jnp.asarray(L.keys), jnp.asarray(qs))
    for a, b in zip(out_t, out_f):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_splay_search_unpadded_callers():
    """Satellite: callers pass arbitrary query counts straight to the
    kernel wrapper — no pre-padding, outputs sliced to the input length."""
    rng = np.random.default_rng(3)
    keys = np.sort(rng.choice(5000, 700, replace=False)).astype(np.int32)
    heights = rng.integers(0, 3, 700).astype(np.int32)
    L = la.build(keys, heights, min_levels=3)
    for nq in (1, 7, 255, 256, 257):
        qs = rng.choice(keys, nq).astype(np.int32)
        f, r, lv = ssk.splay_search(jnp.asarray(L.keys), jnp.asarray(qs))
        assert f.shape == r.shape == lv.shape == (nq,)
        f0, r0, lv0 = ref.splay_search_ref(jnp.asarray(L.keys),
                                           jnp.asarray(qs))
        np.testing.assert_array_equal(np.asarray(f), np.asarray(f0))
        np.testing.assert_array_equal(np.asarray(r), np.asarray(r0))


def test_splay_search_hot_resolves_high():
    """Distribution-adaptivity: keys in the top rows report low
    level_found (the short-path property)."""
    rng = np.random.default_rng(0)
    keys = np.arange(0, 4096, 2, dtype=np.int32)
    heights = np.zeros(len(keys), np.int32)
    hot = rng.choice(len(keys), 32, replace=False)
    heights[hot] = 3
    L = la.build(keys, heights, min_levels=4)
    qs = keys[hot][:32].astype(np.int32)
    _, _, lv = ops.splay_search(jnp.asarray(L.keys), jnp.asarray(qs))
    assert (np.asarray(lv) == 0).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16,
                                   jnp.int32])
@pytest.mark.parametrize("v,h,d,q", [(500, 32, 16, 64),
                                     (2048, 128, 64, 256)])
def test_hot_gather_sweep(dtype, v, h, d, q):
    rng = np.random.default_rng(v + d)
    if dtype == jnp.int32:
        table = rng.integers(0, 1000, (v, d)).astype(np.int32)
    else:
        table = rng.normal(size=(v, d)).astype(np.float32)
    table = jnp.asarray(table).astype(dtype)
    hot_ids = rng.choice(v, h, replace=False)
    hot_rank = np.full(v, -1, np.int32)
    hot_rank[hot_ids] = np.arange(h)
    hot_buf = table[jnp.asarray(hot_ids)]
    ids = rng.integers(0, v, q).astype(np.int32)
    out = ops.hot_gather(table, hot_buf, jnp.asarray(hot_rank),
                         jnp.asarray(ids))
    out0 = ref.hot_gather_ref(table, hot_buf, jnp.asarray(hot_rank),
                              jnp.asarray(ids))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out0))


@pytest.mark.parametrize("n,d,q", [(64, 8, 16), (512, 128, 64)])
def test_gather_rows(n, d, q):
    rng = np.random.default_rng(d)
    table = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, n, q).astype(np.int32))
    out = hg.gather_rows(table, ids)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.gather_rows_ref(table, ids)))


def test_level_arrays_from_jax_state():
    """End-to-end: run a skewed stream through the JAX splay-list, export
    level arrays, and search with the kernel."""
    import jax.numpy as jnp
    from repro.core import splaylist as sx
    import random
    rng = random.Random(2)
    pool = list(range(0, 128, 2))
    stream = [(sx.OP_INSERT, k, True) for k in pool]
    for _ in range(1500):
        k = pool[0] if rng.random() < 0.5 else rng.choice(pool)
        stream.append((sx.OP_CONTAINS, k, True))
    st = sx.make(capacity=256, max_level=16)
    st, _, _ = sx.run_ops(
        st, jnp.array([s[0] for s in stream], jnp.int32),
        jnp.array([s[1] for s in stream], jnp.int32),
        jnp.array([s[2] for s in stream], bool))
    L = la.from_state(st)
    qs = jnp.asarray(np.asarray(pool, np.int32))
    f, r, lv = ops.splay_search(jnp.asarray(L.keys), qs)
    assert bool(f.all())
    # the hammered key resolves near the top; far above the median key
    lv_arr = np.asarray(lv)
    assert lv_arr[0] <= lv_arr.min() + 1
    assert lv_arr[0] < np.median(lv_arr)

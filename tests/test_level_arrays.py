"""LevelArrays invariants: nested rows, incremental refresh.

Non-hypothesis counterpart of the property suite (which is skipped when
hypothesis is absent): the nested-rows invariant (every key in row r
appears in row r+1) is what both the kernels and the rank-windowed
descent lean on, so it gets direct coverage here.
"""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import level_arrays as la
from repro.core import splaylist as sx


def _check_invariants(L: la.LevelArrays):
    kk = L.keys
    n_levels = kk.shape[0]
    for r in range(n_levels):
        live = kk[r][kk[r] != la.PAD_KEY]
        assert len(live) == L.widths[r]
        assert (np.diff(live) > 0).all(), f"row {r} not sorted/unique"
        if r + 1 < n_levels:
            nxt = kk[r + 1][kk[r + 1] != la.PAD_KEY]
            assert set(live).issubset(set(nxt)), f"row {r} not nested"
        # live entries form a prefix: every lane past the width is pad
        assert (kk[r][L.widths[r]:] == la.PAD_KEY).all(), r


@pytest.mark.parametrize("n,hmax,min_levels", [
    (0, 1, 2), (1, 1, 2), (57, 4, 2), (300, 6, 3),
    (123, 1, 8),          # empty top rows (min_levels >> max height)
    (500, 7, 2),
])
def test_nested_rows(n, hmax, min_levels):
    rng = np.random.default_rng(n + hmax)
    keys = rng.choice(10 ** 6, n, replace=False).astype(np.int32)
    heights = rng.integers(0, hmax, n).astype(np.int32)
    L = la.build(keys, heights, min_levels=min_levels)
    _check_invariants(L)
    bottom = L.keys[-1][L.keys[-1] != la.PAD_KEY]
    np.testing.assert_array_equal(bottom, np.sort(keys))


def _make_state(pool, n_ops=800, seed=11, cap=512, ml=16):
    rng = random.Random(seed)
    stream = [(sx.OP_INSERT, k, True) for k in pool]
    for _ in range(n_ops):
        k = pool[0] if rng.random() < 0.4 else rng.choice(pool)
        stream.append((sx.OP_CONTAINS, k, True))
    st = sx.make(capacity=cap, max_level=ml)
    st, _, _ = sx.run_ops(
        st, jnp.array([s[0] for s in stream], jnp.int32),
        jnp.array([s[1] for s in stream], jnp.int32),
        jnp.array([s[2] for s in stream], bool))
    return st


def test_refresh_matches_full_build_same_keys():
    """Heights moved, membership didn't: refresh must equal a scratch
    build at the preserved shape, without consulting the state's order."""
    pool = list(range(0, 160, 2))
    st = _make_state(pool)
    # min_levels = max_level bounds every possible relative height, so the
    # refreshed shape provably stays put across epochs
    prev = la.from_state(st, min_levels=16)
    # another epoch of skewed traffic moves heights only
    qs = jnp.asarray(np.array(pool[:5] * 40, np.int32))
    st2, _, _ = sx.run_contains_batch(st, qs, jnp.ones((len(qs),), bool))
    ref = la.from_state(st2, min_levels=prev.keys.shape[0],
                        width=prev.keys.shape[1])
    out = la.refresh(st2, prev, min_levels=16)
    assert out.keys.shape == prev.keys.shape   # stable shapes, no recompile
    np.testing.assert_array_equal(out.keys, ref.keys)
    np.testing.assert_array_equal(out.widths, ref.widths)
    np.testing.assert_array_equal(out.heights, ref.heights)
    _check_invariants(out)


def test_refresh_falls_back_on_membership_change():
    pool = list(range(0, 100, 2))
    st = _make_state(pool, n_ops=200, seed=3)
    prev = la.from_state(st, min_levels=4)
    # insert new keys -> membership changed -> full build fallback
    ins = jnp.asarray(np.array([1, 3, 5], np.int32))
    st2, _, _ = sx.run_ops(
        st, jnp.full((3,), sx.OP_INSERT, jnp.int32), ins,
        jnp.ones((3,), bool))
    out = la.refresh(st2, prev, min_levels=4)
    bottom = out.keys[-1][out.keys[-1] != la.PAD_KEY]
    assert {1, 3, 5}.issubset(set(bottom.tolist()))
    _check_invariants(out)


def test_refresh_preserves_shape_on_transient_empty():
    """Regression: a delete-everything epoch must keep the previous
    (n_levels, width) rectangle — jit consumers key their caches on the
    shape, and transient empties are routine in delete-heavy serving."""
    pool = list(range(0, 50, 2))
    st = _make_state(pool, n_ops=100, seed=5, cap=128)
    prev = la.from_state(st, min_levels=6)
    dels = jnp.asarray(np.asarray(pool, np.int32))
    st2, _, _ = sx.run_ops(
        st, jnp.full((len(pool),), sx.OP_DELETE, jnp.int32), dels,
        jnp.ones((len(pool),), bool))
    out = la.refresh(st2, prev, min_levels=2)
    assert out.keys.shape == prev.keys.shape
    assert (out.widths == 0).all()
    assert (out.keys == la.PAD_KEY).all()
    _check_invariants(out)
    # and refreshing out of the empty restores membership at that shape
    ins = jnp.asarray(np.asarray(pool[:4], np.int32))
    st3, _, _ = sx.run_ops(
        st2, jnp.full((4,), sx.OP_INSERT, jnp.int32), ins,
        jnp.ones((4,), bool))
    out2 = la.refresh(st3, out, min_levels=2)
    assert out2.keys.shape == prev.keys.shape
    bottom = out2.keys[-1][out2.keys[-1] != la.PAD_KEY]
    assert set(bottom.tolist()) == set(pool[:4])


def test_vectorized_build_matches_row_loop_reference():
    """The prefix-sum construction against the obvious per-row filter."""
    rng = np.random.default_rng(9)
    keys = rng.choice(10 ** 5, 400, replace=False).astype(np.int32)
    heights = rng.integers(0, 5, 400).astype(np.int32)
    L = la.build(keys, heights, min_levels=6)
    order = np.argsort(keys, kind="stable")
    ks, hs = keys[order], heights[order]
    n_levels, width = L.keys.shape
    for r in range(n_levels):
        sel = ks[hs >= n_levels - 1 - r]
        row = np.full((width,), la.PAD_KEY, np.int32)
        row[:len(sel)] = sel
        np.testing.assert_array_equal(L.keys[r], row)

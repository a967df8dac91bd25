"""Differential tests: JAX engine vs the Python oracle, plus the batched
(concurrent-analogue) driver."""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ref_py
from repro.core import splaylist as sx


def _run_stream(stream, ml=16, cap=256):
    kinds = jnp.array([s[0] for s in stream], jnp.int32)
    keys = jnp.array([s[1] for s in stream], jnp.int32)
    upds = jnp.array([s[2] for s in stream], bool)
    st = sx.make(capacity=cap, max_level=ml)
    st, res, plen = sx.run_ops(st, kinds, keys, upds)
    oracle = ref_py.SplayList(max_level=ml, p=0.5)
    ores, oplen = [], []
    for kind, k, u in stream:
        if kind == sx.OP_CONTAINS:
            r = oracle.contains(k, upd=u)
        elif kind == sx.OP_INSERT:
            r = oracle.insert(k, upd=u)
        else:
            r = oracle.delete(k, upd=u)
        ores.append(r)
        oplen.append(oracle.last_path_len)
    return st, np.asarray(res), np.asarray(plen), oracle, \
        np.array(ores), np.array(oplen)


def test_differential_mixed_ops_with_rebuilds():
    rng = random.Random(3)
    pool = list(range(0, 90, 3))
    stream = [(sx.OP_INSERT, k, True) for k in pool]
    for _ in range(1200):
        r = rng.random()
        k = rng.choice(pool + [1, 2, 4])
        kind = (sx.OP_CONTAINS if r < 0.7 else
                sx.OP_INSERT if r < 0.85 else sx.OP_DELETE)
        stream.append((kind, k, rng.random() < 0.6))
    st, res, plen, oracle, ores, oplen = _run_stream(stream)
    assert (res == ores).all()
    assert (plen == oplen).all()
    assert oracle.heights() == sx.heights(st)
    assert oracle.m == int(st.m)
    assert oracle.deleted_hits == int(st.dhits)
    assert oracle.zero_level == int(st.zl)
    assert oracle.rebuilds >= 1   # the stream must exercise rebuild
    got = sx.serving_counters(st)
    assert got["state_rebuilds"] == oracle.rebuilds
    assert got["fold_steps"] == got["fold_active"] == len(stream)


def test_differential_contains_only_skewed():
    rng = random.Random(5)
    pool = list(range(0, 200, 2))
    stream = [(sx.OP_INSERT, k, True) for k in pool]
    hot = pool[:10]
    for _ in range(2000):
        k = rng.choice(hot) if rng.random() < 0.9 else rng.choice(pool)
        stream.append((sx.OP_CONTAINS, k, True))
    st, res, plen, oracle, ores, oplen = _run_stream(stream, ml=18,
                                                     cap=512)
    assert (res == ores).all() and (plen == oplen).all()
    h = sx.heights(st)
    hot_h = np.mean([h[k] for k in hot])
    cold_h = np.mean([h[k] for k in pool[60:]])
    assert hot_h > cold_h + 1   # adaptivity visible in heights


def test_batched_equals_serialized_updates():
    """run_contains_batch == lock-free searches on the snapshot + the
    update fold in index order (the hand-over-hand total order)."""
    rng = random.Random(7)
    pool = list(range(0, 120, 2))
    seed = [(sx.OP_INSERT, k, True) for k in pool]
    kinds = jnp.array([s[0] for s in seed], jnp.int32)
    keys = jnp.array([s[1] for s in seed], jnp.int32)
    upds = jnp.array([s[2] for s in seed], bool)
    st0 = sx.make(capacity=256, max_level=16)
    st0, _, _ = sx.run_ops(st0, kinds, keys, upds)

    B = 64
    qs = np.array([rng.choice(pool + [1, 3]) for _ in range(B)],
                  np.int32)
    coins = np.array([rng.random() < 0.5 for _ in range(B)])

    st_b, res_b, steps_b = sx.run_contains_batch(
        st0, jnp.asarray(qs), jnp.asarray(coins))

    # reference: searches against the snapshot, then serialized updates
    slots, steps_ref = sx.find_batch(st0, jnp.asarray(qs))
    assert (np.asarray(steps_b) == np.asarray(steps_ref)).all()
    st_ref = st0
    for q, c in zip(qs, coins):
        slot, _ = sx.find(st_ref, jnp.int32(q))
        if c and int(slot) >= 0:
            st_ref = sx._update(st_ref, jnp.int32(q))
    assert sx.heights(st_ref) == sx.heights(st_b)
    assert int(st_ref.m) == int(st_b.m)


def test_thresholds_shift_exactness():
    """s <= m/2^e  <=>  s <= (m >> e) for the exact rational comparison."""
    from fractions import Fraction
    rng = random.Random(1)
    for _ in range(2000):
        m = rng.randrange(0, 1 << 30)
        e = rng.randrange(0, 30)
        s = rng.randrange(0, 1 << 20)
        assert (s <= Fraction(m, 2 ** e)) == (s <= (m >> e))
        assert (s > Fraction(m, 2 ** e)) == (s > (m >> e))

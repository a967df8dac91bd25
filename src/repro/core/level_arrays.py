"""Level-array export: the TPU-native splay-list layout (DESIGN.md §5).

Pointer chasing is hostile to TPUs, so the batched search kernel consumes
the splay-list as a dense rectangle ``level_keys[n_levels, width]``:
row r holds (sorted, +INF-padded) the keys whose splay height is at least
(top - r) — row 0 is the hottest, the last row is the full key set.  A
search touches rows top-down and stops at the first row containing the
key; by the splay property hot keys live in the small top rows, which stay
VMEM-resident.  This is the paper's "popular elements move up" realized in
the TPU memory hierarchy instead of list levels.

``widths[r]`` counts row r's live entries, which is all the tiered
descent reads besides the keys (DESIGN.md §5.2).  An incremental
:func:`refresh` path rides along: after a rebalance epoch only the
heights move, not the membership, so the sorted bottom row can be
reused and the O(n log n) argsort skipped; serving loops call
``refresh(state, prev)`` instead of rebuilding from scratch.

The construction itself is a vectorized mask/prefix-sum pass (no Python
loop over levels): position of key i in row r is the prefix count of
keys j <= i with height >= h_r.

This module is the HOST oracle: serving loops use the device-resident
mirror (``core/device_index.py``, DESIGN.md §5.3), which runs the same
construction as jitted jnp — including an incremental ``refresh_device``
that merges membership changes into the previous sorted bottom row with
no argsort and no host transfer.  The two are asserted bit-identical in
``tests/test_device_index.py``; numpy stays the readable ground truth.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import numpy as np

from repro.core import splaylist as sx

PAD_KEY = np.int32(2 ** 31 - 1)


class LevelArrays(NamedTuple):
    keys: np.ndarray        # int32 [n_levels, width], +INF padded, sorted
    widths: np.ndarray      # int32 [n_levels], live entries per row
    heights: np.ndarray     # int32 [width]: splay height of bottom row keys


def _extract(st: sx.SplayState) -> Tuple[np.ndarray, np.ndarray]:
    """Alive (keys, relative heights) of a JAX splay-list state, slot
    order (host-side)."""
    s = sx.to_numpy(st)
    zl = int(s["zl"])
    idx = np.arange(st.capacity)
    alive = (idx >= 2) & (idx < int(s["n_alloc"]))
    alive &= ~s["deleted"] & (s["key"] < PAD_KEY)
    keys = s["key"][alive].astype(np.int32)
    rel_h = (s["top"][alive] - zl).astype(np.int32)
    return keys, rel_h


def from_state(st: sx.SplayState, min_levels: int = 2,
               width: Optional[int] = None) -> LevelArrays:
    """Build level arrays from a JAX splay-list state (host-side)."""
    keys, rel_h = _extract(st)
    return build(keys, rel_h, min_levels=min_levels, width=width)


def from_heights(keys: np.ndarray, rel_heights: np.ndarray,
                 **kw) -> "LevelArrays":
    return build(np.asarray(keys, np.int32),
                 np.asarray(rel_heights, np.int32), **kw)


def build(keys: np.ndarray, rel_h: np.ndarray, min_levels: int = 2,
          width: Optional[int] = None) -> LevelArrays:
    keys = np.asarray(keys, np.int32)
    rel_h = np.asarray(rel_h, np.int32)
    order = np.argsort(keys, kind="stable")
    return _assemble(keys[order], rel_h[order], min_levels, width)


def _assemble(keys_sorted: np.ndarray, rel_h: np.ndarray,
              min_levels: int, width: Optional[int]) -> LevelArrays:
    """Vectorized construction from already-sorted keys: one [L, n]
    membership mask and one prefix-sum for in-row positions."""
    n = len(keys_sorted)
    max_h = int(rel_h.max()) if n else 0
    n_levels = max(max_h + 1, min_levels)
    width = width or (n if n else 1)
    assert width >= n, (width, n)

    row_min_h = (n_levels - 1 - np.arange(n_levels)).astype(np.int32)
    mask = rel_h[None, :] >= row_min_h[:, None]            # [L, n]
    pos = np.cumsum(mask, axis=1, dtype=np.int64) - 1      # [L, n]
    widths = mask.sum(axis=1).astype(np.int32)

    rows = np.full((n_levels, width), PAD_KEY, np.int32)
    if n:
        rr, ii = np.nonzero(mask)
        rows[rr, pos[rr, ii]] = keys_sorted[ii]

    hb = np.zeros((width,), np.int32)
    hb[:n] = rel_h
    return LevelArrays(keys=rows, widths=widths, heights=hb)


def refresh(st: sx.SplayState, prev: LevelArrays,
            min_levels: int = 2) -> LevelArrays:
    """Incremental rebuild after a rebalance epoch (DESIGN.md §5.2).

    The common serving-loop case is that an epoch of updates moved
    *heights* but not *membership*: the sorted bottom row of ``prev`` is
    still the key set.  Then the O(n log n) argsort is skipped — the new
    heights are permuted into the previous sorted order via one
    searchsorted — and the (cheap, vectorized) mask/prefix pass reruns.
    The previous (n_levels, width) shape is kept whenever it still fits,
    so downstream jitted kernels see stable shapes and never recompile.

    Falls back to a full :func:`build` when keys were inserted/deleted
    or the new heights outgrow the previous level count.  A transient
    empty preserves the previous shape exactly.  Device serving loops
    use ``device_index.refresh_device`` instead, which additionally
    folds membership changes without the argsort.
    """
    keys, rel_h = _extract(st)
    width = prev.keys.shape[1]
    prev_levels = prev.keys.shape[0]
    w_bot = int(prev.widths[-1])
    if len(keys) == w_bot and w_bot > 0:
        bottom = prev.keys[-1][:w_bot]
        p = np.searchsorted(bottom, keys)
        p = np.clip(p, 0, w_bot - 1)
        if np.array_equal(bottom[p], keys):
            rel_sorted = np.empty((w_bot,), np.int32)
            rel_sorted[p] = rel_h
            lv = max(min_levels, prev_levels)
            if (int(rel_sorted.max()) + 1) <= lv:
                return _assemble(bottom, rel_sorted, lv, width)
    if len(keys) <= width:
        # keep shapes stable across epochs when capacity allows —
        # including the transient-empty epoch (len(keys) == 0), which
        # must preserve (n_levels, width) exactly so jitted consumers
        # keep their caches (regression-tested in test_level_arrays)
        lv, width_keep = prev_levels, width
        if len(keys) and int(rel_h.max()) + 1 > lv:
            lv = int(rel_h.max()) + 1
        return build(keys, rel_h, min_levels=max(lv, min_levels),
                     width=width_keep)
    return build(keys, rel_h, min_levels=min_levels)

"""Bulk load: the configuration's key set straight into a rebuilt state.

The sequential ``splaylist.run_ops`` load walks the list once per key
(minutes at 10^5 keys on one chip).  Here the sorted keys go into slots
``2 .. n+1`` of an empty state with their hit counts, and one
``splaylist.rebuild`` lays out links, counters and heights from those
counts: ``rebuild`` reads only ``key``, ``selfhits``, ``deleted`` and
``n_alloc``.  The plane is then derived with
``device_index.from_state_device``.  All of it is one jitted call on
the device; shapes depend only on the configuration, so every seed
reuses the compiled program.

``selfhits`` is 1 per key (what a load by inserts leaves) plus the key's
hits in a prior history of the cell's own read stream, thinned by the
splay coin ``p``: the heights are those of an index that has served the
traffic for a while, not the cold balanced list.
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from repro.core import device_index as dix
from repro.core import splaylist as sx


def prior_hits(stream, keys: np.ndarray, history: int, p: float,
               rng: np.random.Generator) -> np.ndarray:
    """Per-key hit counts (aligned with sorted ``keys``) of ``history``
    reads drawn from ``stream``, each kept with probability ``p``.  The
    kept reads are a Binomial(history, p) count of draws, so only those
    are drawn.  Reads of absent keys hit nothing."""
    reads = stream.reads(int(rng.binomial(history, p)))
    pos = np.searchsorted(keys, reads)
    pos_c = np.minimum(pos, len(keys) - 1)
    present = keys[pos_c] == reads
    return np.bincount(pos_c[present], minlength=len(keys)).astype(np.int32)


def bulk_load(keys: np.ndarray, selfhits: np.ndarray, capacity: int,
              levels: int, width: int):
    """``(state, plane)`` holding the sorted, distinct ``keys`` with
    ``selfhits`` (aligned), on the default device."""
    n = len(keys)
    if n > min(capacity - 2, width):
        raise ValueError(f"{n} keys do not fit capacity {capacity} / "
                         f"width {width}")
    if n and not (np.diff(keys) > 0).all():
        raise ValueError("keys must be sorted and distinct")
    key_pad = np.full(capacity - 2, sx.POS_INF_32, np.int32)
    key_pad[:n] = keys
    hits_pad = np.zeros(capacity - 2, np.int32)
    hits_pad[:n] = selfhits
    return _load(key_pad, hits_pad, np.int32(n), levels=levels,
                 width=width)


@functools.partial(jax.jit, static_argnames=("levels", "width"))
def _load(key_pad, hits_pad, n, levels, width):
    st = sx.make(key_pad.shape[0] + 2, levels)
    st = st._replace(key=st.key.at[2:].set(key_pad),
                     selfhits=st.selfhits.at[2:].set(hits_pad),
                     n_alloc=n + 2)
    st = sx.rebuild(st)
    return st, dix.from_state_device(st, n_levels=levels, width=width)

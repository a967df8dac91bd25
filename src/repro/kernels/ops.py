"""Jitted wrappers over the Pallas kernels (interpret on CPU, compiled on
TPU) + the composed two-tier hot_gather."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import hot_gather as hg
from repro.kernels import splay_search as ssk


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def exec_mode() -> str:
    """Execution-mode label for bench payloads and probe prints,
    derived from the *actual* backend (never hardcoded):
    ``compiled-tpu`` when the Pallas kernels compile, otherwise
    ``interpret-<backend>`` (e.g. ``interpret-cpu``)."""
    return ("compiled-" if on_tpu() else "interpret-") \
        + jax.default_backend()


def splay_search(level_keys, queries, query_block: int = 256,
                 widths=None, sharded=None):
    """Batched level-array search (see kernels/splay_search.py).  Queries
    of any length (the kernel wrapper pads to the block multiple and
    slices back).  ``level_keys`` may be a bare [L, W] matrix or an index
    plane struct (``DeviceLevelArrays``/``LevelArrays``) — the struct's
    precomputed widths skip counting them on the fly.  A concretely
    width-sharded plane dispatches to the sharded search
    (``sharded=None`` auto-detects; True/False force either path —
    DESIGN.md §5.5)."""
    return ssk.splay_search(
        level_keys, queries, query_block=query_block,
        interpret=not on_tpu(), widths=widths, sharded=sharded)


def splay_search_sharded(plane, queries, query_block: int = 256,
                         mesh=None, axis: str = "model",
                         routed: bool = True, capacity: int = None,
                         slack: float = ssk.DEFAULT_ROUTE_SLACK,
                         return_stats: bool = False):
    """Width-sharded tiered search: by default the routed all_to_all
    query exchange — owner-bucketed blocks shipped to the shard owning
    their bottom-row rank window, O(q/S) kernel work per shard, spill
    to the replicate-and-mask trace past ``capacity`` (see
    kernels/splay_search.py, DESIGN.md §5.6; ``routed=False`` keeps the
    masked full-batch trace).  Falls back to the replicated path when
    no mesh resolves or the width is indivisible."""
    return ssk.splay_search_sharded(
        plane, queries, query_block=query_block,
        interpret=not on_tpu(), mesh=mesh, axis=axis, routed=routed,
        capacity=capacity, slack=slack, return_stats=return_stats)


def splay_predecessor(plane, queries, query_block: int = 256,
                      sharded=None):
    """Largest live key ``<= q`` and its packed-global rank —
    ``(keys [q], ranks [q])`` int32; ``(NEG_INF_KEY, -1)`` when no
    predecessor exists.  One descent + one select gather; dispatches
    replicated/sharded like :func:`splay_search` (DESIGN.md §5.10)."""
    return ssk.splay_predecessor(
        plane, queries, query_block=query_block,
        interpret=not on_tpu(), sharded=sharded)


def splay_successor(plane, queries, query_block: int = 256,
                    sharded=None):
    """Smallest live key ``>= q`` and its packed-global rank —
    ``(keys [q], ranks [q])`` int32; ``(PAD_KEY, live_count)`` when no
    successor exists (DESIGN.md §5.10)."""
    return ssk.splay_successor(
        plane, queries, query_block=query_block,
        interpret=not on_tpu(), sharded=sharded)


def splay_rank(plane, queries, query_block: int = 256, sharded=None):
    """Number of live keys ``<= q`` (int32 [q]) — the descent's
    bottom-row predecessor index plus one; one search call
    (DESIGN.md §5.10)."""
    return ssk.splay_rank(
        plane, queries, query_block=query_block,
        interpret=not on_tpu(), sharded=sharded)


def splay_select(plane, ranks, sharded=None, mesh=None,
                 axis: str = "model"):
    """Live key at packed-global rank ``r`` (int32 [q]); ``PAD_KEY``
    outside ``[0, live_count)``.  Sharded execution gathers each rank
    from its owning shard's live-lane interval and stitches with one
    psum (DESIGN.md §5.10)."""
    return ssk.splay_select(plane, ranks, sharded=sharded, mesh=mesh,
                            axis=axis)


def splay_range_count(plane, lo, hi, query_block: int = 256,
                      sharded=None):
    """Live keys in the inclusive range ``[lo, hi]`` (int32 [q]; 0 for
    empty/inverted ranges) — a rank pair from one batched descent
    (DESIGN.md §5.10)."""
    return ssk.splay_range_count(
        plane, lo, hi, query_block=query_block,
        interpret=not on_tpu(), sharded=sharded)


def splay_range_scan(plane, lo, hi, max_range: int,
                     query_block: int = 256, sharded=None):
    """Range members in key order: ``(keys [q, max_range], count [q],
    truncated [q])`` — ``count`` is the full population, ``truncated``
    what the static ``max_range`` capacity cut (counted, never silent);
    unused lanes hold ``PAD_KEY`` (DESIGN.md §5.10)."""
    return ssk.splay_range_scan(
        plane, lo, hi, max_range, query_block=query_block,
        interpret=not on_tpu(), sharded=sharded)


def splay_top_k(plane, hits, k: int, sharded=None, mesh=None,
                axis: str = "model"):
    """The ``k`` hottest live keys by slot-indexed hit mass (the
    state's ``selfhits``): ``(keys [k], hits [k], ranks [k])`` in
    descending hit order, ties by ascending rank; ``(PAD_KEY, 0, -1)``
    past the live count (DESIGN.md §5.10)."""
    return ssk.splay_top_k(plane, hits, k, sharded=sharded, mesh=mesh,
                           axis=axis)


@functools.partial(jax.jit, static_argnames=())
def hot_gather(table, hot_buf, hot_rank, ids):
    """Two-tier gather: out[i] = hot_buf[hot_rank[ids[i]]] if hot else
    table[ids[i]].  Hot ids hit the VMEM-resident buffer; only cold ids
    stream HBM rows."""
    r = hot_rank[ids]
    is_hot = r >= 0
    hot_out = hg.gather_hot(hot_buf, jnp.maximum(r, 0),
                            interpret=not on_tpu())
    cold_out = hg.gather_rows(table, jnp.where(is_hot, 0, ids),
                              interpret=not on_tpu())
    return jnp.where(is_hot[:, None], hot_out, cold_out)

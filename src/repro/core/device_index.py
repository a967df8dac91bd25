"""Device-resident splay index plane (DESIGN.md §5.3).

The level-array rectangle (``core/level_arrays.py``) started life as a
host-side export: every rebalance epoch round-tripped the ``SplayState``
through ``to_numpy``, paid a host argsort on membership change, and
re-uploaded the whole ``[L, W]`` matrix — exactly the
adaptivity-vs-throughput tension the splay-list exists to resolve.  This
module keeps the same layout but makes it live where it is consumed:

  * :class:`DeviceLevelArrays` — the rectangle as jnp arrays (a pytree;
    passes straight through jit/scan and into the Pallas search
    wrappers), plus a ``slots`` companion mapping bottom-row keys to
    their state slots so epoch refreshes are pure gathers;
  * :func:`build_device` / :func:`from_state_device` — jitted full
    construction (device co-sort + the row layout of
    ``level_arrays._assemble``, by mask and sort);
  * :func:`refresh_device` — jitted incremental rebuild: alive
    keys/heights are read from the state *on device*, inserted keys are
    merged into the previous sorted bottom row by one sort (deletions
    are masked out by absence), and the row re-layering reruns —
    no full-membership sort, no host transfer, no shape change; with
    ``return_overflow=True`` it also reports the alive keys it could
    not represent (DESIGN.md §5.4 rebuild protocol);
  * :func:`refresh_device_sharded` — the same pipeline under
    ``shard_map`` over the ``splay_width`` logical axis: each shard
    owns a contiguous key range of the sorted bottom row, the boundary
    table travels by a scalar ``all_gather`` (suffix-min of block-first
    keys), prefix sums compose via exclusive cross-shard scans, and
    overflow is all-reduced — the scaling path for planes larger than
    one device's memory.  ``split="mass"`` (DESIGN.md §5.6) re-places
    the shard boundaries at the hit-counter mass quantiles each epoch,
    emitting a *segmented* plane whose routed-search load balances
    under skew.

Stream compaction is a sort: each row of the plane is laid out by one
single-operand ``lax.sort`` along the width axis of the bottom row with
the row's non-members lifted to ``PAD_KEY`` (the bottom row is
ascending on its live lanes, so the members come first, in order), and
the insert merge is one sort of the survivors with the new keys that
carries their heights and slots.  No per-lane gather reads through a
permutation afterwards; the width-sharded refresh sorts each shard's
own block the same way and shifts the resulting runs into place.  The
inverse prefix sum the sort replaced (:func:`_compact_take`, a binary
search per output lane, kept for the plane auditor and as the tests'
oracle) runs one dependent gather per lane per search round: on a v5e
at ``L = 17, W = 131072`` it took 451 ms a call where a 3-operand
compaction sort took 4.4 ms (a scatter of each payload to its prefix
count took 33 ms).
The epoch's newly inserted keys are ordered by ``splaylist.key_order``
and the smallest ``max_new`` kept.

Shape-stability contract: a plane's ``(n_levels, width)`` is fixed at
creation and every ``refresh_device`` preserves it, so jit caches
survive epochs (transient empties included).  ``n_levels`` must bound
the maximum relative height (``state.max_level`` always does; smaller
bounds are fine when the workload's heights are known to be capped) and
``width`` must bound the alive-key count (``capacity - 2`` always
does).  Within those bounds the output is bit-identical to the host
``level_arrays.build`` on the same state — asserted differentially in
``tests/test_device_index.py``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import splaylist as sx

# one canonical sentinel: the splay-list's +INF key is also the level
# arrays' pad value (the host oracle's level_arrays.PAD_KEY equals it)
PAD_KEY = sx.POS_INF_32


class DeviceLevelArrays(NamedTuple):
    """The TPU-native splay layout, device-resident (the fields and
    semantics of ``level_arrays.LevelArrays`` plus the slot map and the
    residency set).

    Arrays are *global*: a width-sharded plane
    (``sharding.shard_index_plane``) keeps these exact shapes and
    values and only changes placement — ``keys`` splits its width
    dimension over the mesh's model axis, ``heights``/``slots``
    likewise, ``widths`` replicates.  ``slots`` pad lanes
    (columns at or beyond the bottom row's live width) are unspecified
    and must not be read."""
    keys: jax.Array        # int32 [L, W], +INF padded, sorted, nested
    widths: jax.Array      # int32 [L], live entries per row
    heights: jax.Array     # int32 [W], splay height of bottom-row keys
    slots: jax.Array       # int32 [W], state slot of bottom-row key j
    #                        (-1 when unknown: refresh falls back to the
    #                        scatter path for the epoch and re-derives it)
    # --- segmented-provenance residency (DESIGN.md §5.8) --------------
    # The §5.6 mass-split refresh materializes each shard's local
    # [L, W/S] sub-plane; these fields keep its ingredients resident so
    # the sharded search consumes keys blocks AS the local sub-plane
    # instead of re-deriving it per batch.  local_ok is the staleness
    # bit: 1 only when keys blocks are per-shard local sub-planes (set
    # by refresh_device_sharded's mass split); every replicated
    # builder/refresh resets it to 0, sending the search back to the
    # per-batch assemble fallback.
    local_bot: jax.Array      # int32 [W], shard's own sorted bottom
    #                           segment (+INF padded within its block)
    local_heights: jax.Array  # int32 [W], aligned splay heights
    local_live: jax.Array     # int32 [W], 1 on live local_bot lanes
    local_ok: jax.Array       # int32 [1], residency validity bit

    @property
    def n_levels(self) -> int:
        return self.keys.shape[0]

    @property
    def width(self) -> int:
        return self.keys.shape[1]


def _compact_take(cs: jax.Array, width: int) -> jax.Array:
    """Inverse of a 0/1 prefix sum: take[j] = index of the j-th marked
    element (cs is the inclusive cumsum of the mark vector).  The gather
    formulation of stream compaction — no scatter."""
    col = jnp.arange(width, dtype=jnp.int32)
    return jnp.minimum(jnp.searchsorted(cs, col + 1).astype(jnp.int32),
                       width - 1)


def _assemble_device(keys_sorted: jax.Array, rel_h: jax.Array,
                     slots: jax.Array, n_levels: int) -> DeviceLevelArrays:
    """The device counterpart of ``level_arrays._assemble``:
    ``keys_sorted`` [W] holds the live keys sorted ascending in a
    prefix, PAD_KEY after; ``rel_h``/``slots`` [W] are aligned (pad lanes
    ignored).  Row compaction is one single-operand sort along the width
    axis: row r keeps the bottom-row keys it holds and lifts the rest to
    PAD_KEY, and since the live keys ascend, the sort packs the row's
    members first, in order — nothing is gathered afterwards."""
    alive = keys_sorted != PAD_KEY
    h = jnp.where(alive, rel_h, -1)

    row_min_h = (n_levels - 1 - jnp.arange(n_levels, dtype=jnp.int32))
    mask = h[None, :] >= row_min_h[:, None]                # [L, W]
    widths = jnp.sum(mask, axis=1, dtype=jnp.int32)
    with jax.named_scope("splay.compact"):
        rows = jax.lax.sort(jnp.where(mask, keys_sorted, PAD_KEY),
                            dimension=1, is_stable=False)

    heights = jnp.where(alive, rel_h, 0).astype(jnp.int32)
    return DeviceLevelArrays(
        keys=rows, widths=widths, heights=heights, slots=slots,
        # residency defaults: the assembled inputs are recorded as
        # provenance, but the validity bit stays 0 — only the sharded
        # mass-split refresh may promote a plane to resident (its blocks
        # are then genuinely per-shard local sub-planes).
        local_bot=keys_sorted.astype(jnp.int32),
        local_heights=heights,
        local_live=alive.astype(jnp.int32),
        local_ok=jnp.zeros((1,), jnp.int32))


@functools.partial(jax.jit, static_argnames=("n_levels",))
def build_device(keys: jax.Array, rel_h: jax.Array,
                 n_levels: int) -> DeviceLevelArrays:
    """Full on-device build from bare (keys, heights): ``keys`` [W]
    int32 with PAD_KEY in dead lanes, ``rel_h`` [W] aligned.  One stable
    device co-sort (live keys are < PAD_KEY so they land in a sorted
    prefix), then the shared row layout.  The slot map is unknown
    (-1): fine for kernel fixtures; planes that will be *refreshed*
    against a state should come from :func:`from_state_device`, which
    fills it (a -1 slot map just makes the first refresh take the
    scatter fallback and re-derive it).

    Sharding: replicated math — inputs/outputs live whole on each
    device; lay the result out width-sharded afterwards with
    ``sharding.shard_index_plane``.  Failure modes: more than ``width``
    live keys cannot be represented (the largest keys silently pad out
    — size ``width`` to bound the key count); heights above
    ``n_levels - 1`` saturate into row 0."""
    keys = keys.astype(jnp.int32)
    h = jnp.where(keys != PAD_KEY, rel_h.astype(jnp.int32), 0)
    ks, hs = jax.lax.sort((keys, h), num_keys=1)
    slots = jnp.full((keys.shape[0],), -1, jnp.int32)
    return _assemble_device(ks, hs, slots, n_levels)


def _alive_slots(st: sx.SplayState) -> Tuple[jax.Array, jax.Array]:
    """Alive (keys, relative heights) in slot order, [capacity]-shaped —
    the device analogue of ``level_arrays._extract`` (no ``to_numpy``).
    Dead lanes hold PAD_KEY / 0."""
    idx = jnp.arange(st.capacity)
    alive = ((idx >= 2) & (idx < st.n_alloc) & (~st.deleted)
             & (st.key < sx.POS_INF_32))
    keys = jnp.where(alive, st.key, PAD_KEY).astype(jnp.int32)
    rel_h = jnp.where(alive, st.top - st.zl, 0).astype(jnp.int32)
    return keys, rel_h


@functools.partial(jax.jit, static_argnames=("n_levels", "width"))
def from_state_device(st: sx.SplayState, n_levels: int,
                      width: int) -> DeviceLevelArrays:
    """Build a fresh plane from a splay-list state, fully on device.
    ``width`` must bound the alive-key count (``capacity - 2`` always
    does); ``n_levels`` must bound relative heights (``max_level``
    always does).

    This is also the overflow-recovery rebuild: after a refresh reports
    nonzero overflow, one ``from_state_device`` at the same (static)
    shape folds every dropped key back in (``splaylist.run_epoch``
    schedules it automatically; DESIGN.md §5.4).  Sharding: replicated
    math, like :func:`build_device`.  Failure modes: alive counts
    beyond ``width`` truncate (largest keys) — undetectable here, but
    counted by the refresh paths' ``overflow_count``."""
    keys, rel_h = _alive_slots(st)
    sl = sx.key_order(keys, keys != PAD_KEY)
    ks, hs = keys[sl], rel_h[sl]
    if st.capacity < width:                # small states pad out
        pad = width - st.capacity
        ks = jnp.pad(ks, (0, pad), constant_values=PAD_KEY)
        hs = jnp.pad(hs, (0, pad))
        sl = jnp.pad(sl, (0, pad), constant_values=-1)
    return _assemble_device(ks[:width], hs[:width], sl[:width], n_levels)


def _merge_rows(bottom, surv, old_h, slots_eff, ns, new_h, new_slots,
                out_len):
    """Two-way merge of the surviving previous bottom row with the
    sorted inserted keys: one sort of the ``[W + kk]`` concatenation of
    the survivors (dead lanes PAD_KEY) and ``ns``, carrying heights and
    slots.  Inserted keys are never in the previous bottom row, so live
    keys never tie; the ``n_old + n_new`` live keys come first and every
    lane after them is PAD_KEY (their heights and slots are unspecified
    and never read).

    ``out_len`` is the emitted row length — ``width`` for the replicated
    refresh (merged lanes beyond it are truncated, flagged upstream as
    overflow), ``width + kk`` for the per-shard merge of the sharded
    refresh, whose local segment must never truncate (the global
    redistribution repacks it)."""
    with jax.named_scope("splay.compact"):
        merged = jax.lax.sort(
            (jnp.concatenate([jnp.where(surv, bottom, PAD_KEY), ns]),
             jnp.concatenate([old_h, new_h]),
             jnp.concatenate([slots_eff, new_slots])),
            num_keys=1, is_stable=False)
    return tuple(m[:out_len] for m in merged)


@functools.partial(jax.jit,
                   static_argnames=("max_new", "return_overflow"))
def refresh_device(st: sx.SplayState, prev: DeviceLevelArrays,
                   max_new: int = 1024, return_overflow: bool = False):
    """Incremental on-device rebuild after a rebalance epoch.

    Membership changes are folded without re-sorting the key set (the
    batch-merge formulation of concurrent rebuilds, arXiv 2309.09359):

      1. every alive slot is classified old/new by one ``searchsorted``
         against the previous sorted bottom row;
      2. surviving old keys keep their relative order — their heights
         come back through the plane's slot map (pure gathers); deleted
         keys are masked out by absence;
      3. the newly inserted keys are extracted *sorted*, at most
         ``max_new`` of them (size it by the number of inserts since
         the last refresh; the *smallest* keys are kept, inserts beyond
         the bound are dropped from the plane until the next full
         build), then merged with the survivors by one sort that
         carries heights and slots;
      4. the row re-layering reruns on the merged row.

    The slot map is validated against the state (``rebuild`` compacts
    slots); a stale or absent map routes that epoch through a scatter
    fallback which also re-derives it, so correctness never depends on
    the map.  Output shape equals ``prev``'s — stable across epochs,
    transient empties included — so jitted consumers never recompile.
    Keys whose relative height exceeds ``n_levels - 1`` saturate into
    row 0 (pick ``n_levels = state.max_level`` to rule this out); alive
    counts beyond ``width`` cannot be represented — size the plane by
    ``capacity - 2`` to rule that out too.

    Sharding: every input is replicated math — state and plane live in
    full on each device (use :func:`refresh_device_sharded` for a
    width-sharded plane).  Failure modes are *counted, not raised*: with
    ``return_overflow=True`` the result is ``(plane, overflow_count)``
    where ``overflow_count`` (int32 scalar) is the number of alive keys
    the refreshed plane could not represent — inserts beyond ``max_new``
    plus merged lanes beyond ``width``.  A nonzero count means the plane
    is *stale, not corrupt*: it still indexes the keys it holds, and a
    full :func:`from_state_device` rebuild (which ``splaylist.run_epoch``
    schedules automatically on the next epoch) restores exactness —
    unless the alive count itself exceeds ``width``, which no same-shape
    rebuild can fix; rebuild wider at the host level.
    """
    n_levels, width = prev.keys.shape
    cap = st.capacity
    k_slot, h_slot = _alive_slots(st)
    alive = k_slot != PAD_KEY

    bottom = prev.keys[n_levels - 1]                       # [W] sorted
    w_bot = prev.widths[n_levels - 1]
    col = jnp.arange(width, dtype=jnp.int32)
    lane = col < w_bot

    # ---- old keys: gather through the slot map ---------------------------
    sc = jnp.clip(prev.slots, 0, cap - 1)
    match = lane & (jnp.take(st.key, sc).astype(jnp.int32) == bottom)
    stale = jnp.any(lane & ~match)

    # state-side classification: which alive slots are inserts
    p = jnp.searchsorted(bottom, k_slot).astype(jnp.int32)
    pc = jnp.clip(p, 0, width - 1)
    is_new = alive & (jnp.take(bottom, pc) != k_slot)

    def via_map(_):
        surv = match & ~jnp.take(st.deleted, sc)
        return surv, sc

    def via_scatter(_):
        # stale/absent slot map (a rebuild compacted the state, or the
        # plane came from build_device): re-derive it for this epoch
        is_old = alive & ~is_new
        dst = jnp.where(is_old, pc, width)
        surv = jnp.zeros((width,), bool).at[dst].set(True, mode="drop")
        slots = jnp.full((width,), -1, jnp.int32).at[dst].set(
            jnp.arange(cap, dtype=jnp.int32), mode="drop")
        return surv, slots

    surv, slots_eff = jax.lax.cond(stale, via_scatter, via_map,
                                   operand=None)
    old_h = (jnp.take(st.top, jnp.clip(slots_eff, 0, cap - 1))
             - st.zl).astype(jnp.int32)

    # ---- new keys: bounded top_k extracts them already sorted ------------
    kk = min(max_new, cap)
    n_new_raw = jnp.sum(is_new.astype(jnp.int32))
    n_new = jnp.minimum(n_new_raw, kk)

    def extract_new(_):
        new_slots = sx.key_order(k_slot, is_new)[:kk]
        ns = jnp.where(jnp.arange(kk) < n_new,
                       jnp.take(k_slot, new_slots), PAD_KEY)
        new_h = (jnp.take(st.top, new_slots) - st.zl).astype(jnp.int32)
        return ns, new_h, new_slots

    def no_new(_):
        z = jnp.zeros((kk,), jnp.int32)
        return jnp.full((kk,), PAD_KEY, jnp.int32), z, z

    ns, new_h, new_slots = jax.lax.cond(n_new > 0, extract_new, no_new,
                                        operand=None)

    # height-only epoch (the common serving case): the merge is the
    # identity over the previous bottom row — skip the merge sort
    n_old = jnp.sum(surv.astype(jnp.int32))

    def identity_merge(_):
        return bottom, old_h, slots_eff

    def merge(_):
        return _merge_rows(bottom, surv, old_h, slots_eff, ns, new_h,
                           new_slots, width)

    merged_k, merged_h, merged_s = jax.lax.cond(
        (n_new == 0) & (n_old == w_bot), identity_merge, merge,
        operand=None)
    plane = _assemble_device(merged_k, merged_h, merged_s, n_levels)
    if not return_overflow:
        return plane
    overflow = ((n_new_raw - n_new)
                + jnp.maximum(n_old + n_new - width, 0)).astype(jnp.int32)
    return plane, overflow


# ---------------------------------------------------------------------------
# width-sharded refresh (DESIGN.md §5.4): the same pipeline under shard_map
# ---------------------------------------------------------------------------

def _refresh_device_shard(st: sx.SplayState, prev: DeviceLevelArrays, *,
                          axis: str, n_shards: int, n_levels: int,
                          width: int, max_new: int, split: str = "lanes"):
    """Per-shard body of :func:`refresh_device_sharded` (runs under
    ``shard_map``; ``prev`` leaves are this shard's blocks, the state is
    replicated).  Stages mirror the replicated refresh — classification,
    bounded extraction, merge, re-layering — with three collectives
    stitching the shards together:

      1. *halo/boundary exchange* (``ppermute`` + scalar ``all_gather``):
         each shard's owned key range is [its block's first bottom-row
         key, the right neighbour's first key) — the range-boundary
         table of the sorted bottom row;
      2. *cross-shard exclusive scans* (``all_gather`` of per-shard
         totals + cumsum): compose the new-key drop cap, the merged-row
         offsets, and every level's prefix sum globally;
      3. *segment redistribution* (``all_gather`` of the compacted local
         merges): membership churn moves keys across shard boundaries
         arbitrarily far (a delete burst can empty whole shards), so the
         packed global bottom row is rebuilt from the bounded per-shard
         segments rather than fixed-radius halos.

    Every collective and the arithmetic that composes its result sit in
    the ``splay.redistribute`` scope, so a profile tells the exchange
    apart from each shard's own classification, merge and compaction.
    The function's name carries ``refresh_device``: a profile reader
    that names layers by jitted function puts it in the refresh.

    The re-layering sorts each shard's own ``[L, W/S]`` block once
    (``_assemble_device``, one single-operand sort) and places every
    level row's runs by whole-run shifts: each shard's members of a row
    hold a contiguous range of the row's global ranks, so no lane
    searches the composed ``[W]`` row.

    Budget per shard and epoch: resident state O(L·W/S) (its plane
    blocks) + O(W) transient buffers (the [L, W] rectangle is never
    materialized on one shard — the placement streams one row per scan
    step); compute O(capacity·log(W/S)) for the classification, one
    sort of ``[L, W/S]`` for the re-layering and O(L·W) lane moves for
    the placement, the local merge one sort of W/S + max_new lanes;
    wire O(W + S·max_new) for the segment exchange plus W received
    per level row of the placement."""
    S = n_shards
    wl = width // S
    cap = st.capacity
    kk = min(max_new, cap)
    ax = jax.lax.axis_index(axis)
    col_l = jnp.arange(wl, dtype=jnp.int32)
    col_g = (ax * wl + col_l).astype(jnp.int32)

    bot_l = prev.keys[n_levels - 1]                    # [wl] own block

    # ---- owned key range from the §5.4 boundary table, generalized to
    # the suffix-min of block-first keys: a *segmented* prev plane (the
    # §5.6 mass-weighted split) can leave an interior block empty, whose
    # raw +INF first key must not shadow the live blocks to its right
    # (a one-element ppermute halo would double-claim their range).  On
    # a packed prev only trailing blocks are empty, the suffix-min is
    # the identity, and lo/hi equal the PR-3 halo construction exactly.
    # The same helper builds the search's query-routing table — refresh
    # and search must agree on ownership for every layout.
    from repro.parallel import sharding as shd
    with jax.named_scope("splay.redistribute"):
        raw = jax.lax.all_gather(
            jnp.where(ax == 0, jnp.int32(sx.NEG_INF_32), bot_l[0]), axis)
        bounds = shd.suffix_min_bounds(raw)
        lo = bounds[ax]
        hi = jnp.where(ax == S - 1, jnp.int32(PAD_KEY),
                       bounds[jnp.minimum(ax + 1, S - 1)])

    # ---- slot-map validation (staleness is a global verdict, psum'd,
    # so every shard takes the same branch as the replicated refresh).
    # Live lanes are a prefix of the *block* — the global prefix mask
    # `col_g < w_bot` only on packed planes, so count them per block
    # (identical masks there; also correct on segmented planes).
    lane = col_l < jnp.sum((bot_l != PAD_KEY).astype(jnp.int32))
    sc = jnp.clip(prev.slots, 0, cap - 1)
    match = lane & (jnp.take(st.key, sc).astype(jnp.int32) == bot_l)
    with jax.named_scope("splay.redistribute"):
        stale = jax.lax.psum(
            jnp.any(lane & ~match).astype(jnp.int32), axis) > 0

    # ---- state-side classification, restricted to the owned range
    k_slot, _ = _alive_slots(st)
    alive = k_slot != PAD_KEY
    owned = alive & (k_slot >= lo) & (k_slot < hi)
    p = jnp.searchsorted(bot_l, k_slot).astype(jnp.int32)
    pc = jnp.clip(p, 0, wl - 1)
    in_block = owned & (jnp.take(bot_l, pc) == k_slot)
    is_new = owned & ~in_block

    def via_map(_):
        surv = match & ~jnp.take(st.deleted, sc)
        return surv, sc

    def via_scatter(_):
        dst = jnp.where(in_block, pc, wl)
        surv = jnp.zeros((wl,), bool).at[dst].set(True, mode="drop")
        slots = jnp.full((wl,), -1, jnp.int32).at[dst].set(
            jnp.arange(cap, dtype=jnp.int32), mode="drop")
        return surv, slots

    surv, slots_eff = jax.lax.cond(stale, via_scatter, via_map,
                                   operand=None)
    old_h = (jnp.take(st.top, jnp.clip(slots_eff, 0, cap - 1))
             - st.zl).astype(jnp.int32)

    # ---- new keys: per-shard bounded top_k + the cross-shard drop cap.
    # Ranges ascend with the shard index, so "the globally smallest kk
    # new keys" = take shards left-to-right until the budget is spent —
    # an exclusive scan of raw counts reproduces the replicated drop
    # semantics exactly.
    raw = jnp.sum(is_new.astype(jnp.int32))
    with jax.named_scope("splay.redistribute"):
        raws = jax.lax.all_gather(raw, axis)           # [S]
        left = jnp.sum(jnp.where(jnp.arange(S) < ax, raws, 0))
        total_raw = jnp.sum(raws)
    n_new = jnp.clip(kk - left, 0, jnp.minimum(raw, kk))

    def extract_new(_):
        new_slots = sx.key_order(k_slot, is_new)[:kk]
        ns = jnp.where(jnp.arange(kk) < n_new,
                       jnp.take(k_slot, new_slots), PAD_KEY)
        new_h = (jnp.take(st.top, new_slots) - st.zl).astype(jnp.int32)
        return ns, new_h, new_slots

    def no_new(_):
        z = jnp.zeros((kk,), jnp.int32)
        return jnp.full((kk,), PAD_KEY, jnp.int32), z, z

    ns, new_h, new_slots = jax.lax.cond(n_new > 0, extract_new, no_new,
                                        operand=None)

    # ---- local merge into a bounded segment (never truncates: the
    # global repack below owns the width-overflow accounting)
    m_len = wl + kk
    seg_k, seg_h, seg_s = _merge_rows(
        bot_l, surv, old_h, slots_eff, ns, new_h, new_slots, m_len)
    c = jnp.sum(surv.astype(jnp.int32)) + n_new

    # ---- redistribution: exclusive scan of segment counts composes the
    # global packed bottom row; each output lane gathers from the shard
    # segment that covers its global rank
    with jax.named_scope("splay.redistribute"):
        counts = jax.lax.all_gather(c, axis)           # [S]
        cum = jnp.cumsum(counts)
        offs = cum - counts
        total = cum[S - 1]
        segs_k = jax.lax.all_gather(seg_k, axis)       # [S, m_len]
        segs_h = jax.lax.all_gather(seg_h, axis)
        segs_s = jax.lax.all_gather(seg_s, axis)

    def pick(segs, pos, fill):
        with jax.named_scope("splay.redistribute"):
            t = jnp.searchsorted(cum, pos, side="right").astype(jnp.int32)
            tc = jnp.clip(t, 0, S - 1)
            li = jnp.clip(pos - jnp.take(offs, tc), 0, m_len - 1)
            v = jnp.take(segs.reshape(S * m_len), tc * m_len + li)
            return jnp.where(pos < total, v, fill)

    overflow = (jnp.maximum(total_raw - kk, 0)
                + jnp.maximum(total - width, 0)).astype(jnp.int32)

    if split == "mass":
        # ---- §5.6 mass-weighted re-split: instead of packing the
        # merged row wall-to-wall, choose shard boundaries at the
        # access-mass quantiles of the state's hit counters (selfhits
        # gathered through the merged slot ids — the same counters the
        # splay heights are maintained from; unknown slots weigh 1, so
        # a counterless plane degrades to the lane-equal split) and
        # give each shard its segment [b_s, b_{s+1}) packed into its
        # own block prefix, +INF pads after.  The plane becomes
        # *segmented*: per-block sorted runs with pads at segment
        # boundaries — searched correctly ONLY by the sharded search
        # (keys/heights hold each shard's local sub-plane;
        # widths stays the global per-row live count).
        pos_g = jnp.arange(width, dtype=jnp.int32)
        keys_g = pick(segs_k, pos_g, jnp.int32(PAD_KEY))   # [W] merged
        hts_g = pick(segs_h, pos_g, jnp.int32(0))
        total_c = jnp.minimum(total, width)
        slot_g = pick(segs_s, pos_g, jnp.int32(-1))    # [W] packed slots
        # per-key mass saturates at 2^16 so the int32 cumsum stays
        # exact for any plane width this repo reaches (W * 2^16 < 2^31
        # for W <= 2^14) however long the counters accumulate — the
        # quantiles only need ~M/S granularity, which a 65536x hot/cold
        # contrast delivers with room to spare
        sh_g = jnp.minimum(
            jnp.take(st.selfhits,
                     jnp.clip(slot_g, 0, cap - 1)).astype(jnp.int32),
            jnp.int32(2 ** 16))
        mass = jnp.where(pos_g < total_c,
                         1 + jnp.where(slot_g >= 0, sh_g, 0), 0)
        bounds_r = shd.mass_split_bounds(jnp.cumsum(mass), total_c,
                                         S, wl)
        b_lo = bounds_r[ax]
        seg_live = col_l < bounds_r[ax + 1] - b_lo
        src = jnp.clip(b_lo + col_l, 0, width - 1)
        k_seg = jnp.where(seg_live, jnp.take(keys_g, src),
                          jnp.int32(PAD_KEY))
        h_seg = jnp.where(seg_live, jnp.take(hts_g, src), 0)
        s_seg = jnp.where(seg_live, jnp.take(slot_g, src), -1)
        local = _assemble_device(k_seg, h_seg, s_seg, n_levels)
        with jax.named_scope("splay.redistribute"):
            widths_g = jax.lax.psum(local.widths, axis)
        # keys ARE this shard's local sub-plane here —
        # record the segment they were assembled from and set the
        # residency bit, so the sharded search consumes them directly
        # instead of re-running _assemble_device per batch (§5.8).
        plane = local._replace(
            widths=widths_g,
            local_bot=k_seg, local_heights=local.heights,
            local_live=(k_seg != PAD_KEY).astype(jnp.int32),
            local_ok=jnp.ones((1,), jnp.int32))
        return plane, overflow

    # ---- re-layering of the shard's own columns of the packed row.
    # Each shard lays out its [L, W/S] block by one sort
    # (_assemble_device, the replicated refresh's compaction): row r's
    # local members come first, in key order.  An exclusive cross-shard
    # scan of per-row totals then places them: shard t's row-r run
    # holds the row's members of global ranks [row_offs[t, r],
    # row_offs[t, r] + tots[t, r]), and these runs tile the row in
    # shard order.
    k_own = pick(segs_k, col_g, jnp.int32(PAD_KEY))
    h_own = pick(segs_h, col_g, jnp.int32(0))
    slots_own = pick(segs_s, col_g, jnp.int32(-1))
    local = _assemble_device(k_own, h_own, slots_own, n_levels)
    with jax.named_scope("splay.redistribute"):
        tots = jax.lax.all_gather(local.widths, axis)  # [S, L]
        row_offs = jnp.cumsum(tots, axis=0) - tots     # [S, L] exclusive
        widths_g = jnp.sum(tots, axis=0)               # [L] global

    # ---- placement, one level row per scan step (an all_gather of the
    # row's runs per step: a shard holds O(W) transient buffers, never
    # the [L, W] rectangle — that is what lets the plane outgrow one
    # device's memory).  Output lane g of this shard takes run t's
    # member g - row_offs[t, r]: a shift of each run by a whole offset,
    # so every run is sliced once at that offset (zero-padded by a
    # block each side) and kept where it covers; lanes no run covers
    # are the row's pads.
    def place_row(_, inp):
        run_r, offs_r, tots_r = inp                    # [wl], [S], [S]
        with jax.named_scope("splay.redistribute"):
            got = jax.lax.all_gather(run_r, axis)      # [S, wl]
            got = jnp.pad(got, ((0, 0), (wl, wl)))
            out = jnp.full((wl,), PAD_KEY, jnp.int32)
            for t in range(S):
                shift = ax * wl - offs_r[t]
                src = shift + col_l
                cover = (src >= 0) & (src < tots_r[t])
                piece = jax.lax.dynamic_slice(
                    got[t], (jnp.clip(shift, -wl, wl) + wl,), (wl,))
                out = jnp.where(cover, piece, out)
        return None, out

    _, rows_own = jax.lax.scan(
        place_row, None,
        (local.keys, jnp.transpose(row_offs), jnp.transpose(tots)))
    heights_own = local.heights

    plane = DeviceLevelArrays(
        keys=rows_own, widths=widths_g, heights=heights_own,
        slots=slots_own,
        # lanes split keeps the packed global layout: keys blocks are
        # global-row columns, NOT local sub-planes, so residency stays
        # invalid (the search assembles per batch)
        local_bot=k_own, local_heights=heights_own,
        local_live=(k_own != PAD_KEY).astype(jnp.int32),
        local_ok=jnp.zeros((1,), jnp.int32))
    return plane, overflow


@functools.lru_cache(maxsize=None)
def _sharded_refresh_fn(mesh, axis: str, n_levels: int, width: int,
                        max_new: int, split: str = "lanes"):
    """Build (and cache) the jitted shard_map for one (mesh, axis,
    shape, max_new, split) cell — planes are shape-stable, so serving
    reuses one entry per mesh."""
    from repro.parallel import sharding as shd
    from jax.sharding import PartitionSpec as P
    S = mesh.shape[axis]
    specs = shd.index_plane_specs(DeviceLevelArrays, axis)
    body = functools.partial(
        _refresh_device_shard, axis=axis, n_shards=S, n_levels=n_levels,
        width=width, max_new=max_new, split=split)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), specs),
                       out_specs=(specs, P()), check_vma=False)
    return jax.jit(fn)


def refresh_device_sharded(st: sx.SplayState, prev: DeviceLevelArrays,
                           max_new: int = 1024, mesh=None,
                           axis: str = "model", split: str = "lanes"):
    """Width-sharded incremental refresh: :func:`refresh_device` under
    ``shard_map`` over the ``splay_width`` logical axis (DESIGN.md
    §5.4), so a plane too large for one device's memory refreshes with
    each shard owning W/S columns — a contiguous key range of the
    sorted bottom row.  New keys route to their owning shard by a
    sharded ``searchsorted`` against the range-boundary table (built
    with a one-element ``ppermute`` halo of block-first keys); rank
    offsets and level prefix sums compose globally from per-shard
    prefix sums plus exclusive cross-shard scans of shard totals.

    Sharding contract: the state is replicated (every shard classifies
    its own key range against the full state); ``prev`` should be laid
    out by ``sharding.shard_index_plane`` /
    :func:`sharding.index_plane_specs` — keys ``P(None, axis)``,
    heights/slots ``P(axis)``, widths replicated.  The result
    carries the same layout.

    Returns ``(plane, overflow_count)``.  ``overflow_count`` (int32,
    all-reduced across shards) counts alive keys the plane could not
    represent — inserts beyond ``max_new`` plus merged lanes beyond
    ``width`` (see :func:`refresh_device` for the rebuild protocol).

    ``split`` (static) picks the shard-boundary rule (DESIGN.md §5.6):
    ``"lanes"`` (default) packs the merged row wall-to-wall — equal
    lane count per shard, bit-identical to the replicated refresh;
    ``"mass"`` places the boundaries at the access-mass quantiles of
    the state's hit counters (``selfhits`` gathered through the merged
    slot ids; unknown slots weigh 1), each shard packing its segment
    into its own block prefix with +INF pads after — a *segmented*
    plane whose routed-search load balances under skew
    (``routing_max_share`` → ~1/S).  A mass-split plane must be
    searched by the *sharded* search (``kernels.splay_search``'s
    routed or masked paths handle segmented planes; the
    gather-to-replicated path assumes a packed bottom row) and is
    accepted as ``prev`` by either split mode of this refresh *on the
    sharded path*.

    Fallback modes: no mesh — neither passed nor active via
    ``sharding.use_mesh`` — or ``axis`` absent from the mesh, or
    ``width`` not divisible by the axis size, all route to the
    replicated :func:`refresh_device` (which packs — ``split`` is
    moot) with the same return convention.  One exception raises: a
    *concrete segmented* ``prev`` on that fallback (``ValueError`` —
    the replicated refresh's packed-row invariants would silently
    corrupt it; see :func:`plane_is_segmented`).

    Equivalence: on any 1×N host mesh the ``"lanes"`` result is
    bit-identical to the replicated refresh on ``keys``/``widths``/
    ``heights`` (asserted in
    ``tests/test_sharded_refresh.py``); the ``slots`` companion agrees
    on live lanes (pad lanes are unspecified in both paths and never
    read).  The ``"mass"`` result indexes the same key set (same
    bottom-row membership and heights, different column placement) —
    asserted through search-answer parity in
    ``benchmarks/sharded_search_probe.py --parity``."""
    from repro.parallel import sharding as shd
    if split not in ("lanes", "mass"):
        raise ValueError(f"split must be 'lanes' or 'mass', got {split!r}")
    mesh = mesh if mesh is not None else shd.active_mesh()
    n_levels, width = prev.keys.shape
    if (mesh is None or axis not in mesh.shape
            or width % mesh.shape[axis]):
        if plane_is_segmented(prev):
            raise ValueError(
                "segmented (mass-split) plane cannot take the "
                "replicated refresh fallback — its interior pad runs "
                "break the packed-row invariants (classification "
                "searchsorted, merge).  Pass a mesh so the sharded "
                "refresh handles it (split='lanes' repacks), or rebuild "
                "with from_state_device first")
        return refresh_device(st, prev, max_new=max_new,
                              return_overflow=True)
    fn = _sharded_refresh_fn(mesh, axis, n_levels, width, max_new, split)
    return fn(st, prev)


def plane_is_segmented(plane) -> bool:
    """True when a *concrete* plane's bottom row has interior pad runs —
    the §5.6 mass-split layout.  Segmented planes are only valid on the
    sharded refresh/search paths; the replicated ones assume a packed
    sorted row and would corrupt/answer wrongly, so their entry points
    refuse concrete segmented inputs.  Tracers return False (inside jit
    the caller owns layout discipline — keep ``mesh``/``split``
    consistent across a serving session)."""
    keys = getattr(plane, "keys", None)
    if isinstance(keys, jax.core.Tracer) or keys is None:
        return False
    import numpy as np
    live = np.asarray(keys[-1]) != PAD_KEY
    if not live.any():
        return False
    return not bool(live[: int(np.nonzero(live)[0][-1]) + 1].all())


def to_host(plane: DeviceLevelArrays):
    """Materialize as a host ``LevelArrays`` (tests / debugging only —
    the serving path never calls this).  Accepts replicated or
    width-sharded planes alike: ``np.asarray`` gathers sharded arrays
    into one host buffer."""
    import numpy as np
    from repro.core import level_arrays as la
    return la.LevelArrays(
        keys=np.asarray(plane.keys), widths=np.asarray(plane.widths),
        heights=np.asarray(plane.heights))

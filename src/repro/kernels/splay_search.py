"""Pallas TPU kernels: batched splay-list search over level arrays.

TPU adaptation of the paper's search phase (DESIGN.md §5): instead of
pointer chasing, each splay level is a dense sorted row; a query block
compares against rows top-down (row 0 = hottest).

One kernel lives here, ``splay_search`` — the tiered pipeline
(DESIGN.md §5.2).  Grid ``(query_blocks, n_levels)``; the level
matrix is tiled *per row*, viewed as ``[L, W/128, 128]`` (one row
block plus its ``[1, W/128]`` chunk-first sample row VMEM resident per
grid step: O(W) instead of O(L·W)).  The row index_map goes through a
scalar-prefetched fetch schedule that aliases statically-empty rows
(padding above the tallest key) to the next live row — consecutive
identical block indices suppress the duplicate DMA on the compiled
(TPU) path; interpret mode computes the same schedule but models no
DMA.  Within a row the predecessor index is a two-stage count with no
per-query gather (the TPU compiler lowers none from a ``[W]`` row): the
queries are compared against the samples to pick their 128-lane chunk,
a one-hot MXU product fetches that chunk, and a compare-and-count inside
it gives the offset.  ``found``/``level_found`` accumulate in revisited
output blocks.  The kernel reads a plane's ``keys`` and ``widths`` and
nothing else.

The wrapper pads the query batch to the block multiple internally and
slices the outputs back — callers never pre-pad.  It also accepts an
index plane struct (``core.device_index.DeviceLevelArrays`` or the host
``core.level_arrays.LevelArrays``) in place of the bare key matrix, in
which case the struct's row widths ride along (both the host build and
the device build/refresh emit them); a bare matrix has them counted on
the fly.

Sharding (DESIGN.md §5.5–§5.6): a plane laid out width-sharded by
``parallel.sharding.shard_index_plane`` executes the search *sharded* —
``splay_search_sharded`` runs the tiered descent under ``shard_map``
over the ``splay_width`` axis.  The default execution is the *routed
query exchange* (§5.6): the query batch enters batch-sharded, each
shard owner-buckets its slice by a sharded ``searchsorted`` over the
per-shard boundary keys (the §5.4 range-boundary table), one
``all_to_all`` ships each static-capacity bucket to its owner, the
owner runs the unmodified tiered kernel over only its O(q/S) received
block on its local ``[L, W/S]`` sub-plane, and the inverse
``all_to_all`` + a positional unpermute return the answers — per-shard
compute O((q/S)·L·log(W/S)).  Queries past a shard's capacity *spill*
to the replicate-and-mask trace (the PR-4 path, kept as
``routed=False``): counted, never dropped, bit-identical either way.
``splay_search`` dispatches here automatically for a concretely
width-sharded plane; gather-to-replicated remains the documented
fallback (no mesh, one shard, indivisible width, or ``sharded=False``).

Ordered operations (DESIGN.md §5.10): the descent's bottom-row
predecessor rank is already an order statistic, so the full ordered-op
family — ``splay_predecessor``/``splay_successor``, ``splay_rank``/
``splay_select``, ``splay_range_count``/``splay_range_scan`` (static
``max_range`` capacity, truncation counted, never silent) and
``splay_top_k`` by hit mass — derives from the same search kernel plus
packed bottom-row gathers.  Every op dispatches replicated vs. sharded
exactly like ``splay_search``; on the sharded plane a rank (or a range
of ranks) decomposes by the live-lane count prefix into per-shard
sub-ranges stitched back by one psum.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.parallel import sharding as shd

PAD_KEY = 2 ** 31 - 1
NEG_INF_KEY = -(2 ** 31) + 1        # splaylist.NEG_INF_32 (head sentinel)
DEFAULT_QUERY_BLOCK = 256
DEFAULT_ROUTE_SLACK = 1.5
# The widest plane the one-chip tiered descent compiles on a TPU v5e:
# its row block, sample row and one-hot chunk fetch fit the scoped VMEM
# at 2^20 lanes, and Mosaic refuses 2^21 and wider (RESOURCE_EXHAUSTED
# ... vmem).  A wider plane is served width-sharded, each device
# descending a block of at most this many lanes
# (``splaylist.run_serving``).
MAX_DESCENT_WIDTH = 2 ** 20


class RouteStats(NamedTuple):
    """Routing balance of one routed-exchange batch (DESIGN.md §5.6).

    ``spill`` (int32 scalar, replicated): queries answered through the
    replicate-and-mask spill path this batch — their owner's received
    block exceeded the static ``capacity`` (or their source bucket
    did).  ``occupancy`` (int32 ``[S]``, replicated): live queries
    received per shard after the exchange, *before* the capacity clamp
    — ``occupancy[s] > capacity`` is exactly the spill condition, and
    ``occupancy.sum() == q`` (every real query has one owner;
    batch-padding fill lanes are excluded from the exchange).  On
    the no-mesh replicated fallback ``spill`` is 0 and ``occupancy`` is
    the single pseudo-shard's whole batch.

    ``assembled`` (int32 scalar, replicated): shards that re-derived
    their local sub-plane through ``_assemble_device`` this batch — the
    §5.8 residency probe.  0 means the batch consumed the resident
    segmented sub-plane end to end (the steady state after a mass-split
    refresh); ``S`` means every shard paid the per-batch re-layering
    (stale residency: a replicated build/refresh touched the plane, or
    a lanes-split layout).  The no-mesh fallback reports 0 (there is no
    sub-plane to assemble)."""
    spill: jax.Array
    occupancy: jax.Array
    assembled: jax.Array


def _is_concrete(x) -> bool:
    return isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer)


def _replicated(x):
    """Gather a (concrete) width-sharded array to every device; identity
    for replicated/single-device arrays and for tracers (inside a jit the
    caller's own sharding context governs)."""
    if not _is_concrete(x):
        return x
    sharding = getattr(x, "sharding", None)
    mesh = getattr(sharding, "mesh", None)
    if mesh is None or getattr(sharding, "is_fully_replicated", True):
        return x
    return jax.device_put(
        x, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))


def _reject_segmented(level_keys):
    """Refuse a segmented (§5.6 mass-split) plane on the
    gather-to-replicated path: its bottom row has interior +INF runs at
    segment boundaries, which violates the sorted-row invariant of the
    single-device binary descent — the answers would be silently wrong,
    not slower.  Concrete arrays only (one bottom-row host pull on the
    already-slow gather path); tracers pass — inside jit the caller
    owns layout discipline, and the sharded entry points (which handle
    segmented planes exactly) are the documented route there."""
    if not _is_concrete(level_keys):
        return
    import numpy as np
    live = np.asarray(level_keys[-1]) != PAD_KEY
    if live.any() and not live[:int(np.nonzero(live)[0][-1]) + 1].all():
        raise ValueError(
            "segmented (mass-split) plane on the gather-to-replicated "
            "search path: interior pad runs break the packed sorted-row "
            "invariant — search it with splay_search_sharded (routed or "
            "masked), or refresh it with split='lanes' to repack first")


def _is_plane(x) -> bool:
    """True for an index plane struct (``DeviceLevelArrays`` or
    ``LevelArrays``), False for a bare ``[L, W]`` key matrix."""
    return hasattr(x, "widths") and hasattr(x, "heights")


def row_widths(level_keys):
    """Live entries per row (rows are +INF padded)."""
    return jnp.sum(level_keys != PAD_KEY, axis=1).astype(jnp.int32)


def _check_query_block(query_block, nq):
    """The query block must be a positive int: it is the Pallas block
    length, and the wrappers pad the batch up to its multiple — a bad
    value surfaces here as a ValueError instead of a downstream
    BlockSpec shape error."""
    if not isinstance(query_block, int) or isinstance(query_block, bool):
        raise ValueError(
            f"query_block must be an int, got {type(query_block).__name__}")
    if query_block < 1:
        raise ValueError(
            f"query_block must be >= 1, got {query_block}")
    padded = nq + ((-nq) % query_block)
    if padded % query_block:            # unreachable by construction
        raise ValueError(
            f"query_block={query_block} does not divide the padded "
            f"batch {padded} (batch {nq})")


def _as_device_plane(plane):
    """Normalize an index plane struct to the full ``DeviceLevelArrays``
    pytree the sharded shard_maps expect: host ``LevelArrays`` (no slot
    map, no residency set) get jnp fields, an unknown (-1) slot map and
    *stale* residency — the per-batch assemble fallback stays their
    execution path."""
    if hasattr(plane, "local_ok"):
        return plane
    from repro.core import device_index as dix
    keys = jnp.asarray(plane.keys, jnp.int32)
    n_levels, width = keys.shape
    heights = jnp.asarray(plane.heights, jnp.int32)
    bot = keys[n_levels - 1]
    return dix.DeviceLevelArrays(
        keys=keys,
        widths=jnp.asarray(plane.widths, jnp.int32),
        heights=heights,
        slots=jnp.full((width,), -1, jnp.int32),
        local_bot=bot,
        local_heights=heights,
        local_live=(bot != PAD_KEY).astype(jnp.int32),
        local_ok=jnp.zeros((1,), jnp.int32))


def _fetch_schedule(widths, n_levels):
    """fetch[r] = r if row r is live else the next live row below it —
    empty rows alias their successor's block so the pipeline issues no
    DMA for them (same block index on consecutive steps)."""
    rows = jnp.arange(n_levels, dtype=jnp.int32)
    cand = jnp.where(widths > 0, rows, n_levels - 1)
    return jax.lax.associative_scan(jnp.minimum, cand, reverse=True)


def tiered_row_bytes(n_levels: int, width: int) -> int:
    """Bytes the tiered descent streams per query block: every level's
    key row, padded to whole 128-lane chunks (the sample rows add
    1/128 of that and are left out)."""
    return n_levels * (-(-width // CHUNK) * CHUNK) * 4


# ---------------------------------------------------------------------------
# tiered kernel: per-row streaming + two-stage compare-and-count probe
# ---------------------------------------------------------------------------

# Lanes per row chunk: a row of width W is viewed as [W/CHUNK, CHUNK]
# (one vreg lane-width per chunk), and its chunk-first keys form the
# [1, W/CHUNK] sample row of the coarse stage.
CHUNK = 128


def _kernel_tiered(fetch_ref, widths_ref, q_ref, samp_ref, row_ref,
                   found_ref, rank_ref, level_ref, *, n_levels: int,
                   n_chunks: int):
    """One (query block, level) grid step.  The predecessor index of
    each query in row r is a count, found without any per-query gather
    (Mosaic lowers no ``[QB]``-from-``[W]`` gather):

      1. coarse — compare the ``[QB, 1]`` queries against the row's
         ``[1, W/128]`` chunk-first samples and count: ``c`` samples are
         ``<= q``, so q's predecessor lies in chunk ``j = c - 1``;
      2. chunk fetch — a one-hot ``[QB, W/128]`` matrix times the row's
         four byte planes (``[W/128, 128]`` bf16 each, MXU, f32
         accumulate — exact: one 0..255 term per output) reassembles
         each query's 128-lane chunk;
      3. fine — compare-and-count inside the chunk gives the offset.

    Rows are sorted and +INF padded, so the count IS the binary
    search's predecessor index (clamped to the live width, as the
    window bound did), and q is in the row iff it is in its chunk."""
    del fetch_ref  # consumed by the index_maps only
    r = pl.program_id(1)
    q = q_ref[...]                                     # [QB, 1]
    qb = q.shape[0]
    w_r = widths_ref[r]

    @pl.when(r == 0)
    def _init():
        found_ref[...] = jnp.zeros((qb, 1), jnp.int32)
        level_ref[...] = jnp.full((qb, 1), n_levels, jnp.int32)
        rank_ref[...] = jnp.full((qb, 1), -1, jnp.int32)

    # empty rows alias the next live row's block (no DMA); skip them
    @pl.when(w_r > 0)
    def _probe():
        samp = samp_ref[0]                             # [1, S]
        c = jnp.sum((samp <= q).astype(jnp.int32), axis=1,
                    keepdims=True)                     # [QB, 1]
        j = jnp.maximum(c - 1, 0)
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (qb, n_chunks), 1)
                  == j).astype(jnp.float32).astype(jnp.bfloat16)
        row = row_ref[0]                               # [S, 128]
        chunk = jnp.zeros((qb, CHUNK), jnp.int32)
        for b in range(4):
            plane = ((row >> (8 * b)) & 0xFF).astype(jnp.float32)
            part = jnp.dot(onehot, plane.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
            chunk = chunk | (part.astype(jnp.int32) << (8 * b))
        le = jnp.sum((chunk <= q).astype(jnp.int32), axis=1,
                     keepdims=True)
        eq = jnp.sum((chunk == q).astype(jnp.int32), axis=1,
                     keepdims=True)
        live = c > 0
        p = jnp.minimum(jnp.where(live, j * CHUNK + le - 1, -1), w_r - 1)
        hit = live & (eq > 0) & (q != PAD_KEY)
        found = found_ref[...]
        level_ref[...] = jnp.where(hit & (found == 0), r, level_ref[...])
        found_ref[...] = found | hit.astype(jnp.int32)

        @pl.when(r == n_levels - 1)
        def _emit_rank():
            rank_ref[...] = p                          # bottom-row rank


def splay_search(level_keys, queries, query_block: int =
                 DEFAULT_QUERY_BLOCK, interpret: bool = True,
                 widths=None, sharded=None):
    """Tiered batched search.  level_keys: int32 [n_levels, width]
    (sorted rows, +INF padded, nested) — or an index plane struct
    (``DeviceLevelArrays``/``LevelArrays``), whose widths are used
    directly.  queries int32 [q] (any length — padded to the block
    multiple internally).  widths: the precomputed live count per row
    (counted on the fly when a bare matrix is passed without it).
    Returns (found [q] bool, rank [q] int32, level_found [q] int32).

    Dispatch (DESIGN.md §5.5): ``sharded=None`` routes a plane that is
    *concretely* width-sharded (``shard_index_plane`` layout, detected
    by ``sharding.plane_width_mesh``) to :func:`splay_search_sharded` —
    the descent then runs under ``shard_map`` and no replicated
    ``[L, W]`` rectangle is materialized.  ``sharded=True`` forces that
    path (falling back to replicated if no mesh can be resolved);
    ``sharded=False`` forces the legacy gather-to-replicated execution
    (the single-device kernel on the gathered plane) — the seam the
    parity tests pin.  Replicated execution constrains the query batch
    to the ``"batch"`` logical axis when a mesh is active."""
    nq = jnp.asarray(queries).shape[0]
    _check_query_block(query_block, nq)
    if _is_plane(level_keys):
        plane = level_keys
        if sharded is None:
            sharded = shd.plane_width_mesh(plane) is not None
        if sharded:
            return splay_search_sharded(plane, queries,
                                        query_block=query_block,
                                        interpret=interpret)
        level_keys = _replicated(jnp.asarray(plane.keys))
        _reject_segmented(level_keys)
        if widths is None:
            widths = _replicated(jnp.asarray(plane.widths))
    queries = shd.constrain(jnp.asarray(queries), "batch")
    return _splay_search_arrays(level_keys, queries,
                                query_block=query_block,
                                interpret=interpret, widths=widths)


@functools.partial(jax.jit,
                   static_argnames=("query_block", "interpret"))
def _splay_search_arrays(level_keys, queries, query_block: int =
                         DEFAULT_QUERY_BLOCK, interpret: bool = True,
                         widths=None):
    n_levels, width = level_keys.shape
    nq = queries.shape[0]
    if nq == 0:
        z = jnp.zeros((0,), jnp.int32)
        return jnp.zeros((0,), jnp.bool_), z, z
    pad = (-nq) % query_block
    if pad:
        queries = jnp.pad(queries, (0, pad), constant_values=PAD_KEY - 1)
    nq_p = nq + pad

    if widths is None:
        widths = row_widths(level_keys)
    fetch = _fetch_schedule(widths, n_levels)
    keys = jnp.asarray(level_keys, jnp.int32)
    wpad = (-width) % CHUNK
    if wpad:
        keys = jnp.pad(keys, ((0, 0), (0, wpad)), constant_values=PAD_KEY)
    n_chunks = (width + wpad) // CHUNK
    rows = keys.reshape(n_levels, n_chunks, CHUNK)
    samples = rows[:, :, 0].reshape(n_levels, 1, n_chunks)

    kernel = functools.partial(_kernel_tiered, n_levels=n_levels,
                               n_chunks=n_chunks)
    qspec = pl.BlockSpec((query_block, 1), lambda i, r, f, w: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nq_p // query_block, n_levels),
        in_specs=[
            qspec,
            pl.BlockSpec((1, 1, n_chunks), lambda i, r, f, w: (f[r], 0, 0)),
            pl.BlockSpec((1, n_chunks, CHUNK),
                         lambda i, r, f, w: (f[r], 0, 0)),
        ],
        out_specs=(qspec, qspec, qspec),
    )
    out_shape = jax.ShapeDtypeStruct((nq_p, 1), jnp.int32)
    found, rank, lvl = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(out_shape,) * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="splay_search_tiered",
    )(fetch, widths, queries.reshape(nq_p, 1), samples, rows)
    return found[:nq, 0] > 0, rank[:nq, 0], lvl[:nq, 0]


# ---------------------------------------------------------------------------
# width-sharded execution (DESIGN.md §5.5–§5.6): ownership routing +
# per-shard tiered descent on locally-assembled sub-planes.  Default is
# the routed all_to_all query exchange; the replicate-and-mask trace is
# kept as the spill target and as `routed=False`.
# ---------------------------------------------------------------------------

def _route_tables(bot, axis: str):
    """(boundary table [S], rank lifts [S]) from ONE two-scalar
    ``all_gather`` per shard block.

    Boundary table: shard s's entry is the smallest bottom-row key at
    or right of block s (suffix-min of block-first keys), with shard 0
    forced to the −∞ sentinel so every query has exactly one owner —
    the §5.4 range-boundary table.  The suffix-min matters for
    *segmented* planes (the §5.6 mass-weighted split can leave an
    interior block empty — its raw first key is the +INF pad, which
    would break the ownership searchsorted's monotonicity); on packed
    planes only trailing blocks can be empty and the suffix-min is the
    identity, so the table — and the routing — is bit-identical to the
    PR-4 one.

    Rank lifts: the exclusive prefix of per-block live-lane counts —
    the lift from a shard's local predecessor index to the *packed
    global* one.  On a packed plane every block left of an owned
    query's shard is full, so the lift equals the PR-4 ``ax * wl``
    column offset exactly; on a segmented plane the blocks hold the
    packed ranks ``[b_s, b_{s+1})``, so the lift is the left-segment
    length sum either way."""
    ax = jax.lax.axis_index(axis).astype(jnp.int32)
    lo = jnp.where(ax == 0, jnp.int32(NEG_INF_KEY), bot[0])
    cnt = jnp.sum((bot != PAD_KEY).astype(jnp.int32))
    both = jax.lax.all_gather(jnp.stack([lo, cnt]), axis)  # [S, 2]
    counts = both[:, 1]
    return shd.suffix_min_bounds(both[:, 0]), jnp.cumsum(counts) - counts


def _owner_of(bounds, queries):
    """Owner shard of each query: the unique s with
    ``bounds[s] <= clip(q) < bounds[s+1]``.  Queries clamp into
    (−∞ sentinel, +INF pad sentinel) for routing only: an all-pad
    block's boundary key IS the pad sentinel, so a q == PAD_KEY query
    must route to the last live range (whose window-bounded descent
    answers it like the replicated kernel, which never probes pad
    lanes), and a q below shard 0's −∞ sentinel must still route to
    shard 0 (whose descent answers rank −1 / not-found exactly like
    the replicated kernel)."""
    return (jnp.searchsorted(bounds,
                             jnp.clip(queries, NEG_INF_KEY, PAD_KEY - 1),
                             side="right")
            .astype(jnp.int32) - 1)                    # in [0, S-1]


def _local_subplane(plane, *, n_levels: int):
    """The shard's local [L, W/S] sub-plane (runs under ``shard_map``;
    ``plane`` leaves are this shard's blocks).  The one shared entry to
    local re-layering — both sharded search bodies go through here.

    Resident fast path (DESIGN.md §5.8): when the residency bit
    ``local_ok`` is set (only the mass-split refresh sets it), the
    plane's keys blocks already ARE the per-shard local sub-plane —
    the only global field is ``widths``, re-derived from the resident
    provenance by one mask-sum.  Stale residency
    (any replicated build/refresh, lanes-split layout, host plane)
    re-layers the provenance blocks through ``_assemble_device`` per
    batch — the pre-§5.8 behavior, kept as the fallback.

    Returns ``(local_plane, assembled)`` with ``assembled`` an int32
    0/1 flag — the counted probe behind ``RouteStats.assembled``."""
    from repro.core import device_index as dix
    wl = plane.local_bot.shape[0]

    def resident(p_):
        row_min_h = (n_levels - 1
                     - jnp.arange(n_levels, dtype=jnp.int32))
        live = (p_.local_live > 0)[None, :]
        lw = jnp.sum(live & (p_.local_heights[None, :]
                             >= row_min_h[:, None]),
                     axis=1).astype(jnp.int32)
        return p_._replace(widths=lw), jnp.int32(0)

    def assemble(p_):
        return (dix._assemble_device(
                    p_.local_bot, p_.local_heights,
                    jnp.full((wl,), -1, jnp.int32), n_levels),
                jnp.int32(1))

    return jax.lax.cond(plane.local_ok[0] > 0, resident, assemble, plane)


def _masked_descent(local, bounds, lift, queries, *, axis: str,
                    query_block: int, interpret: bool):
    """The replicate-and-mask trace (the PR-4 §5.5 execution, now the
    spill target): every shard descends the FULL (replicated) query
    batch on its local sub-plane, masks the lanes it does not own, and
    ONE stacked ``[3, q]`` psum composes the outputs.  Aggregate
    compute is S× redundant — which is exactly why §5.6 routes instead
    — but any query answers correctly here, capacity-free."""
    owner = _owner_of(bounds, queries)
    mine = owner == jax.lax.axis_index(axis).astype(jnp.int32)
    f, r, lv = _splay_search_arrays(local.keys, queries,
                                    query_block=query_block,
                                    interpret=interpret, widths=local.widths)
    rank_g = jnp.where(r >= 0, r + lift, -1)
    stacked = jnp.where(mine[None, :],
                        jnp.stack([f.astype(jnp.int32), rank_g, lv]),
                        0)
    f_o, r_o, l_o = jax.lax.psum(stacked, axis)
    return f_o > 0, r_o, l_o


def _search_shard_body(plane, queries, *, axis: str, n_levels: int,
                       query_block: int, interpret: bool):
    """Per-shard body of the ``routed=False`` path (runs under
    ``shard_map``; ``plane`` leaves are this shard's blocks, queries
    are replicated).  Three stages:

      1. *routing* — the §5.4 range-boundary table
         (:func:`_route_tables`) and one sharded ``searchsorted``
         assign each query the shard whose contiguous key range
         contains it.  Ownership by bottom-row key range means the
         owner's columns contain the query's bottom-row rank window —
         including windows that straddle a shard boundary on the
         *global* plane: the halo-established range bound closes them
         against the local −∞/+∞ sentinels instead (the true
         predecessor left of the boundary, when there is one, is by
         construction not the bottom-row answer of an owned query).
      2. *local descent* — the shard's [L, W/S] sub-plane comes from
         :func:`_local_subplane`: resident (one mask-sum) on a
         mass-split plane, else re-layered per batch (same
         mask/prefix-sum pass as the refresh; rows of the sub-plane are
         the shard's key range restricted to each level, so row
         membership — and hence ``level_found`` — matches the global
         plane exactly); the tiered kernel runs on it.  Resident
         footprint O(L·W/S).
      3. *composition* — local ranks lift to packed-global by the
         shard's live-lane prefix (:func:`_route_tables`), and ONE
         stacked ``[3, q]`` ``psum`` (masked to each query's owner)
         emits found/rank/level.

    Wire per batch: two scalar all_gathers + one [3, q] psum (plus the
    scalar ``assembled`` psum) — independent of W (the refresh's
    collectives are O(W); the search adds only O(q))."""
    bot = plane.keys[n_levels - 1]
    bounds, lifts = _route_tables(bot, axis)
    lift = lifts[jax.lax.axis_index(axis).astype(jnp.int32)]
    local, assembled = _local_subplane(plane, n_levels=n_levels)
    f, r, lv = _masked_descent(local, bounds, lift, queries, axis=axis,
                               query_block=query_block,
                               interpret=interpret)
    return f, r, lv, jax.lax.psum(assembled, axis)


def _routed_shard_body(plane, q_loc, *, axis: str, n_shards: int,
                       n_levels: int, capacity: int, query_block: int,
                       interpret: bool, n_live: int):
    """Per-shard body of the routed query exchange (DESIGN.md §5.6;
    runs under ``shard_map``; ``plane`` leaves are this shard's blocks,
    ``q_loc`` is its ``[q/S]`` slice of the batch-sharded queries).

      1. *bucket* — route the local slice by the boundary table, then
         compact each destination's queries into one lane-contiguous
         bucket of the static ``[S, capacity]`` send block (gather-only:
         per-destination prefix sums + one inverse-prefix take).  A
         bucket position past ``capacity`` marks the query spilled at
         the source (only possible when ``capacity < q/S``).
      2. *exchange* — ONE ``all_to_all`` of the send block (the [S, S]
         per-pair counts ride a scalar ``all_gather``); shard s
         receives row j = shard j's bucket for s.  Received buckets
         compact source-major into the kernel batch ``[capacity]``;
         received queries whose compacted rank lands past ``capacity``
         spill at the destination.
      3. *descend* — the unmodified tiered kernel over the O(q/S)
         compacted block on the locally re-layered [L, W/S] sub-plane
         (same sub-plane as the masked trace — answers are identical).
      4. *return* — answers (plus a validity flag) scatter-free back
         into the ``[S, capacity]`` recv layout by the same positional
         arithmetic, the inverse ``all_to_all`` ships them home, and
         each source unpermutes by its (owner, bucket position) pairs.
      5. *spill* — queries without a valid routed answer (source- or
         destination-side capacity overflow) are answered by the
         replicate-and-mask trace (:func:`_masked_descent` over the
         all_gathered batch), entered only when the psum'd spill count
         is nonzero: counted, never dropped, bit-identical either way.

    Wire per batch: two all_to_alls of [S·capacity] + O(S²) scalars —
    O(q·slack), W-independent; the full-batch all_gather is paid only
    on spill epochs.  Per-shard kernel compute drops from O(q·L·log
    (W/S)) to O((q/S)·slack·L·log(W/S)) — the §5.6 point."""
    S = n_shards
    qs = q_loc.shape[0]
    ax = jax.lax.axis_index(axis).astype(jnp.int32)
    fill = jnp.int32(PAD_KEY - 1)                      # inert query value

    local, assembled = _local_subplane(plane, n_levels=n_levels)

    # steps 1, 2 and 4 are the exchange, scoped ``splay.route``: the
    # routing table, owner bucketing, the count all_gather and the query
    # and answer all_to_alls
    with jax.named_scope("splay.route"):
        bounds, lifts = _route_tables(plane.keys[n_levels - 1], axis)
        lift = lifts[ax]

        # ---- 1. owner-bucket the local slice.  Batch-padding fill lanes
        # (global index >= n_live, appended by the wrapper when q % S != 0)
        # get owner -1: never bucketed, never exchanged, never counted in
        # the pair-count matrix — so occupancy and spill reflect real
        # queries only, and pads can't push a shard over capacity.
        gidx = ax * qs + jnp.arange(qs, dtype=jnp.int32)
        owner = jnp.where(gidx < n_live, _owner_of(bounds, q_loc),
                          jnp.int32(-1))               # [qs]
        onehot = (owner[:, None]
                  == jnp.arange(S, dtype=jnp.int32)[None, :])
        cs = jnp.cumsum(onehot.astype(jnp.int32), axis=0)   # [qs, S]
        cnt = cs[qs - 1]                               # [S] per-dest count
        pos = jnp.take_along_axis(cs, owner[:, None].astype(jnp.int32),
                                  axis=1)[:, 0] - 1    # bucket position
        lane = jnp.arange(capacity, dtype=jnp.int32)

        def bucket(cs_d):
            # inverse prefix sum: lane c of dest d's bucket holds the c-th
            # owned query (same gather formulation as _compact_take)
            take = jnp.minimum(
                jnp.searchsorted(cs_d, lane + 1).astype(jnp.int32), qs - 1)
            return jnp.take(q_loc, take)

        send = jnp.where(
            lane[None, :] < jnp.minimum(cnt, capacity)[:, None],
            jax.vmap(bucket)(jnp.transpose(cs)), fill)

        # ---- 2. exchange + destination-side compaction -------------------
        recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                                  tiled=True)          # [S, cap] by src
        pair_cnt = jax.lax.all_gather(cnt, axis)       # [S_src, S_dst]
        rcv_cnt = jnp.minimum(pair_cnt[:, ax], capacity)   # live per row
        cum_r = jnp.cumsum(rcv_cnt)
        occ = cum_r[S - 1]                             # my occupancy
        src_of = jnp.searchsorted(cum_r, lane,
                                  side="right").astype(jnp.int32)
        src_c = jnp.minimum(src_of, S - 1)
        lane_of = lane - (jnp.take(cum_r, src_c) - jnp.take(rcv_cnt, src_c))
        kq = jnp.where(lane < jnp.minimum(occ, capacity),
                       recv[src_c, jnp.clip(lane_of, 0, capacity - 1)],
                       fill)                           # [cap] kernel batch

    # ---- 3. the tiered descent over the compacted O(q/S) block -----------
    f, r, lv = _splay_search_arrays(local.keys, kq,
                                    query_block=query_block,
                                    interpret=interpret, widths=local.widths)
    rank_g = jnp.where(r >= 0, r + lift, -1)

    with jax.named_scope("splay.route"):
        # ---- 4. positional un-exchange -----------------------------------
        off_r = cum_r - rcv_cnt                        # [S] excl offsets
        gpos = off_r[:, None] + lane[None, :]          # [S, cap]
        live_r = lane[None, :] < rcv_cnt[:, None]
        valid = live_r & (gpos < capacity)
        gp = jnp.clip(gpos, 0, capacity - 1)
        back = jnp.stack([jnp.take(f.astype(jnp.int32), gp),
                          jnp.take(rank_g, gp), jnp.take(lv, gp),
                          valid.astype(jnp.int32)])    # [4, S, cap]
        home = jax.lax.all_to_all(back, axis, split_axis=1, concat_axis=1,
                                  tiled=True)          # [4, S, cap] by dst
        idx = (jnp.clip(owner, 0, S - 1) * capacity
               + jnp.minimum(jnp.maximum(pos, 0), capacity - 1))
        flat = home.reshape(4, S * capacity)
        # pad lanes (owner -1) read a garbage-but-in-bounds slot; their ok
        # value is irrelevant (the wrapper slices them off) and they are
        # excluded from the pair-count-derived spill/occupancy below
        ok = (pos < capacity) & (jnp.take(flat[3], idx) > 0)
        f_rt = jnp.take(flat[0], idx) > 0
        r_rt = jnp.take(flat[1], idx)
        l_rt = jnp.take(flat[2], idx)

    # ---- 5. spill: replicate-and-mask trace, entered only when
    # needed.  The spill count and occupancy both derive from the
    # replicated [S, S] pair-count matrix — no further collective:
    # source-side truncation is pair_cnt past capacity, destination-
    # side overflow is the received-live total past capacity, and the
    # two partition ~ok exactly.
    occupancy = jnp.sum(pair_cnt, axis=0)              # [S] per dest
    clamped = jnp.minimum(pair_cnt, capacity)
    n_spill = (jnp.sum(pair_cnt - clamped)
               + jnp.sum(jnp.maximum(
                   jnp.sum(clamped, axis=0) - capacity, 0))
               ).astype(jnp.int32)

    def spill_path(_):
        q_all = jax.lax.all_gather(q_loc, axis, tiled=True)  # [S*qs]
        fa, ra, la = _masked_descent(
            local, bounds, lift, q_all, axis=axis,
            query_block=query_block, interpret=interpret)
        sl = lambda x: jax.lax.dynamic_slice(x, (ax * qs,), (qs,))
        return sl(fa), sl(ra), sl(la)

    def no_spill(_):
        return (jnp.zeros((qs,), jnp.bool_), jnp.zeros((qs,), jnp.int32),
                jnp.zeros((qs,), jnp.int32))

    f_sp, r_sp, l_sp = jax.lax.cond(n_spill > 0, spill_path, no_spill,
                                    operand=None)
    return (jnp.where(ok, f_rt, f_sp), jnp.where(ok, r_rt, r_sp),
            jnp.where(ok, l_rt, l_sp), n_spill, occupancy,
            jax.lax.psum(assembled, axis))


@functools.lru_cache(maxsize=None)
def _sharded_search_fn(mesh, axis: str, n_levels: int, query_block: int,
                       interpret: bool):
    """Build (and cache) the jitted shard_map of the replicate-and-mask
    path for one (mesh, axis, n_levels, query_block) cell —
    planes are shape-stable, so serving reuses one entry per mesh.  The
    plane enters as one pytree laid out by ``index_plane_specs`` (its
    residency fields ride along for :func:`_local_subplane`)."""
    from repro.core.device_index import DeviceLevelArrays
    specs = shd.index_plane_specs(DeviceLevelArrays, axis)
    body = functools.partial(
        _search_shard_body, axis=axis, n_levels=n_levels,
        query_block=query_block, interpret=interpret)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(specs, P()),
                       out_specs=(P(), P(), P(), P()), check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _routed_search_fn(mesh, axis: str, n_levels: int, query_block: int,
                      interpret: bool, capacity: int, n_live: int):
    """Build (and cache) the jitted shard_map of the routed exchange for
    one (mesh, axis, n_levels, query_block, capacity, n_live) cell.
    The plane enters as one ``index_plane_specs`` pytree; queries enter
    batch-sharded (``P(axis)``) and the answer triple leaves
    batch-sharded; the spill count, occupancy vector and assembled
    count are replicated."""
    from repro.core.device_index import DeviceLevelArrays
    specs = shd.index_plane_specs(DeviceLevelArrays, axis)
    body = functools.partial(
        _routed_shard_body, axis=axis, n_shards=mesh.shape[axis],
        n_levels=n_levels, capacity=capacity, query_block=query_block,
        interpret=interpret, n_live=n_live)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(specs, P(axis)),
        out_specs=(P(axis), P(axis), P(axis), P(), P(), P()),
        check_vma=False)
    return jax.jit(fn)


def route_capacity(nq: int, n_shards: int,
                   slack: float = DEFAULT_ROUTE_SLACK) -> int:
    """The default static per-shard receive capacity of the routed
    exchange: ``ceil(q/S) · slack``, clamped into ``[1, q]``
    (DESIGN.md §5.6).  ``slack`` absorbs routing imbalance — under the
    mass-weighted split (§5.6) occupancy concentrates near q/S, so the
    default 1.5 leaves spill a rare event rather than a safety
    requirement (spilled queries still answer exactly, just slower).
    The upper clamp is the batch size itself: a shard can never receive
    more than ``q`` live queries (``occupancy.sum() == q``), so any
    capacity past it is wasted wire — ``slack >= S`` therefore makes
    spill structurally impossible, which is the routing controller's
    escape hatch (DESIGN.md §5.7).

    Raises ``ValueError`` on non-positive ``nq``/``n_shards`` and on
    ``slack < 1.0`` (a sub-1 slack silently guarantees spill on a
    perfectly balanced batch — always a caller bug)."""
    if nq <= 0:
        raise ValueError(f"route_capacity: nq must be positive, got {nq}")
    if n_shards <= 0:
        raise ValueError(
            f"route_capacity: n_shards must be positive, got {n_shards}")
    if slack < 1.0:
        raise ValueError(
            f"route_capacity: slack must be >= 1.0, got {slack} "
            "(sub-1 slack guarantees spill on a balanced batch)")
    qs = -(-nq // n_shards)
    return max(1, min(nq, int(-(-qs * slack // 1))))


def splay_search_sharded(level_keys, queries, query_block: int =
                         DEFAULT_QUERY_BLOCK, interpret: bool = True,
                         mesh=None, axis: str = "model",
                         routed: bool = True, capacity: int = None,
                         slack: float = DEFAULT_ROUTE_SLACK,
                         return_stats: bool = False):
    """Width-sharded tiered search (DESIGN.md §5.5–§5.6): the
    tiered descent under ``shard_map`` over the ``splay_width``
    axis.  Each shard owns the contiguous key range of its plane
    segment (the same ownership as the §5.4 sharded refresh); by
    default (``routed=True``) the query batch is *exchanged*: each
    shard owner-buckets its batch slice, ONE ``all_to_all`` ships the
    static-capacity buckets, the owner runs the tiered kernel over only
    its O(q/S) received block on its locally re-layered sub-plane, and
    the inverse exchange + positional unpermute return the answers —
    per-shard compute O((q/S)·L·log(W/S)).  ``routed=False`` keeps the
    replicate-and-mask trace (every shard descends the full batch and
    masks; per-shard compute O(q·L·log(W/S))), which is also where
    queries *spill* when a shard's received block exceeds ``capacity``
    — counted, never dropped, bit-identical either way.  No replicated
    ``[L, W]`` rectangle is ever materialized on either path.

    ``capacity`` (static) is the per-shard receive block size; default
    :func:`route_capacity` = ``ceil(q/S) · slack``.  ``slack`` is the
    imbalance headroom (only read when ``capacity`` is None).
    ``return_stats=True`` appends a :class:`RouteStats` (spill count,
    per-shard occupancy, assembled-shard count) to the returned triple.

    Local sub-planes come from :func:`_local_subplane`: resident on a
    mass-split plane (``local_ok`` set — no per-batch
    ``_assemble_device``), re-layered per batch otherwise; the
    ``RouteStats.assembled`` counter reports which path ran.

    ``level_keys`` must be an index plane struct
    (``DeviceLevelArrays``/``LevelArrays``).  Mesh resolution: the
    ``mesh`` argument, else the plane's own concrete layout
    (``sharding.plane_width_mesh``), else the active
    ``sharding.use_mesh``.  Outputs are the global answer triple (the
    routed path leaves them batch-sharded over the mesh; the masked
    path replicates them — same values either way).

    Equivalence: bit-identical to the replicated tiered search on every
    plane and query batch — membership, bottom-row predecessor rank,
    and first-row-found are functions of (plane, query) alone, and the
    per-shard sub-plane preserves row membership exactly (asserted on
    1/2/4-way host meshes in ``tests/test_sharded_search.py``,
    boundary-straddling windows, forced spill, and mass-split planes
    included).  On a segmented
    (§5.6 mass-split) plane this sharded entry point is the ONLY
    correct search — the gather-to-replicated path assumes a packed
    bottom row.

    Fallback modes (never raises): no resolvable mesh, ``axis`` absent
    from the mesh, or ``width % S != 0`` all route to the replicated
    gather-to-replicated path with the same return convention (stats:
    zero spill, one pseudo-shard owning the whole batch)."""
    plane = level_keys
    if not _is_plane(plane):
        raise TypeError("splay_search_sharded takes an index plane "
                        "struct (DeviceLevelArrays/LevelArrays), got "
                        f"{type(level_keys).__name__}")
    if capacity is not None and int(capacity) < 1:
        raise ValueError(
            f"splay_search_sharded: capacity must be >= 1, got {capacity}")
    if capacity is None and slack < 1.0:
        raise ValueError(
            f"splay_search_sharded: slack must be >= 1.0, got {slack}")
    nq = jnp.asarray(queries).shape[0]
    _check_query_block(query_block, nq)
    plane = _as_device_plane(plane)
    if mesh is None:
        mesh = shd.plane_width_mesh(plane, axis) or shd.active_mesh()
    n_levels, width = plane.keys.shape
    if (mesh is None or axis not in mesh.shape
            or width % mesh.shape[axis]):
        out = splay_search(plane, queries, query_block=query_block,
                           interpret=interpret, sharded=False)
        if return_stats:
            return out + (RouteStats(
                jnp.zeros((), jnp.int32),
                jnp.full((1,), nq, jnp.int32),
                jnp.zeros((), jnp.int32)),)
        return out
    S = mesh.shape[axis]
    queries = jnp.asarray(queries)
    if nq == 0:
        z = jnp.zeros((0,), jnp.int32)
        out = (jnp.zeros((0,), jnp.bool_), z, z)
        if return_stats:
            return out + (RouteStats(jnp.zeros((), jnp.int32),
                                     jnp.zeros((S,), jnp.int32),
                                     jnp.zeros((), jnp.int32)),)
        return out
    if not routed:
        fn = _sharded_search_fn(mesh, axis, n_levels, query_block,
                                interpret)
        f, r, lv, assembled = fn(plane, queries)
        out = (f, r, lv)
        if return_stats:
            return out + (RouteStats(
                jnp.zeros((), jnp.int32),
                jnp.full((S,), nq, jnp.int32), assembled),)
        return out
    qs = -(-nq // S)
    pad = qs * S - nq
    if capacity is None:
        capacity = route_capacity(nq, S, slack)
    else:
        # a shard can never receive more than the whole batch: clamp
        # explicit capacities at q too (wire-size hygiene, same answers)
        capacity = min(int(capacity), nq)
    if pad:
        queries = jnp.pad(queries, (0, pad),
                          constant_values=PAD_KEY - 1)
    fn = _routed_search_fn(mesh, axis, n_levels, query_block, interpret,
                           int(capacity), int(nq))
    f, r, lv, spill, occ, assembled = fn(plane, queries)
    out = (f[:nq], r[:nq], lv[:nq])
    if return_stats:
        return out + (RouteStats(spill, occ, assembled),)
    return out


# ---------------------------------------------------------------------------
# ordered-operation suite (DESIGN.md §5.10): predecessor / successor /
# rank / select / range-count / range-scan / top-k
# ---------------------------------------------------------------------------

def _require_plane(level_keys, op: str):
    """Ordered ops are defined on *packed global ranks*, a plane-level
    concept — they take an index plane struct, never a bare matrix."""
    if not _is_plane(level_keys):
        raise TypeError(
            f"{op} takes an index plane struct "
            "(DeviceLevelArrays/LevelArrays), got "
            f"{type(level_keys).__name__}")
    return level_keys


def _ordered_dispatch(plane, sharded):
    """The same auto-dispatch rule as :func:`splay_search`: ``None``
    means sharded exactly when the plane is concretely width-sharded."""
    if sharded is None:
        sharded = shd.plane_width_mesh(plane) is not None
    return bool(sharded)


def _usable_width_mesh(plane, axis: str = "model", mesh=None):
    """The mesh the sharded ordered paths run under, or None when the
    replicated fallback applies — mirrors the resolution + fallback
    conditions of :func:`splay_search_sharded` exactly (explicit
    ``mesh`` argument, else plane layout, else active mesh; axis
    present; width divisible).  The explicit argument is how in-jit
    callers (``splaylist._run_epoch``, where the plane is a tracer)
    reach the sharded path."""
    mesh = mesh or shd.plane_width_mesh(plane, axis) or shd.active_mesh()
    width = jnp.asarray(plane.keys).shape[1]
    if mesh is None or axis not in mesh.shape or width % mesh.shape[axis]:
        return None
    return mesh


def _select_shard_body(plane, ranks, *, axis: str, n_levels: int):
    """Per-shard body of the sharded :func:`splay_select` (runs under
    ``shard_map``; ``plane`` leaves are this shard's blocks, ``ranks``
    replicated).  Each shard owns the packed-global rank interval
    ``[lift_s, lift_s + cnt_s)`` — the §5.6 live-lane count prefix from
    :func:`_route_tables` — because every shard block (packed or
    mass-segmented) holds its live keys contiguously from lane 0.  The
    shard gathers its owned ranks from its local bottom row and ONE
    stacked ``[2, q]`` psum stitches values + ownership; unowned ranks
    (negative, or past the live count) come back ``PAD_KEY``."""
    bot = plane.keys[n_levels - 1]
    wl = bot.shape[0]
    ax = jax.lax.axis_index(axis).astype(jnp.int32)
    _, lifts = _route_tables(bot, axis)
    lift = lifts[ax]
    cnt = jnp.sum((bot != PAD_KEY).astype(jnp.int32))
    mine = (ranks >= lift) & (ranks < lift + cnt)
    loc = jnp.clip(ranks - lift, 0, wl - 1)
    vals = jnp.where(mine, bot[loc], 0)
    stacked = jnp.stack([vals, mine.astype(jnp.int32)])
    v_o, owned = jax.lax.psum(stacked, axis)
    return jnp.where(owned > 0, v_o, jnp.int32(PAD_KEY))


def _topk_shard_body(plane, hits, *, axis: str, n_levels: int, k: int):
    """Per-shard body of the sharded :func:`splay_top_k`: each shard
    ranks its own live lanes by hit mass and contributes its local
    top-``min(k, W/S)`` candidates (any global top-k key is in its
    owner's local top-k); one ``[S, 3, k_local]`` all_gather + a
    replicated lexsort on (hits desc, packed-global rank asc) merges
    them — the same deterministic tie order as ``lax.top_k`` over the
    packed replicated row, so sharded and replicated answers are
    bit-identical.  Missing lanes (k past the live count) carry hit −1
    into the merge and are masked by the wrapper."""
    bot = plane.keys[n_levels - 1]
    wl = bot.shape[0]
    ax = jax.lax.axis_index(axis).astype(jnp.int32)
    _, lifts = _route_tables(bot, axis)
    cap = hits.shape[0]
    live = (bot != PAD_KEY) & (plane.slots >= 0)
    h = jnp.where(live, hits[jnp.clip(plane.slots, 0, cap - 1)],
                  jnp.int32(-1))
    kk = min(k, wl)
    hv, idx = jax.lax.top_k(h, kk)
    valid = hv >= 0
    grank = jnp.where(valid, idx + lifts[ax], jnp.int32(2 ** 31 - 1))
    kcand = jnp.where(valid, bot[idx], jnp.int32(PAD_KEY))
    cand = jax.lax.all_gather(jnp.stack([hv, kcand, grank]),
                              axis)                       # [S, 3, kk]
    hv_a = cand[:, 0].reshape(-1)
    key_a = cand[:, 1].reshape(-1)
    gr_a = cand[:, 2].reshape(-1)
    order = jnp.lexsort((gr_a, -hv_a))[:k]
    return key_a[order], hv_a[order], gr_a[order]


@functools.lru_cache(maxsize=None)
def _select_fn(mesh, axis: str, n_levels: int):
    """Build (and cache) the jitted shard_map of the sharded select for
    one (mesh, axis, n_levels) cell."""
    from repro.core.device_index import DeviceLevelArrays
    specs = shd.index_plane_specs(DeviceLevelArrays, axis)
    body = functools.partial(_select_shard_body, axis=axis,
                             n_levels=n_levels)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(specs, P()),
                       out_specs=P(), check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _topk_fn(mesh, axis: str, n_levels: int, k: int):
    """Build (and cache) the jitted shard_map of the sharded top-k for
    one (mesh, axis, n_levels, k) cell."""
    from repro.core.device_index import DeviceLevelArrays
    specs = shd.index_plane_specs(DeviceLevelArrays, axis)
    body = functools.partial(_topk_shard_body, axis=axis,
                             n_levels=n_levels, k=k)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(specs, P()),
                       out_specs=(P(), P(), P()), check_vma=False)
    return jax.jit(fn)


def splay_select(level_keys, ranks, sharded=None, axis: str = "model",
                 mesh=None):
    """``select(r)``: the live key at packed-global rank ``r`` (0-based
    over the sorted live bottom row); ``PAD_KEY`` for any rank outside
    ``[0, live_count)`` — out-of-range is answered, never raised, so
    callers compose it under jit.  ``ranks`` int32 [q] → keys int32 [q].

    Sharded execution gathers each rank from the one shard whose
    live-lane interval contains it and stitches with one psum
    (:func:`_select_shard_body`) — segmented (mass-split) planes are
    exact here because every shard block is locally packed.  The
    replicated path is a plain bottom-row gather and (like every
    replicated entry point) refuses a segmented plane."""
    plane = _require_plane(level_keys, "splay_select")
    ranks = jnp.asarray(ranks, jnp.int32)
    if ranks.shape[0] == 0:
        return jnp.zeros((0,), jnp.int32)
    if mesh is not None or _ordered_dispatch(plane, sharded):
        mesh = _usable_width_mesh(plane, axis, mesh)
        if mesh is not None:
            dplane = _as_device_plane(plane)
            n_levels = dplane.keys.shape[0]
            return _select_fn(mesh, axis, n_levels)(dplane, ranks)
    keys = _replicated(jnp.asarray(plane.keys, jnp.int32))
    _reject_segmented(keys)
    n_levels, width = keys.shape
    bot = keys[n_levels - 1]
    total = _replicated(jnp.asarray(plane.widths,
                                    jnp.int32))[n_levels - 1]
    ok = (ranks >= 0) & (ranks < total)
    return jnp.where(ok, bot[jnp.clip(ranks, 0, width - 1)],
                     jnp.int32(PAD_KEY))


def splay_rank(level_keys, queries, query_block: int =
               DEFAULT_QUERY_BLOCK, interpret: bool = True,
               sharded=None):
    """``rank(q)``: the number of live keys ``<= q`` — exactly the
    descent's bottom-row predecessor index plus one, so this is ONE
    :func:`splay_search` call (replicated or routed sharded by the same
    dispatch) and nothing else.  ``queries`` int32 [q] → int32 [q] in
    ``[0, live_count]``.  The key domain is
    ``(NEG_INF_KEY, PAD_KEY - 1]``; queries may be any int32 (extremes
    clamp against the sentinels without changing the count)."""
    plane = _require_plane(level_keys, "splay_rank")
    queries = jnp.asarray(queries, jnp.int32)
    q_eff = jnp.minimum(queries, jnp.int32(PAD_KEY - 1))
    _, r, _ = splay_search(plane, q_eff, query_block=query_block,
                           interpret=interpret, sharded=sharded)
    return r + 1


def splay_predecessor(level_keys, queries, query_block: int =
                      DEFAULT_QUERY_BLOCK, interpret: bool = True,
                      sharded=None):
    """``predecessor(q)``: the largest live key ``<= q`` and its
    packed-global rank — the descent's final window endpoint, lifted to
    the global rank exactly as membership ranks are.  Returns
    ``(keys [q] int32, ranks [q] int32)``; no predecessor (q below the
    smallest live key) answers ``(NEG_INF_KEY, -1)``.  One search plus
    one :func:`splay_select` gather."""
    plane = _require_plane(level_keys, "splay_predecessor")
    queries = jnp.asarray(queries, jnp.int32)
    q_eff = jnp.minimum(queries, jnp.int32(PAD_KEY - 1))
    _, r, _ = splay_search(plane, q_eff, query_block=query_block,
                           interpret=interpret, sharded=sharded)
    keys = splay_select(plane, r, sharded=sharded)
    return jnp.where(r >= 0, keys, jnp.int32(NEG_INF_KEY)), r


def splay_successor(level_keys, queries, query_block: int =
                    DEFAULT_QUERY_BLOCK, interpret: bool = True,
                    sharded=None):
    """``successor(q)``: the smallest live key ``>= q`` and its
    packed-global rank.  A membership hit answers ``(q, rank)``
    directly; a miss answers the key one past the predecessor rank.  No
    successor (q above the largest live key) answers
    ``(PAD_KEY, live_count)`` — the select past the live count already
    yields ``PAD_KEY``, so the rank is the one extra signal."""
    plane = _require_plane(level_keys, "splay_successor")
    queries = jnp.asarray(queries, jnp.int32)
    none = queries >= jnp.int32(PAD_KEY)          # no key >= PAD_KEY
    q_eff = jnp.minimum(queries, jnp.int32(PAD_KEY - 1))
    f, r, _ = splay_search(plane, q_eff, query_block=query_block,
                           interpret=interpret, sharded=sharded)
    r_succ = jnp.where(f & ~none, r, r + 1)
    keys = splay_select(plane, r_succ, sharded=sharded)
    keys = jnp.where(f & ~none, q_eff, keys)
    return jnp.where(none, jnp.int32(PAD_KEY), keys), r_succ


def _range_ranks(plane, lo, hi, *, query_block, interpret, sharded):
    """(start rank, in-range count) of the inclusive key range
    ``[lo, hi]`` — ONE batched descent over the concatenated endpoint
    batch (so the routed path pays one exchange for both ends), then
    pure rank arithmetic: ``count = rank(hi) - |{k < lo}|``, clamped at
    0 for empty/inverted ranges."""
    n = lo.shape[0]
    lo_eff = jnp.minimum(lo, jnp.int32(PAD_KEY - 1))
    hi_eff = jnp.minimum(hi, jnp.int32(PAD_KEY - 1))
    f, r, _ = splay_search(plane, jnp.concatenate([lo_eff, hi_eff]),
                           query_block=query_block, interpret=interpret,
                           sharded=sharded)
    f_lo, r_lo = f[:n], r[:n]
    r_hi = r[n:]
    start = jnp.where(f_lo, r_lo, r_lo + 1)       # |{live k < lo}|
    count = jnp.maximum(r_hi + 1 - start, 0)
    count = jnp.where(lo >= jnp.int32(PAD_KEY), 0, count)
    return start, count


def splay_range_count(level_keys, lo, hi, query_block: int =
                      DEFAULT_QUERY_BLOCK, interpret: bool = True,
                      sharded=None):
    """Number of live keys in the inclusive range ``[lo, hi]`` —
    int32 [q] (0 for empty or inverted ranges).  A rank pair from one
    batched descent; on the sharded plane a range spanning adjacent
    owners needs no extra machinery: each endpoint routes to its own
    owner and the packed-global ranks subtract shard-free."""
    plane = _require_plane(level_keys, "splay_range_count")
    lo = jnp.asarray(lo, jnp.int32)
    hi = jnp.asarray(hi, jnp.int32)
    if lo.shape != hi.shape:
        raise ValueError(
            f"splay_range_count: lo/hi shapes differ: {lo.shape} vs "
            f"{hi.shape}")
    if lo.shape[0] == 0:
        return jnp.zeros((0,), jnp.int32)
    _, count = _range_ranks(plane, lo, hi, query_block=query_block,
                            interpret=interpret, sharded=sharded)
    return count


def splay_range_scan(level_keys, lo, hi, max_range: int,
                     query_block: int = DEFAULT_QUERY_BLOCK,
                     interpret: bool = True, sharded=None):
    """The live keys in the inclusive range ``[lo, hi]``, in key order:
    a rank pair plus a contiguous bottom-row gather (the gather-first
    layout's cheap range scan).  Returns
    ``(keys [q, max_range] int32, count [q] int32, truncated [q]
    int32)``: ``keys`` holds the first ``min(count, max_range)`` range
    members and ``PAD_KEY`` beyond them; ``count`` is the FULL in-range
    population regardless of capacity; ``truncated = max(count -
    max_range, 0)`` counts what the static capacity cut — truncation is
    counted, never silent.  ``max_range`` is a static capacity (it
    shapes the result and the sharded gather's psum wire), so pick it
    per call site.

    Sharded execution: the endpoint ranks come from the routed
    exchange and the ``q * max_range`` rank window gathers through
    :func:`_select_shard_body` — a range spanning adjacent owners
    decomposes into per-shard sub-ranges by the live-lane count prefix
    and ONE psum stitches the slices back in rank order."""
    plane = _require_plane(level_keys, "splay_range_scan")
    if not isinstance(max_range, int) or isinstance(max_range, bool) \
            or max_range < 1:
        raise ValueError(
            f"splay_range_scan: max_range must be a positive int, got "
            f"{max_range!r}")
    lo = jnp.asarray(lo, jnp.int32)
    hi = jnp.asarray(hi, jnp.int32)
    if lo.shape != hi.shape:
        raise ValueError(
            f"splay_range_scan: lo/hi shapes differ: {lo.shape} vs "
            f"{hi.shape}")
    n = lo.shape[0]
    if n == 0:
        return (jnp.zeros((0, max_range), jnp.int32),
                jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32))
    start, count = _range_ranks(plane, lo, hi, query_block=query_block,
                                interpret=interpret, sharded=sharded)
    offs = jnp.arange(max_range, dtype=jnp.int32)[None, :]
    want = offs < jnp.minimum(count, max_range)[:, None]
    ranks = jnp.where(want, start[:, None] + offs, -1)
    keys = splay_select(plane, ranks.reshape(-1),
                        sharded=sharded).reshape(n, max_range)
    truncated = jnp.maximum(count - max_range, 0)
    return keys, count, truncated


def splay_top_k(level_keys, hits, k: int, sharded=None,
                axis: str = "model", mesh=None):
    """The ``k`` hottest live keys by hit mass: ``hits`` is a
    slot-indexed int32 ``[capacity]`` counter array (the state's
    ``selfhits``), gathered onto the bottom row through the plane's
    ``slots`` companion — so this only answers on a device-built plane
    with a live slot map (host planes carry ``slots = -1`` and report
    every lane missing).  Returns ``(keys [k], hits [k], ranks [k])``
    in descending hit order, ties broken by ascending packed-global
    rank (the ``lax.top_k`` index order); lanes past the live count
    answer ``(PAD_KEY, 0, -1)``.  ``k`` is static and must not exceed
    the plane width.

    Sharded execution is a per-shard local top-k + one ``[S, 3, k]``
    candidate all_gather + a replicated merge — never a replicated
    ``[W]`` hit row — and is bit-identical to the replicated path
    (same tie order)."""
    plane = _require_plane(level_keys, "splay_top_k")
    if not isinstance(k, int) or isinstance(k, bool) or k < 1:
        raise ValueError(f"splay_top_k: k must be a positive int, got "
                         f"{k!r}")
    width = jnp.asarray(plane.keys).shape[1]
    if k > width:
        raise ValueError(
            f"splay_top_k: k={k} exceeds the plane width {width}")
    hits = jnp.asarray(hits, jnp.int32)
    if mesh is not None or _ordered_dispatch(plane, sharded):
        mesh = _usable_width_mesh(plane, axis, mesh)
        if mesh is not None:
            dplane = _as_device_plane(plane)
            n_levels = dplane.keys.shape[0]
            keys, hv, ranks = _topk_fn(mesh, axis, n_levels,
                                       k)(dplane, hits)
            valid = hv >= 0
            return (jnp.where(valid, keys, jnp.int32(PAD_KEY)),
                    jnp.maximum(hv, 0),
                    jnp.where(valid, ranks, -1))
    keys = _replicated(jnp.asarray(plane.keys, jnp.int32))
    _reject_segmented(keys)
    n_levels, _ = keys.shape
    bot = keys[n_levels - 1]
    slots = _replicated(jnp.asarray(plane.slots, jnp.int32)) \
        if hasattr(plane, "slots") else jnp.full((width,), -1, jnp.int32)
    cap = hits.shape[0]
    live = (bot != PAD_KEY) & (slots >= 0)
    h = jnp.where(live, hits[jnp.clip(slots, 0, cap - 1)],
                  jnp.int32(-1))
    hv, idx = jax.lax.top_k(h, k)
    valid = hv >= 0
    return (jnp.where(valid, bot[idx], jnp.int32(PAD_KEY)),
            jnp.maximum(hv, 0),
            jnp.where(valid, idx, -1))

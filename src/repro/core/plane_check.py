"""Plane fsck: a jitted auditor for the device index plane.

The kernels in ``kernels/splay_search.py`` and the refresh paths in
``core/device_index.py`` never validate their inputs — they *assume*
the structural invariants that ``_assemble_device`` establishes and
the incremental refresh preserves (DESIGN.md §5.11 lists them as a
table).  A bit-flip, a lost shard, or a buggy refresh silently breaks
those assumptions and the descent starts returning wrong verdicts
without crashing.  This module is the serving loop's defence: one
jitted pass over ``(SplayState, DeviceLevelArrays)`` that re-derives
every invariant from scratch and returns a structured ``PlaneAudit``
of violation counts — never a bare boolean, never a silent pass.

Invariants audited (field → what the kernels assume):

====================  ====================================================
``row_unsorted``      every row is, per segment, a packed live prefix of
                      strictly ascending keys (pad-before-live counts too)
``block_order``       every live bottom key lies inside its block's
                      half-open ownership range from the recomputed
                      ``sharding.suffix_min_bounds`` boundary table —
                      exactly the table the routed search and the
                      sharded refresh rebuild per call
``widths_bad``        ``widths[r]`` equals the *global* live-lane count
                      of row r, and widths are nested
                      (``widths[r] <= widths[r+1]``)
``heights_bad``       per segment and row, the live-lane count equals
                      the number of bottom lanes with
                      ``heights >= L-1-r`` (heights↔row membership
                      prefix consistency); live heights non-negative
``upper_bad``         every live key of a row above the bottom is in
                      its block's bottom segment (one ``searchsorted``
                      per row), at a height of at least ``L-1-r`` — with
                      ``row_unsorted`` and ``heights_bad`` this pins
                      each row to exactly the keys its height admits
``local_bad``         when ``local_ok == 1``: ``local_bot`` /
                      ``local_heights`` / ``local_live`` are exact
                      copies of the resident bottom row (the §5.8
                      residency provenance); ``local_ok`` is 0/1
``state_missing``     alive state keys absent from the plane's bottom
                      row (the refresh dropped a key)
``state_extra``       bottom-row keys not alive in the state (the
                      plane resurrects a deleted/unknown key)
``counter_bad``       negative ``selfhits``/``hits``/``m``/``dhits``,
                      or ``dhits > m`` (the fractions in Lemma 1/2
                      would be meaningless)
``counter_saturated`` ``m`` or a ``selfhits`` lane within 2x of int32
                      overflow — a *warning* (exactness holds to
                      ``2**30``; see docs/COMPLEXITY.md), reported
                      separately so callers can treat it as non-fatal
====================  ====================================================

Segment discipline: ``n_segments`` is static.  ``1`` audits the packed
/ global layout (meshless planes, lanes-split sharded planes); ``S``
audits the §5.6 mass-split layout where each of the ``S`` width-``W/S``
blocks is an independent local assembly (each row's run packed from
its block's first lane).  ``audit_plane`` infers
the segment count from the concrete layout when not given.

``state_missing``/``state_extra`` compare against the state *snapshot*
passed in: audit at the epoch boundary (after refresh), where the two
agree exactly — mid-epoch they legitimately drift by the op batch.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import device_index as dix
from repro.core import splaylist as sx
from repro.parallel import sharding as shd

PAD_KEY = dix.PAD_KEY

# exact-count headroom: counters are exact integers up to 2**30 with a
# 2x safety margin before int32 overflow (docs/COMPLEXITY.md)
SATURATION_LIMIT = 2 ** 30


class PlaneAudit(NamedTuple):
    """Violation counts from one ``audit_plane`` pass (all int).

    A clean plane is all-zero *except possibly* ``counter_saturated``,
    which is a headroom warning, not a correctness violation —
    ``audit_ok`` treats it as non-fatal."""
    row_unsorted: int
    block_order: int
    widths_bad: int
    heights_bad: int
    upper_bad: int
    local_bad: int
    state_missing: int
    state_extra: int
    counter_bad: int
    counter_saturated: int


# the fields whose non-zero counts mean the plane is structurally wrong
FATAL_FIELDS = tuple(f for f in PlaneAudit._fields
                     if f != "counter_saturated")


@functools.partial(jax.jit, static_argnames=("n_segments",))
def _audit_device(st: sx.SplayState, plane: dix.DeviceLevelArrays,
                  n_segments: int):
    L, W = plane.keys.shape
    S = int(n_segments)
    wl = W // S
    keys = plane.keys
    col = jnp.arange(W, dtype=jnp.int32)
    blk = col // wl
    live = keys != PAD_KEY                      # [L, W]
    bot = keys[L - 1]
    bot_live = live[L - 1]

    # -- per-segment sorted packed live prefix ---------------------------
    same_blk = (blk[1:] == blk[:-1])[None, :]
    adj_live = live[:, :-1] & live[:, 1:] & same_blk
    inversions = adj_live & (keys[:, :-1] >= keys[:, 1:])
    pad_before_live = same_blk & ~live[:, :-1] & live[:, 1:]
    row_unsorted = jnp.sum(inversions) + jnp.sum(pad_before_live)

    # -- cross-block ordering via the recomputed boundary table ----------
    # same construction as the routed search: raw block-first keys with
    # shard 0 pinned at -inf, suffix-min over trailing empty blocks
    blk_first = bot.reshape(S, wl)[:, 0]
    raw = jnp.where(jnp.arange(S) == 0, jnp.int32(sx.NEG_INF_32),
                    blk_first)
    bounds = shd.suffix_min_bounds(raw)                       # [S]
    hi_tab = jnp.concatenate(
        [bounds[1:], jnp.array([sx.POS_INF_32], jnp.int32)])
    lo = bounds[blk]
    hi = hi_tab[blk]
    block_order = jnp.sum(bot_live & ((bot < lo) | (bot >= hi)))

    # -- widths: global live totals + nestedness -------------------------
    live_counts = jnp.sum(live, axis=1).astype(plane.widths.dtype)
    widths_bad = (jnp.sum(live_counts != plane.widths)
                  + jnp.sum(plane.widths[:-1] > plane.widths[1:]))

    # -- heights <-> row membership prefix consistency -------------------
    h = plane.heights
    hh = jnp.where(bot_live, h, -1)
    row_min = (L - 1 - jnp.arange(L, dtype=jnp.int32))        # [L]
    member = hh[None, :] >= row_min[:, None]                  # [L, W]
    exp_cnt = jnp.sum(member.reshape(L, S, wl), axis=2)       # [L, S]
    got_cnt = jnp.sum(live.reshape(L, S, wl), axis=2)         # [L, S]
    heights_bad = (jnp.sum(exp_cnt != got_cnt)
                   + jnp.sum(bot_live & (h < 0)))

    # -- upper rows: members of the block's bottom segment, tall enough -
    # (a key changed to a value absent from the bottom row, order kept,
    # passes every check above; the bottom row itself is checked
    # against the state below)
    if L > 1:
        up = keys[:-1].reshape(L - 1, S, wl)
        pos = jax.vmap(jax.vmap(jnp.searchsorted), in_axes=(None, 0))(
            bot.reshape(S, wl), up)                           # [L-1, S, wl]
        lane = (jnp.clip(pos, 0, wl - 1)
                + (jnp.arange(S, dtype=jnp.int32) * wl)[None, :, None]
                ).reshape(L - 1, W)
        member = ((jnp.take(bot, lane) == keys[:-1])
                  & (jnp.take(hh, lane) >= row_min[:-1, None]))
        upper_bad = jnp.sum(live[:-1] & ~member)
    else:
        upper_bad = jnp.int32(0)

    # -- residency provenance (§5.8) -------------------------------------
    lok = plane.local_ok[0]
    lok_range_bad = ((lok != 0) & (lok != 1)).astype(jnp.int32)
    local_mismatch = (
        jnp.sum(plane.local_bot != bot)
        + jnp.sum(plane.local_live != bot_live.astype(plane.local_live.dtype))
        + jnp.sum(plane.local_heights != h))
    local_bad = lok_range_bad + jnp.where(lok == 1, local_mismatch, 0)

    # -- state <-> plane membership agreement ----------------------------
    skeys, _ = dix._alive_slots(st)
    sk = sx.sort_values(skeys)                      # live prefix, PAD tail
    cs = jnp.cumsum(bot_live.astype(jnp.int32))
    n_plane = cs[W - 1]
    take = dix._compact_take(cs, W)
    pk = jnp.where(col < n_plane, jnp.take(bot, take), PAD_KEY)
    cap = sk.shape[0]
    pos = jnp.clip(jnp.searchsorted(pk, sk).astype(jnp.int32), 0, W - 1)
    state_missing = jnp.sum((sk != PAD_KEY)
                            & (jnp.take(pk, pos) != sk))
    pos2 = jnp.clip(jnp.searchsorted(sk, pk).astype(jnp.int32), 0, cap - 1)
    state_extra = jnp.sum((pk != PAD_KEY)
                          & (jnp.take(sk, pos2) != pk))

    # -- hit counters -----------------------------------------------------
    counter_bad = (jnp.any(st.selfhits < 0).astype(jnp.int32)
                   + jnp.any(st.hits < 0).astype(jnp.int32)
                   + (st.m < 0).astype(jnp.int32)
                   + (st.dhits < 0).astype(jnp.int32)
                   + (st.dhits > st.m).astype(jnp.int32))
    counter_saturated = ((st.m > SATURATION_LIMIT)
                         | (jnp.max(st.selfhits) > SATURATION_LIMIT)
                         ).astype(jnp.int32)

    return PlaneAudit(
        row_unsorted=row_unsorted.astype(jnp.int32),
        block_order=block_order.astype(jnp.int32),
        widths_bad=widths_bad.astype(jnp.int32),
        heights_bad=heights_bad.astype(jnp.int32),
        upper_bad=upper_bad.astype(jnp.int32),
        local_bad=local_bad.astype(jnp.int32),
        state_missing=state_missing.astype(jnp.int32),
        state_extra=state_extra.astype(jnp.int32),
        counter_bad=counter_bad,
        counter_saturated=counter_saturated,
    )


def infer_segments(plane, axis: str = "model") -> int:
    """Best-effort segment count for a *concrete* plane: segmented
    layouts carry their mesh in the array shardings
    (``sharding.plane_width_mesh``); packed layouts audit as one
    segment.  Raises when the plane looks segmented but its layout
    mesh is unrecoverable — pass ``n_segments`` explicitly then."""
    if not dix.plane_is_segmented(plane):
        return 1
    mesh = shd.plane_width_mesh(plane, axis)
    if mesh is None:
        raise ValueError(
            "plane looks segmented (interior pad runs) but carries no "
            "width-sharded layout to infer the segment count from; "
            "pass n_segments explicitly")
    return int(mesh.shape[axis])


def audit_plane(st: sx.SplayState, plane: dix.DeviceLevelArrays,
                n_segments: int | None = None,
                axis: str = "model") -> PlaneAudit:
    """Run the full invariant audit and return host-int violation
    counts.  ``n_segments`` is 1 for packed/global layouts and the
    shard count for §5.6 mass-split layouts; ``None`` infers it from
    the concrete plane (``infer_segments``)."""
    L, W = plane.keys.shape
    if n_segments is None:
        n_segments = infer_segments(plane, axis)
    n_segments = int(n_segments)
    if n_segments < 1 or W % n_segments:
        raise ValueError(
            f"audit_plane: width {W} not divisible into "
            f"{n_segments} segments")
    out = _audit_device(st, plane, n_segments=n_segments)
    return PlaneAudit(*(int(np.asarray(v)) for v in out))


def audit_ok(audit: PlaneAudit) -> bool:
    """True when no *fatal* invariant is violated (saturation is a
    warning, not corruption)."""
    return all(getattr(audit, f) == 0 for f in FATAL_FIELDS)


def audit_summary(audit: PlaneAudit) -> str:
    """One-line human summary: ``audit OK`` for clean planes, else
    ``audit FAIL[field=count,...]`` naming every violated invariant
    (saturation shows as a ``warn:`` suffix either way)."""
    bad = [f"{f}={getattr(audit, f)}" for f in FATAL_FIELDS
           if getattr(audit, f)]
    tail = (" warn:counter_saturated"
            if audit.counter_saturated else "")
    if not bad:
        return "audit OK" + tail
    return "audit FAIL[" + ",".join(bad) + "]" + tail


__all__ = [
    "PlaneAudit", "FATAL_FIELDS", "SATURATION_LIMIT",
    "audit_plane", "audit_ok", "audit_summary", "infer_segments",
]

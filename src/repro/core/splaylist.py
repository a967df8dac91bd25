"""Functional JAX splay-list engine.

Array-backed implementation of the splay-list with the forward-pass
rebalancing of Section 5, bit-exact against the pure-Python oracle
(``repro.core.ref_py``) — the test suite runs identical operation streams
through both and asserts equal results, path lengths, and final heights.

Representation (capacity ``C`` slots, ``L = max_level`` data levels, one
sentinel level on top; slot 0 = head, slot 1 = tail):

    key       int  [C]      NEG/POS_INF sentinels at slots 0/1
    nxt       int32[L+1, C] successor slot per level (-1 = unmaterialized)
    hits      cnt  [L+1, C] hits_u^h  (interval-sum semantics)
    selfhits  cnt  [C]      sh_u
    top       int32[C]      topmost level of the node
    nzero     int32[C]      lowest *materialized* level (lazy expansion)
    deleted   bool [C]
    m, dhits  cnt  []       total hit-ops / hits on marked nodes
    zl        int32[]       current bottom level of the list
    n_alloc   int32[]       bump allocator
    size      int32[]       unmarked key count
    counters  int32[K]      cumulative work counters, named by ``COUNTERS``

Counters use ``count_dtype`` (default int32: exact for m < 2^30; pass
int64 under jax_enable_x64 for longer runs).  Threshold comparisons are
exact integer shifts: ``s <= m/2^e  <=>  s <= (m >> e)`` and
``s > m/2^e  <=>  s > (m >> e)``.

Concurrency mapping (see DESIGN.md §2): the paper's lock-free search phase
is `find`/`find_batch` (pure, vmappable); the hand-over-hand locked update
phase is the serialized `update` fold inside `run_ops`/`run_batch` — a
total order over updates, which is precisely the guarantee hand-over-hand
locking provides in the C++ implementation.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF_32 = -(2 ** 31) + 1
POS_INF_32 = 2 ** 31 - 1

# op kinds for run_ops / run_epoch / run_serving.  The first three are
# the paper's mutating set ops (result: 0/1 verdict).  OP_PRED and
# OP_RANGE are the ordered read queries (DESIGN.md §5.10): pure reads —
# no counter touch, no splay, ``upd`` ignored — whose int32 result is
# the *answer*, not a verdict: OP_PRED answers the largest live key
# <= key (NEG_INF_32 when none), OP_RANGE answers the rank count
# |{live k' : k' <= key}| (a closed prefix-range count; a two-sided
# [lo, hi] count is the difference of two OP_RANGE lanes).
OP_CONTAINS = 0
OP_INSERT = 1
OP_DELETE = 2
OP_PRED = 3
OP_RANGE = 4

HEAD = 0
TAIL = 1

# Names, in order, of ``SplayState.counters``: cumulative int32 counts of
# the serving path's work, carried in the state (no host sync to keep
# them) and read on the host by :func:`serving_counters`.  They wrap
# modulo 2^32; a reader takes differences of two readings.
#   epochs              serving epochs run
#   fold_steps          scan steps of the update fold (B per fold)
#   fold_active         fold steps that carried an update: unique keys
#                       with weight > 0 (aggregated), lanes with
#                       ``upd & present`` (per-lane), every lane (run_ops)
#   state_rebuilds      rebuilds of the state (``_maybe_rebuild`` fired)
#   plane_rebuilds      epochs whose plane came from a full rebuild
#   plane_rows_rebuilt  plane rows the epochs' refreshes recomputed
#   plane_rows_changed  plane rows whose keys or width an epoch changed
#   route_queries       lanes sent through the routed query exchange
#                       (the width-sharded plane search)
#   route_spilled       of them, lanes answered on its spill path
COUNTERS = ("epochs", "fold_steps", "fold_active", "state_rebuilds",
            "plane_rebuilds", "plane_rows_rebuilt", "plane_rows_changed",
            "route_queries", "route_spilled")


class SplayState(NamedTuple):
    key: jax.Array        # [C]
    nxt: jax.Array        # [L+1, C]
    hits: jax.Array       # [L+1, C]
    selfhits: jax.Array   # [C]
    top: jax.Array        # [C]
    nzero: jax.Array      # [C]
    deleted: jax.Array    # [C]
    m: jax.Array          # scalar
    dhits: jax.Array      # scalar
    zl: jax.Array         # scalar int32
    n_alloc: jax.Array    # scalar int32
    size: jax.Array       # scalar int32
    counters: jax.Array   # int32 [len(COUNTERS)]

    @property
    def max_level(self) -> int:
        return self.nxt.shape[0] - 1

    @property
    def capacity(self) -> int:
        return self.key.shape[0]


def make(capacity: int, max_level: int = 32,
         count_dtype=jnp.int32, key_dtype=jnp.int32) -> SplayState:
    """Empty splay-list. head/tail sentinels occupy slots 0/1."""
    L = max_level
    ml1 = L - 1
    key = jnp.full((capacity,), POS_INF_32, dtype=key_dtype)
    key = key.at[HEAD].set(NEG_INF_32)
    nxt = jnp.full((L + 1, capacity), -1, dtype=jnp.int32)
    # head materialized at [ML1, ML] only (lazy expansion applies to head!)
    nxt = nxt.at[ml1, HEAD].set(TAIL)
    nxt = nxt.at[L, HEAD].set(TAIL)
    hits = jnp.zeros((L + 1, capacity), dtype=count_dtype)
    selfhits = jnp.zeros((capacity,), dtype=count_dtype)
    selfhits = selfhits.at[HEAD].set(1).at[TAIL].set(1)
    top = jnp.zeros((capacity,), dtype=jnp.int32)
    top = top.at[HEAD].set(L).at[TAIL].set(L)
    nzero = jnp.full((capacity,), L, dtype=jnp.int32)
    nzero = nzero.at[HEAD].set(ml1).at[TAIL].set(L)
    deleted = jnp.zeros((capacity,), dtype=bool)
    zero = jnp.array(0, dtype=count_dtype)
    return SplayState(
        key=key, nxt=nxt, hits=hits, selfhits=selfhits, top=top,
        nzero=nzero, deleted=deleted, m=zero, dhits=zero,
        zl=jnp.array(ml1, jnp.int32), n_alloc=jnp.array(2, jnp.int32),
        size=jnp.array(0, jnp.int32),
        counters=jnp.zeros((len(COUNTERS),), jnp.int32))


def _count(st: SplayState, **by) -> SplayState:
    """``st`` with ``by[name]`` added to each named counter."""
    assert set(by) <= set(COUNTERS), by
    inc = jnp.stack([jnp.asarray(by.get(c, 0)).astype(jnp.int32)
                     for c in COUNTERS])
    return st._replace(counters=st.counters + inc)


def serving_counters(st: SplayState) -> dict:
    """The state's counters on the host, ``{name: int}`` in ``COUNTERS``
    order (one device read of ``len(COUNTERS)`` int32)."""
    return dict(zip(COUNTERS, np.asarray(st.counters).tolist()))


# ---------------------------------------------------------------------------
# primitive accessors
# ---------------------------------------------------------------------------

def _eff_next(st: SplayState, i, h):
    """Successor of slot i at level h under lazy expansion."""
    lvl = jnp.maximum(h, st.nzero[i])
    return st.nxt[lvl, i]


def _whits(st: SplayState, i, h):
    """hits_i^h honouring lazy expansion (logical 0 below nzero)."""
    return jnp.where(h >= st.nzero[i], st.hits[h, i],
                     jnp.zeros((), st.hits.dtype))


def _get_hits(st: SplayState, i, h):
    """hits(C_i^h) = sh_i + hits_i^h."""
    return st.selfhits[i] + _whits(st, i, h)


def _masked_set(x, idx, val, do):
    """``x.at[idx].set(val)`` where ``do`` holds, else ``x`` unchanged.

    The update phase is written with these element-wise selects instead
    of ``lax.cond`` over the state: XLA moves the whole carried state
    (two ``[L+1, C]`` arrays) through every conditional, which on the TPU
    costs milliseconds per operation at ``C ~ 10^5``."""
    return x.at[idx].set(jnp.where(do, val, x[idx]))


def _fill_down(st: SplayState, i, h, do=True) -> SplayState:
    """Materialize slot i's levels down to h (vectorized updateZeroLevel);
    a no-op where ``do`` is false."""
    zl_i = st.nzero[i]
    h = jnp.where(do, h, zl_i)
    lvls = jnp.arange(st.nxt.shape[0])
    mask = (lvls >= h) & (lvls < zl_i)
    col_nxt = jnp.where(mask, st.nxt[zl_i, i], st.nxt[:, i])
    col_hits = jnp.where(mask, 0, st.hits[:, i])
    return st._replace(
        nxt=st.nxt.at[:, i].set(col_nxt),
        hits=st.hits.at[:, i].set(col_hits),
        nzero=st.nzero.at[i].set(jnp.minimum(zl_i, h)))


def _shift(x, e):
    return jnp.right_shift(x, e.astype(x.dtype))


# ---------------------------------------------------------------------------
# find — the lock-free search phase (pure)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=())
def find(st: SplayState, k) -> Tuple[jax.Array, jax.Array]:
    """Return (slot, steps): slot of the node with key k if physically
    present else -1. Counts horizontal moves + level descents (the paper's
    'average length of a path' metric)."""
    slot, steps, _ = _find(st, k)
    return slot, steps


def _find(st: SplayState, k):
    """:func:`find` plus where its walk ended: for an absent key, the
    bottom-level predecessor an insert links the new node behind."""
    ml1 = st.max_level - 1

    def cond(c):
        pred, h, steps, found = c
        return (h >= st.zl) & (~found)

    def body(c):
        pred, h, steps, found = c
        curr = _eff_next(st, pred, h)
        adv = st.key[curr] <= k
        pred2 = jnp.where(adv, curr, pred)
        found2 = jnp.where(adv, found, st.key[pred] == k)
        h2 = jnp.where(adv, h, h - 1)
        return pred2, h2, steps + 1, found2

    pred0 = jnp.array(HEAD, jnp.int32)
    pred, h, steps, found = jax.lax.while_loop(
        cond, body, (pred0, jnp.array(ml1, jnp.int32),
                     jnp.array(0, jnp.int32), jnp.array(False)))
    # found can also become true exactly at loop exit (descended past bottom)
    found = found | (st.key[pred] == k)
    slot = jnp.where(found & (pred != HEAD), pred, -1)
    return slot.astype(jnp.int32), steps, pred


def find_batch(st: SplayState, ks) -> Tuple[jax.Array, jax.Array]:
    """Vectorized lock-free search for a batch of keys (read-only)."""
    return jax.vmap(lambda k: find(st, k))(ks)


# ---------------------------------------------------------------------------
# the forward-pass update (counters + ascent/descent), Section 5
# ---------------------------------------------------------------------------

def _update(st: SplayState, k, w=None, active=True) -> SplayState:
    """Forward-pass rebalance for a physically-present key k.

    ``w`` is the hit weight (default 1): the batched-update aggregation
    of ``run_contains_batch(..., aggregate=True)`` folds ``w`` identical
    hit-operations into ONE traversal by adding ``w`` everywhere the
    unit pass adds 1 (m, the parent subtree counters, selfhits).  The
    ascent/descent checks then see the epoch-final counters — the
    flat-combining analogue of the paper's combined update phase.

    ``active`` (traced bool) gates the whole pass: false leaves the
    state untouched.  Every branch of the pass is a masked element
    update (:func:`_masked_set`), never a ``lax.cond`` over the state."""
    L = st.max_level
    ml1 = L - 1
    one = jnp.ones((), st.m.dtype) if w is None else w.astype(st.m.dtype)
    zero = jnp.zeros((), st.m.dtype)
    st = st._replace(m=st.m + jnp.where(active, one, zero))
    curr_m = st.m

    def asc_sum(s, pp, curh):
        return _whits(s, pp, curh + 1) - _whits(s, pp, curh)

    def promote_cascade(s: SplayState, curr, pp, act):
        """Promote curr up while the ascent condition holds."""
        def cond(c):
            s, curh, _ = c
            ok = act & (curh + 1 < L) & (curh < s.top[pp])
            thr = _shift(curr_m, ml1 - curh - 1)
            return ok & (asc_sum(s, pp, curh) > thr)

        def body(c):
            s, curh, _ = c
            s = _fill_down(s, pp, curh)
            new_hits = s.hits[curh + 1, pp] - s.hits[curh, pp] - s.selfhits[curr]
            s = s._replace(
                top=s.top.at[curr].set(curh + 1),
                hits=s.hits.at[curh + 1, curr].set(new_hits),
                nxt=s.nxt.at[curh + 1, curr].set(s.nxt[curh + 1, pp]))
            s = s._replace(
                hits=s.hits.at[curh + 1, pp].set(s.hits[curh, pp]),
                nxt=s.nxt.at[curh + 1, pp].set(curr))
            return s, curh + 1, True

        s, curh, promoted = jax.lax.while_loop(
            cond, body, (s, s.top[curr], False))
        return s, promoted

    def demote(s: SplayState, curr, pred, h, do):
        s = s._replace(zl=jnp.where(do & (h == s.zl), s.zl - 1, s.zl))
        s = _fill_down(s, curr, h - 1, do)
        s = _fill_down(s, pred, h - 1, do)
        gh_curr = s.selfhits[curr] + s.hits[h, curr]
        hits = s.hits.at[h, pred].add(jnp.where(do, gh_curr, zero))
        nxt = _masked_set(s.nxt, (h, pred), s.nxt[h, curr], do)
        return s._replace(
            hits=_masked_set(hits, (h, curr), 0, do),
            nxt=_masked_set(nxt, (h, curr), -1, do),
            top=_masked_set(s.top, curr, h - 1, do))

    def body(c):
        s, h, pred, pp, found, done, scanned = c
        curr = _eff_next(s, pred, h)
        gt = s.key[curr] > k

        # ---- branch A (gt): end of scan at this level --------------------
        # Two sub-cases, mirroring the oracle's control flow exactly:
        #   * level entry (nothing scanned yet): pred is the parent of k at
        #     this level -> increment its subtree counter;
        #   * scan exit (something scanned): the parent was already counted
        #     inside the scan via is_parent -> descend with no increment.
        incr = gt & ~(found | scanned)
        s = _fill_down(s, pred, h, incr)
        s = s._replace(hits=s.hits.at[h, pred].add(jnp.where(incr, one, zero)))

        # ---- branch B (~gt): process curr --------------------------------
        b = ~gt
        is_parent = s.key[_eff_next(s, curr, h)] > k
        is_target = s.key[curr] == k
        hit_self = b & is_parent & is_target
        hit_sub = b & is_parent & ~is_target
        s = s._replace(selfhits=s.selfhits.at[curr].add(
            jnp.where(hit_self, one, zero)))
        s = _fill_down(s, curr, h, hit_sub)
        s = s._replace(hits=s.hits.at[h, curr].add(
            jnp.where(hit_sub, one, zero)))

        s, promoted = promote_cascade(s, curr, pp, b)
        nk = s.key[_eff_next(s, curr, h)]
        thr = _shift(curr_m, ml1 - h)
        desc = (b & ~promoted & (s.top[curr] == h) & (nk <= k) &
                (_get_hits(s, curr, h) + _get_hits(s, pred, h) <= thr))
        s = demote(s, curr, pred, h, desc)

        return (s, jnp.where(gt, h - 1, h),
                jnp.where(gt | desc, pred, curr),
                jnp.where(gt, pred, jnp.where(promoted, curr, pp)),
                found | hit_self, gt & found, b)

    def cond(c):
        s, h, pred, pp, found, done, scanned = c
        return (~done) & (h >= s.zl)

    init = (st, jnp.array(ml1, jnp.int32), jnp.array(HEAD, jnp.int32),
            jnp.array(HEAD, jnp.int32), jnp.array(False),
            ~jnp.asarray(active), jnp.array(False))
    st, *_ = jax.lax.while_loop(cond, body, init)
    return st


# ---------------------------------------------------------------------------
# physical insert at the bottom level
# ---------------------------------------------------------------------------

def _link_bottom(st: SplayState, k, pred, do=True) -> SplayState:
    """Link a new node for ``k`` behind ``pred`` at the bottom level
    (``pred`` from :func:`_find`'s walk for the absent key)."""
    zl = st.zl
    st = _fill_down(st, pred, zl, do)
    j = st.n_alloc
    nxt = _masked_set(st.nxt, (zl, j), st.nxt[zl, pred], do)
    return st._replace(
        key=_masked_set(st.key, j, k.astype(st.key.dtype), do),
        nxt=_masked_set(nxt, (zl, pred), j, do),
        top=_masked_set(st.top, j, zl, do),
        nzero=_masked_set(st.nzero, j, zl, do),
        selfhits=_masked_set(st.selfhits, j, 0, do),
        deleted=_masked_set(st.deleted, j, False, do),
        n_alloc=st.n_alloc + jnp.where(do, 1, 0).astype(jnp.int32))


# ---------------------------------------------------------------------------
# public operations.  `upd` is the pre-sampled Bernoulli(p) coin for the
# relaxed rebalancing of Section 4 (pass True for the exact algorithm).
# ---------------------------------------------------------------------------

def _live_mask(st: SplayState) -> jax.Array:
    """bool [C]: the slots whose keys the ordered queries (and the index
    plane — same predicate as ``device_index._alive_slots``) see as
    live: allocated nodes, not delete-marked, sentinels excluded."""
    idx = jnp.arange(st.capacity)
    return ((idx >= 2) & (idx < st.n_alloc) & (~st.deleted)
            & (st.key < POS_INF_32))


def _pred_key(st: SplayState, k) -> jax.Array:
    """The largest live key ``<= k`` (``NEG_INF_32`` when none)."""
    mask = _live_mask(st) & (st.key <= k)
    return jnp.max(jnp.where(mask, st.key, NEG_INF_32)).astype(jnp.int32)


def _prefix_count(st: SplayState, k) -> jax.Array:
    """``|{live k' : k' <= k}|``."""
    return jnp.sum((_live_mask(st) & (st.key <= k)).astype(jnp.int32))


def _apply_op(st: SplayState, kind, k, upd) -> Tuple[SplayState,
                                                      jax.Array, jax.Array]:
    """One operation of any kind (traced ``kind``), without the rebuild
    check: (state, int32 result, path_len).  The set operations are
    masked updates around one :func:`_update` pass, so one program with
    no conditional over the state serves every kind:

    * contains: rebalance a present key when the coin ``upd`` says so
      (a hit on a marked node counts toward the deleted hits);
    * insert: revive a marked node, or link a new one at the bottom
      (both rebalance unconditionally), or visit an existing one;
    * delete: mark a live node (rebalance, then its selfhits join the
      deleted hits), or visit a marked one;
    * ``OP_PRED`` / ``OP_RANGE``: pure reads of the live key set."""
    slot, steps, pred = _find(st, k)
    present = slot >= 0
    slot_c = jnp.maximum(slot, 0)
    marked = present & st.deleted[slot_c]
    live = present & ~marked
    one = jnp.ones((), st.m.dtype)
    zero = jnp.zeros((), st.m.dtype)
    is_c, is_i, is_d = (kind == OP_CONTAINS, kind == OP_INSERT,
                        kind == OP_DELETE)
    revive, new, gone = is_i & marked, is_i & ~present, is_d & live

    st = st._replace(
        deleted=_masked_set(st.deleted, slot_c, gone, revive | gone),
        dhits=st.dhits - jnp.where(revive, st.selfhits[slot_c], zero),
        size=st.size + (revive | new).astype(jnp.int32)
        - gone.astype(jnp.int32))
    st = _link_bottom(st, k, pred, new)
    st = _update(st, k, active=(is_i & (upd | ~present | marked)) | gone
                 | ((is_c | is_d) & present & upd))
    st = st._replace(dhits=st.dhits + jnp.where(
        gone, st.selfhits[slot_c],
        jnp.where((is_c | is_d) & marked & upd, one, zero)))

    res = jnp.where(is_c, live, jnp.where(is_i, ~present | marked, gone))
    res = jax.lax.switch(jnp.maximum(kind - OP_DELETE, 0), [
        lambda: res.astype(jnp.int32),
        lambda: _pred_key(st, k),
        lambda: _prefix_count(st, k)])
    return st, res, steps


def contains(st: SplayState, k, upd) -> Tuple[SplayState, jax.Array,
                                               jax.Array]:
    st, live, steps = _apply_op(st, OP_CONTAINS, k, upd)
    return _maybe_rebuild(st), live.astype(bool), steps


def insert(st: SplayState, k, upd) -> Tuple[SplayState, jax.Array, jax.Array]:
    st, ok, steps = _apply_op(st, OP_INSERT, k, upd)
    return st, ok.astype(bool), steps


def delete(st: SplayState, k, upd) -> Tuple[SplayState, jax.Array,
                                             jax.Array]:
    st, ok, steps = _apply_op(st, OP_DELETE, k, upd)
    return _maybe_rebuild(st), ok.astype(bool), steps


def predecessor(st: SplayState, k, upd=None) -> Tuple[SplayState,
                                                      jax.Array,
                                                      jax.Array]:
    """The ``OP_PRED`` state walk: largest live key ``<= k``
    (``NEG_INF_32`` when none), as (state, key, path_len) matching the
    :func:`run_ops` branch signature.  A pure read — the state comes
    back untouched and ``upd`` is ignored (ordered queries never splay;
    DESIGN.md §5.10) — so the answer is bit-identical to the plane's
    ``kernels.ops.splay_predecessor`` on the epoch snapshot.
    ``path_len`` is the :func:`find` walk length (the same adaptivity
    metric as ``contains``)."""
    del upd
    _, steps = find(st, k)
    return st, _pred_key(st, k), steps


def rank_count(st: SplayState, k, upd=None) -> Tuple[SplayState,
                                                     jax.Array,
                                                     jax.Array]:
    """The ``OP_RANGE`` state walk: ``|{live k' : k' <= k}|`` — the
    closed prefix-range count (the plane answers it as predecessor rank
    + 1; ``kernels.ops.splay_rank``).  Pure read, ``upd`` ignored;
    returns (state, count, path_len) like the other op branches."""
    del upd
    _, steps = find(st, k)
    return st, _prefix_count(st, k), steps


# ---------------------------------------------------------------------------
# rebuild (Section 2.2 "Efficient Rebuild") — JAX-native, vectorized.
# The paper's recursion is unrolled level-by-level: at relative level r
# (top-down) every segment whose hit total H satisfies bit_length(H)-1 == r
# splits at its weighted median (the middle cell of the virtual array T).
# ---------------------------------------------------------------------------

def sort_values(x):
    """``jnp.sort`` of a 1-D int32 vector, as one unstable single-operand
    sort padded to a power of two with ``POS_INF_32`` (the result keeps
    the first ``len(x)`` lanes).  The TPU compiler takes tens of seconds
    over a stable, multi-operand or ``top_k`` sort of 10^5 lanes, and a
    few over this one."""
    c = x.shape[0]
    p2 = 1 << max(c - 1, 1).bit_length()
    return jax.lax.sort(jnp.pad(x.astype(jnp.int32), (0, p2 - c),
                                constant_values=POS_INF_32),
                        is_stable=False)[:c]


def key_order(keys, alive):
    """The slot order of a stable ``argsort(where(alive, keys, +INF))``
    for distinct alive keys: alive slots by key, then the other slots
    by index — from one :func:`sort_values`, a searchsorted and a
    permutation scatter."""
    c = keys.shape[0]
    sk = jnp.where(alive, keys, POS_INF_32).astype(jnp.int32)
    srt = sort_values(sk)
    n = jnp.sum(alive.astype(jnp.int32))
    rest = jnp.cumsum((~alive).astype(jnp.int32)) - 1
    pos = jnp.where(alive, jnp.searchsorted(srt, sk).astype(jnp.int32),
                    n + rest)
    return jnp.zeros((c,), jnp.int32).at[pos].set(
        jnp.arange(c, dtype=jnp.int32), unique_indices=True)


def _maybe_rebuild(st: SplayState) -> SplayState:
    trig = (st.m > 0) & (2 * st.dhits >= st.m)

    def fire(s):
        with jax.named_scope("splay.state_rebuild"):
            return _count(rebuild(s), state_rebuilds=1)

    return jax.lax.cond(trig, fire, lambda s: s, st)


def rebuild(st: SplayState) -> SplayState:
    C = st.capacity
    L = st.max_level
    ml1 = L - 1
    cnt_dt = st.hits.dtype

    # gather alive nodes in key order
    is_node = (jnp.arange(C) >= 2) & (jnp.arange(C) < st.n_alloc)
    alive = is_node & ~st.deleted & (st.key < POS_INF_32)
    order = key_order(st.key, alive)                   # alive first, by key
    keys_s = st.key[order]
    sh_s = jnp.where(alive[order], st.selfhits[order], 0)
    alive_s = alive[order]
    n = jnp.sum(alive_s.astype(jnp.int32))

    big_m = jnp.sum(sh_s)

    def bitlen(x):
        """number of bits of x (0 -> 0); exact integer floor(log2)+1."""
        def body(i, o):
            return jnp.where(_shift(x, i) > 0, i + 1, o)
        return jax.lax.fori_loop(0, 8 * x.dtype.itemsize - 1, body,
                                 jnp.zeros((), jnp.int32))

    k_new = jnp.maximum(bitlen(big_m) - 1, 0)
    k_new = jnp.minimum(k_new, ml1)
    zl_new = ml1 - k_new

    pref = jnp.cumsum(sh_s)                            # inclusive prefix
    pref0 = jnp.concatenate([jnp.zeros((1,), cnt_dt), pref[:-1]])

    # heights: rel height per sorted position, assigned top-down
    rel = jnp.full((C,), -1, jnp.int32)                # -1 = unassigned → 0
    idx = jnp.arange(C)

    def level_body(r_rev, rel):
        r = k_new - r_rev                              # from k_new down to 0
        # boundaries: positions already assigned height > r
        bnd = rel > r
        # segment start prefix value: max over j<=i of (bnd? pref[j] : 0)
        start_w = jax.lax.cummax(jnp.where(bnd, pref, jnp.zeros_like(pref)))
        # shift right: segment of i starts after the last boundary strictly
        # before i
        start_w = jnp.concatenate(
            [jnp.zeros((1,), cnt_dt), start_w[:-1]])
        # segment end prefix value: min over j>=i of (bnd? pref0[j] : M)
        end_base = jnp.where(bnd, pref0, jnp.full_like(pref0, big_m))
        end_w = jax.lax.cummin(end_base, reverse=True)
        end_w = jnp.concatenate([end_w[1:], jnp.full((1,), big_m, cnt_dt)])
        seg_h = end_w - start_w
        fires = (~bnd) & alive_s & (rel < 0) & (
            seg_h >= (jnp.ones((), cnt_dt) << r.astype(cnt_dt)))
        # weighted median: first position with pref - start_w >= ceil(H/2)
        pos = (seg_h + 1) // 2
        reach = (pref - start_w) >= pos
        reach_prev = (pref0 - start_w) >= pos
        is_median = fires & reach & ~reach_prev
        return jnp.where(is_median, r, rel)

    rel = jax.lax.fori_loop(0, k_new + 1, level_body, rel)
    rel = jnp.where(alive_s, jnp.maximum(rel, 0), -1)
    top_new = jnp.where(alive_s, zl_new + rel, 0)

    # fresh layout: the alive nodes are a key-ordered prefix of the sorted
    # positions (at most C - 2 of them), and position p lands in slot
    # p + 2 — so every per-slot array is its per-position array shifted
    # right by two lanes behind the HEAD and TAIL columns.  Written as
    # shifts, not scatters or gathers: the TPU compiler takes many
    # minutes over [L+1, C] gathers indexed by a scan at C ~ 10^5.
    def by_slot(head, tail, rows, fill):
        rows = jnp.where(alive_s, rows, fill)
        lead = jnp.stack([jnp.broadcast_to(head, rows.shape[:-1]),
                          jnp.broadcast_to(tail, rows.shape[:-1])], -1)
        return jnp.concatenate([lead.astype(rows.dtype), rows[..., :C - 2]],
                               axis=-1)

    new_key = by_slot(NEG_INF_32, POS_INF_32, keys_s.astype(st.key.dtype),
                      POS_INF_32)
    new_sh = by_slot(1, 1, sh_s.astype(cnt_dt), 0)
    new_top = by_slot(L, L, top_new.astype(jnp.int32), 0)
    new_nzero = by_slot(zl_new, L, jnp.broadcast_to(zl_new, (C,)), L)

    # per-level links + interval-sum hit counters
    lvls = jnp.arange(L + 1, dtype=jnp.int32)[:, None]          # [L+1, 1]
    at_lvl = alive_s[None, :] & (top_new[None, :] >= lvls)      # [L+1, C]
    # next alive position at this level and its exclusive hit prefix,
    # scanning right-to-left (pref0 is non-decreasing, so the min over
    # the later positions is the nearest one's)
    nxt_pos = jax.lax.cummin(jnp.where(at_lvl, idx[None, :], C + 7),
                             axis=1, reverse=True)
    nxt_pref0 = jax.lax.cummin(jnp.where(at_lvl, pref0[None, :], big_m),
                               axis=1, reverse=True)
    nxt_pos_excl = jnp.concatenate(
        [nxt_pos[:, 1:], jnp.full((L + 1, 1), C + 7)], axis=1)
    # successor slot (tail if none)
    succ_slot = jnp.where(nxt_pos_excl <= C - 1, nxt_pos_excl + 2,
                          TAIL).astype(jnp.int32)
    # interval sum (this, succ): pref0[succ_pos] - pref[this]
    succ_pref0 = jnp.concatenate(
        [nxt_pref0[:, 1:], jnp.full((L + 1, 1), big_m, cnt_dt)], axis=1)
    seg_hits = (succ_pref0 - pref[None, :]).astype(cnt_dt)

    write_mask = at_lvl & (lvls >= zl_new)
    new_nxt = by_slot(-1, -1, jnp.where(write_mask, succ_slot, -1), -1)
    new_hits = by_slot(0, 0, jnp.where(write_mask, seg_hits, 0), 0)

    # head links: first alive position at each level (or tail)
    first_pos = nxt_pos[:, 0]
    head_succ = jnp.where(first_pos <= C - 1, first_pos + 2,
                          TAIL).astype(jnp.int32)
    head_hits = nxt_pref0[:, 0].astype(cnt_dt)
    head_lvl_mask = (lvls[:, 0] >= zl_new) & (lvls[:, 0] <= ml1)
    new_nxt = new_nxt.at[:, HEAD].set(
        jnp.where(head_lvl_mask, head_succ, -1))
    new_nxt = new_nxt.at[L, HEAD].set(TAIL)
    new_hits = new_hits.at[:, HEAD].set(jnp.where(head_lvl_mask, head_hits, 0))

    # clean slots: deleted=False everywhere, parked slot C-1 reset
    new_deleted = jnp.zeros((C,), bool)

    return SplayState(
        key=new_key, nxt=new_nxt, hits=new_hits, selfhits=new_sh,
        top=new_top, nzero=new_nzero, deleted=new_deleted,
        m=big_m, dhits=jnp.zeros((), cnt_dt),
        zl=zl_new.astype(jnp.int32), n_alloc=(n + 2).astype(jnp.int32),
        size=n.astype(jnp.int32), counters=st.counters)


# ---------------------------------------------------------------------------
# operation-stream driver (the benchmark engine)
# ---------------------------------------------------------------------------

@jax.jit
def run_ops(st: SplayState, kinds, keys, upd_mask):
    """Apply a stream of operations (scan; one :func:`_apply_op` per op,
    whatever its kind, then the rebuild check).  Returns final state
    plus per-op (result int32, path_len).  The result lane carries the
    op's answer: 0/1 verdicts for contains/insert/delete, the
    predecessor key for ``OP_PRED``, the prefix-range count for
    ``OP_RANGE`` (see the op-kind constants)."""

    def step(s, op):
        s, res, plen = _apply_op(s, *op)
        return _maybe_rebuild(s), (res, plen)

    st, (res, plen) = jax.lax.scan(step, st, (kinds, keys, upd_mask))
    # every op of the stream walks and answers: each step is active
    n = keys.shape[0]
    return _count(st, fold_steps=n, fold_active=n), res, plen


def pad_op_batch(kinds, keys, upd_mask, batch: int):
    """Host-side static-shape padding for epoch op buffers (the serving
    engine's jit-stability seam, DESIGN.md §5.9): right-pad an op batch
    of ``n <= batch`` live lanes to exactly ``batch`` lanes with
    guaranteed no-ops — ``OP_CONTAINS`` with ``upd=False`` (a pure
    read: no counter touch, no structural change, so the padded epoch
    leaves the state bit-identical to the unpadded one).

    Pad *keys* cycle the batch's live keys (``np.resize``) instead of a
    sentinel: on the routed sharded search path every in-batch lane is
    exchanged (only wrapper-added pads past ``n_live`` are excluded),
    so a constant sentinel key would pile fake occupancy onto one shard
    and distort the controller's balance signal — cycled real keys keep
    the per-shard occupancy mirroring the live key distribution.  An
    all-pad batch (``n == 0``) falls back to the max in-range key,
    which stays harmless (reads only).

    Returns ``(kinds[batch], keys[batch], upd[batch], n)`` as int32 /
    int32 / bool numpy arrays plus the live-lane count."""
    kinds = np.asarray(kinds, np.int32).ravel()
    keys = np.asarray(keys, np.int32).ravel()
    upd = np.asarray(upd_mask, bool).ravel()
    n = kinds.shape[0]
    if not (keys.shape[0] == n and upd.shape[0] == n):
        raise ValueError(
            f"ragged op batch: kinds={n}, keys={keys.shape[0]}, "
            f"upd={upd.shape[0]}")
    if n > batch:
        raise ValueError(f"op batch of {n} exceeds pad target {batch}")
    out_kinds = np.full(batch, OP_CONTAINS, np.int32)
    out_keys = np.full(batch, POS_INF_32 - 1, np.int32)
    out_upd = np.zeros(batch, bool)
    out_kinds[:n] = kinds
    out_upd[:n] = upd
    if n:
        out_keys[:] = np.resize(keys, batch)
    return out_kinds, out_keys, out_upd, n


@functools.partial(jax.jit, static_argnames=("aggregate",))
def run_contains_batch(st: SplayState, keys, upd_mask,
                       aggregate: bool = False):
    """The concurrent-execution analogue (DESIGN.md §2): a batch of B
    lock-free searches evaluated in parallel (vmap) against the state
    snapshot, followed by the serialized update fold for the subsampled
    updaters (hand-over-hand locking guarantees exactly this total order
    in the C++ version).  Rebuild is deferred to the batch boundary so
    marked-but-visited keys stay physically present for the whole batch.

    ``aggregate=True`` (DESIGN.md §2.1) switches the fold to the batched
    aggregation mode: the key batch is deduplicated (sort + segment
    sums), per-key hit counts accumulate into a weight, and ONE weighted
    rebalance fold runs per *unique* key (in ascending key order) instead
    of one per operation — the flat-combining analogue of the paper's
    combined update phase.  On a duplicate-free batch this performs
    exactly the per-op folds of the serialized mode, just in sorted key
    order.  Search results are computed against the snapshot either way.
    Returns (state, results[B], steps[B])."""
    slots, steps = find_batch(st, keys)
    present = slots >= 0
    marked = present & st.deleted[jnp.maximum(slots, 0)]
    one = jnp.ones((), st.m.dtype)

    if aggregate:
        B = keys.shape[0]
        cdt = st.m.dtype
        order = jnp.argsort(keys)
        ks = keys[order]
        do = (upd_mask & present)[order]
        mk = marked[order]
        first = jnp.concatenate(
            [jnp.ones((1,), bool), ks[1:] != ks[:-1]])
        seg = jnp.cumsum(first.astype(jnp.int32)) - 1
        w = jax.ops.segment_sum(do.astype(cdt), seg, num_segments=B)
        wm = jax.ops.segment_sum((do & mk).astype(cdt), seg,
                                 num_segments=B)
        uk = jax.ops.segment_min(ks, seg, num_segments=B)

        def agg_step(s, op):
            k, wk, wmk = op
            s = _update(s, k, wk, active=wk > 0)
            return s._replace(dhits=s.dhits + wmk), ()

        st, _ = jax.lax.scan(agg_step, st, (uk, w, wm))
        st = _count(st, fold_steps=B, fold_active=jnp.sum(w > 0))
        st = _maybe_rebuild(st)
        return st, present & ~marked, steps

    def upd_step(s, op):
        k, do, pres, mk = op
        s = _update(s, k, active=do & pres)
        return s._replace(dhits=jnp.where(do & pres & mk, s.dhits + one,
                                          s.dhits)), ()

    st, _ = jax.lax.scan(upd_step, st, (keys, upd_mask, present, marked))
    st = _count(st, fold_steps=keys.shape[0],
                fold_active=jnp.sum(upd_mask & present))
    st = _maybe_rebuild(st)
    return st, present & ~marked, steps


# ---------------------------------------------------------------------------
# serving epochs: op batch + device index-plane refresh, all under jit
# (DESIGN.md §5.3)
# ---------------------------------------------------------------------------

def _check_plane_dispatch(plane, mesh, axis, split):
    """Guard for the meshless (replicated) epoch paths: a mass split
    needs the sharded refresh, and a *concrete* segmented plane cannot
    take any replicated path — the packed-row invariants would return
    wrong answers / corrupt the refresh silently (DESIGN.md §5.6).
    Tracer planes pass (inside an outer jit the caller keeps
    ``mesh``/``split`` consistent across the session)."""
    from repro.core import device_index as dix
    width = plane.keys.shape[1]
    sharded = (mesh is not None and axis in mesh.shape
               and width % mesh.shape[axis] == 0)
    if sharded:
        return
    if split == "mass":
        raise ValueError(
            "split='mass' requires the width-sharded path — pass mesh= "
            "with a plane width divisible by the axis size")
    if dix.plane_is_segmented(plane):
        raise ValueError(
            "segmented (mass-split) plane on the replicated epoch path "
            "— pass mesh= (a split='lanes' refresh repacks it) or "
            "rebuild with from_state_device before meshless serving")


def _place(st: SplayState, plane, mesh, axis):
    """The ``(state, plane, mesh)`` an epoch runs on.  An explicit
    ``mesh`` wins; ``None`` means the plane's own width-sharded mesh
    (``sharding.plane_width_mesh``).  A concrete plane on no such mesh
    and wider than ``splay_search.MAX_DESCENT_WIDTH`` — the widest plane
    one device's descent compiles — is laid out width-sharded
    (``sharding.shard_index_plane``) over the ``(1, S)`` mesh of the
    fewest local devices whose blocks fit (``sharding.width_mesh``), the
    state replicated on the same mesh; the epochs keep that layout, so
    this happens once.  A plane that fits stays where it is, whatever
    the device count.  Raises ``ValueError`` when a device would have to
    descend a block wider than the limit."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.kernels import splay_search as ssk
    from repro.parallel import sharding as shd
    width = plane.keys.shape[1]
    limit = ssk.MAX_DESCENT_WIDTH
    if mesh is None:
        mesh = shd.plane_width_mesh(plane, axis)
    if (mesh is None and width > limit
            and not isinstance(plane.keys, jax.core.Tracer)):
        mesh = shd.width_mesh(width, limit, axis)
        plane = shd.shard_index_plane(plane, mesh, axis)
        st = jax.device_put(st, NamedSharding(mesh, P()))
    shards = (mesh.shape[axis] if mesh is not None and axis in mesh.shape
              and width % mesh.shape[axis] == 0 else 1)
    if width // shards > limit:
        raise ValueError(
            f"a {width}-lane plane in {shards} block(s) leaves "
            f"{width // shards} lanes to one device's descent, more than "
            f"the {limit} it compiles for: pass a mesh with more devices "
            f"on the '{axis}' axis")
    return st, plane, mesh


def _check_route_args(route_capacity, route_slack):
    """Host-side guard for the routed exchange's sizing knobs, applied
    even on meshless runs (where they are inert) so nonsense never jits
    a cell it would silently misuse on the next, sharded, call."""
    if route_capacity is not None and int(route_capacity) < 1:
        raise ValueError(
            f"route_capacity must be >= 1, got {route_capacity}")
    if route_slack is not None and route_slack < 1.0:
        raise ValueError(
            f"route_slack must be >= 1.0, got {route_slack} "
            "(sub-1 slack guarantees spill on a balanced batch)")


@functools.partial(jax.jit, static_argnames=("aggregate", "max_new",
                                             "mesh", "axis",
                                             "plane_search", "split",
                                             "route_capacity",
                                             "route_slack", "ordered",
                                             "routed"))
def _run_epoch(st: SplayState, plane, kinds, keys, upd_mask,
               aggregate: bool = False, max_new: int = None,
               rebuild=False, mesh=None, axis: str = "model",
               plane_search: bool = False, split: str = "lanes",
               route_capacity: int = None, route_slack: float = None,
               ordered: bool = False, routed: bool = True):
    """One serving epoch entirely on device: apply a batch of operations
    (contains/insert/delete via :func:`run_ops`; ``aggregate=True`` runs
    the flat-combined contains fold of :func:`run_contains_batch`
    instead, ignoring ``kinds``), then refresh the device-resident index
    plane.  The level arrays never leave the accelerator — no
    ``to_numpy``, no host argsort, stable shapes across epochs.

    ``max_new`` bounds the refresh's new-key extraction (default: the
    batch size, which one epoch's inserts cannot exceed; engines that
    refresh less often than they batch pass their own bound).
    ``rebuild`` (traced bool) routes the plane through a full
    ``from_state_device`` rebuild instead of the incremental refresh —
    the overflow recovery path (DESIGN.md §5.4).

    Sharded serving (DESIGN.md §5.5–§5.6): ``mesh`` (static, hashable)
    turns the epoch's plane work sharded end-to-end — the refresh runs
    as ``device_index.refresh_device_sharded`` and, with
    ``plane_search``, the batch's membership answers come from the
    *routed* sharded search over the carried plane (the all_to_all
    query exchange; per-shard search compute O(B/S)) — no replicated
    ``[L, W]`` rectangle is materialized at any point.  Pass a plane
    laid out by ``sharding.shard_index_plane``; the epoch's plane
    output keeps that layout (both refresh branches are constrained to
    it).  An indivisible ``width % S`` silently degrades to the
    replicated paths (same values).  ``split`` (static,
    ``"lanes"``/``"mass"``) is the sharded refresh's boundary rule —
    ``"mass"`` re-splits the shard boundaries every epoch at the hit-
    counter mass quantiles, keeping the routed exchange's per-shard
    occupancy near B/S under skew (the full-rebuild recovery branch
    always emits the packed layout; the next incremental refresh
    re-splits it).  ``route_capacity``/``route_slack`` (static) size
    the exchange's per-shard receive block
    (``kernels.splay_search.route_capacity`` by default); queries past
    it spill to the masked full-batch trace — answers stay exact, the
    epoch just pays the replicated-trace cost for that batch.  In the
    host wrappers ``run_epoch``/``run_serving``, ``mesh=None`` means the
    plane's own mesh, and a plane wider than
    ``kernels.splay_search.MAX_DESCENT_WIDTH`` is laid out width-sharded
    over the local devices on the first call (``_place``); with a mesh
    the state and the batch stay replicated, so the fold runs whole on
    every device.  ``route_queries``/``route_spilled`` count the lanes
    the routed exchange sent and spilled.

    ``plane_search`` (static; requires ``aggregate=True`` — the whole
    batch must be read-only: ``OP_CONTAINS`` lanes, plus
    ``OP_PRED``/``OP_RANGE`` lanes when ``ordered``) answers
    ``results``/``path_len`` from the carried plane instead of the
    state walk: ``results`` is the plane's membership verdict and
    ``path_len`` is ``level_found`` (the search-depth analogue of the
    walk length; same adaptivity signal, different unit).  The plane
    entering the epoch is the membership snapshot the state-walk
    answers are computed against, so the verdicts are bit-identical —
    *except* while the previous epoch overflowed (the plane is stale by
    exactly the dropped keys until the scheduled rebuild lands;
    ``run_serving``'s state machine bounds that to one epoch).  The
    rebalance fold still runs either way — hit counting is what adapts
    the structure, with the hit weight restricted to the
    ``OP_CONTAINS`` lanes (ordered queries are pure reads and never
    splay, matching the :func:`run_ops` branches).

    ``ordered`` (static; DESIGN.md §5.10) grows the ``plane_search``
    answers to the ordered op codes: ``OP_PRED`` lanes answer the
    predecessor *key* (``NEG_INF_32`` when none) and ``OP_RANGE`` lanes
    the prefix-range *count*, both derived from the same descent's
    bottom-row rank (the pred key costs one extra
    ``kernels.ops.splay_select`` gather — sharded: one [2, B] psum —
    which is why the flag is opt-in; ``ordered=False`` is bit-for-bit
    the membership-only epoch).  Off the ``plane_search`` path the op
    codes need no flag: :func:`run_ops` answers them from the state
    walk natively.  Bit-identical across all three paths.

    Returns ``(state, plane, results[B] int32, path_len[B], overflow,
    spill, occupancy)`` — ``results`` carries per-op answers: 0/1
    verdicts for contains/insert/delete lanes, predecessor keys /
    prefix-range counts for ordered lanes (see the op-kind constants).
    ``overflow`` (int32 scalar) counts alive
    keys the refreshed plane could not represent this epoch: inserts
    beyond ``max_new`` plus alive keys beyond the plane width.  Nonzero
    overflow means the plane is stale until the caller (or
    :func:`run_serving`'s carry) triggers the rebuild; a rebuild at the
    same shape cannot fix ``size > width`` — that persists in
    ``overflow`` as the host-visible signal to re-plan with a wider
    plane.  ``spill`` (int32 scalar) counts the batch's queries
    answered through the routed exchange's spill path this epoch (0
    except on the sharded ``plane_search`` path) — persistent nonzero
    spill is the signal to raise ``route_capacity`` or switch
    ``split="mass"``.  ``occupancy`` (int32 ``[S]``) is the routed
    exchange's per-shard live-query counts (``RouteStats.occupancy``;
    sums to B) on that same path, and a single-element zero vector on
    every other path — the balance signal the routing controller
    (``core.route_controller``, DESIGN.md §5.7) feeds on.

    ``routed`` (static, default True) selects the sharded
    ``plane_search`` execution mode: ``False`` answers the batch
    through the *masked replicated trace* instead of the routed
    all_to_all exchange — bit-identical verdicts, no routing, no
    spill.  This is rung 1 of the §5.11 degradation ladder: the
    serving loop drops to it after an audit failure or shard loss
    because the masked trace has no per-shard capacity to overrun
    while the plane is being repaired.  Inert off the sharded
    ``plane_search`` path."""
    from repro.core import device_index as dix
    n_levels, width = plane.keys.shape
    sharded = (mesh is not None and axis in mesh.shape
               and width % mesh.shape[axis] == 0)
    if sharded:
        # the state and the batch stay whole on every device: the fold
        # runs as on one chip, with no collective inside it
        from jax.sharding import NamedSharding, PartitionSpec as P
        st, kinds, keys, upd_mask = jax.lax.with_sharding_constraint(
            (st, kinds, keys, upd_mask), NamedSharding(mesh, P()))
    spill = jnp.zeros((), jnp.int32)
    occupancy = jnp.zeros((1,), jnp.int32)
    if plane_search:
        if not aggregate:
            raise ValueError("plane_search answers the batch from the "
                             "index plane — read-only batches only "
                             "(contains / ordered queries), i.e. "
                             "aggregate=True")
        from repro.kernels import ops as kops
        from repro.kernels import splay_search as ssk
        with jax.named_scope("splay.descent"):
            if sharded:
                res, rank, plen, rstats = kops.splay_search_sharded(
                    plane, keys, mesh=mesh, axis=axis, routed=routed,
                    capacity=route_capacity,
                    slack=(route_slack if route_slack is not None
                           else ssk.DEFAULT_ROUTE_SLACK),
                    return_stats=True)
                spill = rstats.spill
                occupancy = rstats.occupancy
            else:
                res, rank, plen = kops.splay_search(plane, keys,
                                                    sharded=False)
        upd_eff = upd_mask
        if ordered:
            # ordered lanes: answers off the same descent's bottom-row
            # rank (DESIGN.md §5.10); pure reads, so they carry no hit
            # weight into the rebalance fold (matches run_ops exactly)
            with jax.named_scope("splay.select"):
                pred_keys = kops.splay_select(
                    plane, rank, sharded=sharded,
                    mesh=(mesh if sharded else None), axis=axis)
            res = jnp.where(
                kinds == OP_PRED,
                jnp.where(rank >= 0, pred_keys, jnp.int32(NEG_INF_32)),
                jnp.where(kinds == OP_RANGE, rank + 1,
                          res.astype(jnp.int32)))
            upd_eff = upd_mask & (kinds == OP_CONTAINS)
        with jax.named_scope("splay.fold"):
            st, _, _ = run_contains_batch(st, keys, upd_eff, aggregate=True)
    elif aggregate:
        with jax.named_scope("splay.fold"):
            st, res, plen = run_contains_batch(st, keys, upd_mask,
                                               aggregate=True)
    else:
        with jax.named_scope("splay.fold"):
            st, res, plen = run_ops(st, kinds, keys, upd_mask)
    res = res.astype(jnp.int32)
    if max_new is None:
        # an epoch cannot insert more keys than it has ops: bound the
        # refresh's new-key extraction by the batch size
        max_new = keys.shape[0]

    def full_rebuild(_):
        with jax.named_scope("splay.plane_rebuild"):
            pl = dix.from_state_device(st, n_levels=n_levels, width=width)
            # a full build drops nothing the plane can hold; only alive
            # counts beyond the (static) width remain unrepresentable
            ovf = jnp.maximum(st.size - width, 0).astype(jnp.int32)
            return pl, ovf

    def incremental(_):
        with jax.named_scope("splay.refresh"):
            if sharded:
                return dix.refresh_device_sharded(
                    st, plane, max_new=max_new, mesh=mesh, axis=axis,
                    split=split)
            return dix.refresh_device(st, plane, max_new=max_new,
                                      return_overflow=True)

    plane_in = plane
    plane, overflow = jax.lax.cond(rebuild, full_rebuild, incremental,
                                   operand=None)
    # every refresh recomputes all rows; count those it actually changed
    changed = jnp.sum(jnp.any(plane.keys != plane_in.keys, axis=1)
                      | (plane.widths != plane_in.widths))
    routed_lanes = keys.shape[0] if (plane_search and sharded
                                     and routed) else 0
    st = _count(st, epochs=1, plane_rebuilds=rebuild,
                plane_rows_rebuilt=n_levels, plane_rows_changed=changed,
                route_queries=routed_lanes, route_spilled=spill)
    if sharded:
        # keep the carry in the width-sharded layout whichever branch
        # produced it (the rebuild branch is replicated math), and the
        # state replicated
        from repro.parallel import sharding as shd
        specs = shd.index_plane_specs(type(plane), axis)
        plane = type(plane)(*(
            jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s))
            for x, s in zip(plane, specs)))
        st = jax.lax.with_sharding_constraint(st, NamedSharding(mesh, P()))
    return st, plane, res, plen, overflow, spill, occupancy


def run_epoch(st: SplayState, plane, kinds, keys, upd_mask,
              aggregate: bool = False, max_new: int = None,
              rebuild=False, mesh=None, axis: str = "model",
              plane_search: bool = False, split: str = "lanes",
              route_capacity: int = None, route_slack: float = None,
              ordered: bool = False, routed: bool = True):
    span = dict(epochs=1, batch=np.shape(keys)[0])
    with jax.profiler.TraceAnnotation("splay.serve.guard", **span):
        st, plane, mesh = _place(st, plane, mesh, axis)
        _check_plane_dispatch(plane, mesh, axis, split)
        _check_route_args(route_capacity, route_slack)
    with jax.profiler.TraceAnnotation("splay.serve.dispatch", **span):
        return _run_epoch(st, plane, kinds, keys, upd_mask,
                          aggregate=aggregate, max_new=max_new,
                          rebuild=rebuild, mesh=mesh, axis=axis,
                          plane_search=plane_search, split=split,
                          route_capacity=route_capacity,
                          route_slack=route_slack, ordered=ordered,
                          routed=routed)


run_epoch.__doc__ = _run_epoch.__doc__


@functools.partial(jax.jit, static_argnames=("aggregate", "max_new",
                                             "mesh", "axis",
                                             "plane_search", "split",
                                             "route_capacity",
                                             "route_slack", "ordered",
                                             "routed"))
def _run_serving(st: SplayState, plane, kinds, keys, upd_mask,
                 aggregate: bool = False, max_new: int = None,
                 mesh=None, axis: str = "model",
                 plane_search: bool = False, split: str = "lanes",
                 route_capacity: int = None, route_slack: float = None,
                 ordered: bool = False, routed: bool = True):
    """The jitted epoch *loop*: scan :func:`run_epoch` over ``[E, B]``
    op batches, threading (state, plane, rebuild-pending) through the
    carry — E epochs of search + update + index refresh with zero host
    round-trips of index-plane data.

    ``mesh``/``axis``/``plane_search``/``split``/``route_capacity``/
    ``route_slack``/``ordered`` thread straight into :func:`run_epoch`
    (``ordered`` makes the plane-search epochs answer the
    ``OP_PRED``/``OP_RANGE`` lanes — ordered reads interleaving with
    the serving stream, DESIGN.md §5.10; results are int32 per-op
    answers either way) (DESIGN.md
    §5.5–§5.6): with a mesh and a ``shard_index_plane``-laid-out
    plane, every epoch's refresh runs width-sharded and (with
    ``plane_search``) the membership answers come from the *routed*
    sharded search — the serving loop never materializes a replicated
    ``[L, W]`` rectangle, which is what lets the plane outgrow one
    device's memory *in serving*, not just during refresh.  With
    ``split="mass"`` every incremental refresh re-splits the shard
    boundaries at the hit-counter mass quantiles, so the exchange's
    occupancy tracks the workload as it drifts (a rebuild-recovery
    epoch emits the packed layout; the next refresh re-splits).

    ``mesh=None`` in ``run_serving`` means the plane's own mesh, as in
    :func:`run_epoch` (a plane too wide for one device's descent is
    laid out width-sharded on the first call).

    Overflow state machine (DESIGN.md §5.4): an epoch whose refresh
    reports nonzero overflow arms a pending flag, and the *next*
    epoch's refresh is a full ``from_state_device`` rebuild, folding the
    dropped inserts back in instead of silently losing them.  The alive
    count *entering* the near-full zone (within one batch of the plane
    width) arms it too — but edge-triggered, once per crossing, so
    steady-state serving at high occupancy keeps the cheap incremental
    refresh instead of paying a full rebuild every epoch.  Returns
    ``(state, plane, results[E, B], path_len[E, B], overflow[E],
    spill[E], occupancy[E, S])``; ``overflow[e] > 0`` flags the stale
    epochs (staleness lasts one epoch; persistent nonzero overflow
    means the alive count exceeds the plane width — rebuild wider at
    the host level), ``spill[e]`` counts the routed-exchange spills per
    epoch (persistently nonzero spill under ``split="lanes"`` is the
    signal to switch to ``"mass"`` or raise ``route_capacity``), and
    ``occupancy[e]`` is that epoch's per-shard live-query counts
    (``[E, 1]`` zeros off the sharded ``plane_search`` path) — together
    the per-epoch feedback the routing controller consumes between
    calls (``core.route_controller``, DESIGN.md §5.7)."""
    width = plane.keys.shape[1]
    B = keys.shape[1]

    def step(carry, ep):
        s, pl, pending, pressed = carry
        kd, ks, up = ep
        s, pl, res, plen, ovf, spl, occ = _run_epoch(
            s, pl, kd, ks, up, aggregate=aggregate, max_new=max_new,
            rebuild=pending, mesh=mesh, axis=axis,
            plane_search=plane_search, split=split,
            route_capacity=route_capacity, route_slack=route_slack,
            ordered=ordered, routed=routed)
        pressure = s.size + B > width
        pending = (ovf > 0) | (pressure & ~pressed)
        return (s, pl, pending, pressure), (res, plen, ovf, spl, occ)

    (st, plane, _, _), (res, plen, ovf, spl, occ) = jax.lax.scan(
        step, (st, plane, jnp.asarray(False), jnp.asarray(False)),
        (kinds, keys, upd_mask))
    return st, plane, res, plen, ovf, spl, occ


def run_serving(st: SplayState, plane, kinds, keys, upd_mask,
                aggregate: bool = False, max_new: int = None,
                mesh=None, axis: str = "model",
                plane_search: bool = False, split: str = "lanes",
                route_capacity: int = None, route_slack: float = None,
                ordered: bool = False, routed: bool = True):
    n_epochs, batch = np.shape(keys)
    span = dict(epochs=n_epochs, batch=batch)
    with jax.profiler.TraceAnnotation("splay.serve.guard", **span):
        st, plane, mesh = _place(st, plane, mesh, axis)
        _check_plane_dispatch(plane, mesh, axis, split)
        _check_route_args(route_capacity, route_slack)
    with jax.profiler.TraceAnnotation("splay.serve.dispatch", **span):
        return _run_serving(st, plane, kinds, keys, upd_mask,
                            aggregate=aggregate, max_new=max_new,
                            mesh=mesh, axis=axis,
                            plane_search=plane_search, split=split,
                            route_capacity=route_capacity,
                            route_slack=route_slack, ordered=ordered,
                            routed=routed)


run_serving.__doc__ = _run_serving.__doc__


# ---------------------------------------------------------------------------
# host-side introspection (tests / stats)
# ---------------------------------------------------------------------------

def to_numpy(st: SplayState) -> dict:
    return {f: np.asarray(getattr(st, f)) for f in st._fields}


def heights(st: SplayState) -> dict:
    """key -> relative height, walking the bottom list on host."""
    s = to_numpy(st)
    out = {}
    zl = int(s["zl"])
    L = st.max_level

    def eff_next(i, h):
        lvl = max(h, int(s["nzero"][i]))
        return int(s["nxt"][lvl, i])

    i = eff_next(HEAD, zl)
    while i != TAIL and i >= 0:
        out[int(s["key"][i])] = int(s["top"][i]) - zl
        i = eff_next(i, zl)
    return out

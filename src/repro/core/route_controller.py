"""Closed-loop routing controller for the sharded serving loop
(DESIGN.md §5.7).

PRs 4–5 gave the width-sharded search a *routed* query exchange whose
per-shard receive block is a static guess (``route_capacity =
ceil(q/S)·slack``) and whose mass-weighted re-split only fires when a
caller happens to pass ``split="mass"`` — under a drifting access
distribution the exchange silently degrades into spill-path fallbacks.
This module closes the loop on the feedback ``run_epoch`` already
returns (``spill``, per-shard ``occupancy``): a tiny host-level
controller that, once per epoch,

(a) grows/shrinks ``route_slack`` along a *quantized ladder* from an
    EWMA of the observed peak occupancy — quantized because every
    distinct slack value is a distinct jit cell, so the controller must
    pick from a handful of pre-chosen rungs rather than re-trace per
    epoch; a wide hysteresis band (grow above ``high_water·capacity``,
    shrink only below ``low_water·capacity-at-the-lower-rung``) means
    steady state never oscillates between rungs;
(b) escalates the refresh to the mass-weighted boundary re-split
    (``split="mass"``) when the spill rate or the occupancy Gini
    crosses a threshold, and
(c) de-escalates back to the cheap equal-lane refresh once balance
    holds calm long enough — with a doubling backoff so a workload that
    keeps re-skewing settles into ``"mass"`` instead of flapping; a
    re-split that *stays* imbalanced past ``rebuild_patience`` epochs
    (stale hit counters after a hot-set migration) escalates one rung
    further to a full plane rebuild.

Everything here is plain host math over concrete stats — the actuators
(``route_slack``, ``split``, ``rebuild``) are static jit arguments, so
the controller *is* the host/device boundary: devices report, the host
steers the next epoch's cell.  The escape hatch is structural: the
ladder tops out at ``slack = S``, where ``route_capacity`` clamps at
``q`` and spill becomes impossible, so recovery from any transition is
bounded by the ladder length (≤ ``len(slack_ladder)`` epochs), not by
how adversarial the drift is.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import numpy as np

from repro.kernels.splay_search import DEFAULT_ROUTE_SLACK, route_capacity

__all__ = [
    "ControllerConfig", "ControllerState", "default_slack_ladder",
    "init_controller", "controller_step", "controller_to_dict",
    "controller_from_dict", "overflow_machine_step",
    "run_serving_controlled", "max_share", "routing_gini",
]


def overflow_machine_step(overflow: int, size: int, batch: int,
                          width: int, pressed: bool
                          ) -> Tuple[bool, bool]:
    """One host-side step of ``run_serving``'s overflow state machine
    (DESIGN.md §5.4): given this epoch's refresh ``overflow``, the
    post-epoch alive ``size``, the epoch ``batch`` size, the plane
    ``width``, and whether the near-full pressure flag was already set
    (``pressed``), return ``(pending, pressed')`` — whether the *next*
    epoch must take the full-rebuild branch, and the updated
    edge-trigger latch.  Shared by every host-stepped epoch loop
    (:func:`run_serving_controlled`, the device-indexed
    ``serve.kv_cache.PagedKVPool``) so their rebuild scheduling is
    bit-identical to the device-side scan in ``splaylist.run_serving``."""
    pressure = int(size) + int(batch) > int(width)
    pending = int(overflow) > 0 or (pressure and not pressed)
    return pending, pressure


# ---------------------------------------------------------------------------
# balance statistics (shared with benchmarks/sharded_search_probe.py)
# ---------------------------------------------------------------------------

def max_share(occupancy) -> float:
    """Largest shard's fraction of the live queries (1/S = balanced,
    1.0 = single-owner batch)."""
    occ = np.asarray(occupancy, np.float64)
    tot = occ.sum()
    return float(occ.max() / tot) if tot > 0 else 0.0


def routing_gini(occupancy) -> float:
    """Gini coefficient of the per-shard occupancy vector (0 =
    perfectly balanced, ->1 = all load on one shard)."""
    x = np.sort(np.asarray(occupancy, np.float64))
    n = x.size
    tot = x.sum()
    if tot == 0 or n < 2:
        return 0.0
    return float((2 * np.arange(1, n + 1) - n - 1).dot(x) / (n * tot))


# ---------------------------------------------------------------------------
# configuration / state
# ---------------------------------------------------------------------------

def default_slack_ladder(n_shards: int,
                         base: float = DEFAULT_ROUTE_SLACK,
                         growth: float = 1.5) -> Tuple[float, ...]:
    """The quantized slack rungs: ``1.0, base, base·g, ...`` capped at
    ``n_shards`` (where capacity clamps at ``q`` and spill is
    structurally impossible).  Quantization is what bounds jit cells:
    the controller can only ever visit ``len(ladder)`` distinct
    ``route_slack`` values."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    top = float(n_shards)
    rungs = [1.0]
    s = base
    while s < top and len(rungs) < 16:
        if s > rungs[-1]:
            rungs.append(float(s))
        s *= growth
    if rungs[-1] < top:
        rungs.append(top)
    return tuple(rungs)


class ControllerConfig(NamedTuple):
    """Static gains/thresholds of the routing controller (DESIGN.md
    §5.7).  All comparisons are strict-inequality on the 'hot' side so
    a workload sitting exactly on a threshold does not actuate."""
    slack_ladder: Tuple[float, ...]   # quantized route_slack rungs
    ewma_alpha: float = 0.5           # weight of the newest peak occ.
    high_water: float = 0.85          # grow when ewma > hw·capacity
    low_water: float = 0.5            # shrink when ewma < lw·cap(lower)
    calm_epochs: int = 3              # calm streak before de-actuation
    spill_hi: float = 0.01            # spill rate that forces "mass"
    gini_hi: float = 0.25             # imbalance that forces "mass"
    gini_lo: float = 0.10             # balance that counts as calm
    rebuild_patience: int = 3         # bad-gini epochs in mass -> rebuild


class ControllerState(NamedTuple):
    """The per-epoch carry of the controller: actuators (``slack_idx``
    into the ladder, ``split``, ``force_rebuild``), the EWMA estimator,
    the hysteresis counters, and observability (last epoch's stats plus
    lifetime actuation counts — ``retraces`` is exactly the number of
    extra jit cells the controller has demanded)."""
    slack_idx: int                    # index into cfg.slack_ladder
    split: str = "lanes"              # refresh boundary rule for next ep
    force_rebuild: bool = False       # one-shot full-rebuild request
    ewma: float = -1.0                # EWMA of peak occupancy (-1 unset)
    calm: int = 0                     # consecutive calm epochs
    backoff: int = 1                  # calm streak needed to de-escalate
    mass_bad: int = 0                 # bad-gini epochs while in "mass"
    retraces: int = 0                 # slack rung changes (jit cells)
    escalations: int = 0              # lanes->mass transitions
    last_spill: int = 0
    last_share: float = 0.0
    last_gini: float = 0.0

    def slack_of(self, cfg: ControllerConfig) -> float:
        """The concrete ``route_slack`` this state's rung selects."""
        return cfg.slack_ladder[self.slack_idx]


def init_controller(n_shards: int, **overrides
                    ) -> Tuple[ControllerConfig, ControllerState]:
    """Build the default config for an ``n_shards``-way mesh and the
    initial state: ladder rung at ``DEFAULT_ROUTE_SLACK`` (the static
    baseline — controller-off and controller-on start identically),
    equal-lane refresh, estimator unset.  ``overrides`` replace
    individual :class:`ControllerConfig` fields."""
    ladder = overrides.pop("slack_ladder", None) or \
        default_slack_ladder(n_shards)
    cfg = ControllerConfig(slack_ladder=tuple(ladder), **overrides)
    start = min(range(len(cfg.slack_ladder)),
                key=lambda i: (abs(cfg.slack_ladder[i]
                                   - DEFAULT_ROUTE_SLACK), i))
    return cfg, ControllerState(slack_idx=start)


def controller_to_dict(cfg: ControllerConfig,
                       state: ControllerState) -> dict:
    """JSON-safe serialization of the whole controller (config +
    carry) for the §5.11 crash-consistent serving snapshot.  Every
    field is a plain int/float/str/bool/list, so the dict survives a
    ``json.dumps`` round-trip bit-identically — the restored
    controller continues the slack ladder, calm streaks, and doubling
    backoff exactly where the crashed one stopped (pinned by
    ``tests/test_route_controller.py``)."""
    c = cfg._asdict()
    c["slack_ladder"] = [float(s) for s in cfg.slack_ladder]
    s = state._asdict()
    s["force_rebuild"] = bool(state.force_rebuild)
    return {"config": c, "state": s}


def controller_from_dict(d: dict
                         ) -> Tuple[ControllerConfig, ControllerState]:
    """Inverse of :func:`controller_to_dict`."""
    c = dict(d["config"])
    c["slack_ladder"] = tuple(float(s) for s in c["slack_ladder"])
    cfg = ControllerConfig(**c)
    state = ControllerState(**d["state"])
    return cfg, state


# ---------------------------------------------------------------------------
# the control law
# ---------------------------------------------------------------------------

def controller_step(cfg: ControllerConfig, state: ControllerState,
                    spill: int, occupancy, nq: int) -> ControllerState:
    """One epoch of the control law: fold this epoch's ``(spill,
    occupancy)`` into the estimator and emit the actuators for the
    *next* epoch.  Pure host math — no jax, no tracing; safe to call
    with stats pulled from any of the ``run_epoch``/``run_serving``
    return tuples.

    Single-pseudo-shard occupancy (the meshless fallback's ``[1]``
    vector) is a no-op: there is nothing to balance, so the state only
    records the stats."""
    occ = np.asarray(occupancy)
    spill = int(spill)
    share = max_share(occ)
    gini = routing_gini(occ)
    if occ.size <= 1:                 # meshless: observe, never actuate
        return state._replace(force_rebuild=False, last_spill=spill,
                              last_share=share, last_gini=gini)

    n_shards = int(occ.size)
    peak = float(occ.max())
    a = cfg.ewma_alpha
    ewma = peak if state.ewma < 0 else a * peak + (1 - a) * state.ewma
    spill_rate = spill / max(nq, 1)
    idx = state.slack_idx
    split = state.split
    backoff = state.backoff
    retraces = state.retraces
    escalations = state.escalations
    capacity = route_capacity(nq, n_shards, cfg.slack_ladder[idx])

    calm_now = (spill == 0 and gini <= cfg.gini_lo
                and ewma <= cfg.high_water * capacity)
    calm = state.calm + 1 if calm_now else 0

    # (b) escalation: spill or imbalance past threshold -> mass re-split
    force_rebuild = False
    mass_bad = state.mass_bad
    if spill_rate > cfg.spill_hi or gini > cfg.gini_hi:
        if split == "lanes":
            split = "mass"
            escalations += 1
            mass_bad = 0
        elif gini > cfg.gini_hi:
            # mass is already on and the boundaries STILL don't balance
            # (stale hit counters after a migration): after
            # rebuild_patience such epochs, escalate to a full rebuild
            mass_bad += 1
            if mass_bad >= cfg.rebuild_patience:
                force_rebuild = True
                mass_bad = 0
    else:
        mass_bad = 0
        # (c) de-escalation: calm streak long enough -> back to lanes,
        # and the next de-escalation needs twice the streak (flapping
        # workloads settle into mass instead of thrashing re-splits)
        if split == "mass" and calm >= max(cfg.calm_epochs, backoff):
            split = "lanes"
            backoff *= 2
            calm = 0

    # (a) slack ladder: grow on pressure, shrink only deep inside the
    # hysteresis band (low_water of the *lower* rung's capacity, so a
    # shrink can never trigger an immediate re-grow)
    if spill > 0 or ewma > cfg.high_water * capacity:
        if idx < len(cfg.slack_ladder) - 1:
            idx += 1
            retraces += 1
            calm = 0
    elif (idx > 0 and calm >= cfg.calm_epochs and spill == 0
          and ewma < cfg.low_water * route_capacity(
              nq, n_shards, cfg.slack_ladder[idx - 1])):
        idx -= 1
        retraces += 1
        calm = 0

    return ControllerState(
        slack_idx=idx, split=split, force_rebuild=force_rebuild,
        ewma=ewma, calm=calm, backoff=backoff, mass_bad=mass_bad,
        retraces=retraces, escalations=escalations, last_spill=spill,
        last_share=share, last_gini=gini)


# ---------------------------------------------------------------------------
# the controlled serving loop
# ---------------------------------------------------------------------------

def run_serving_controlled(st, plane, kinds, keys, upd_mask,
                           aggregate: bool = False, max_new: int = None,
                           mesh=None, axis: str = "model",
                           plane_search: bool = False,
                           cfg: ControllerConfig = None,
                           state: ControllerState = None):
    """The closed-loop face of ``splaylist.run_serving``: the same
    ``[E, B]`` epoch loop, but stepped from the host one epoch at a
    time so the controller can re-pick ``route_slack``/``split``/
    ``rebuild`` between epochs (they are static jit arguments — a
    device-side loop cannot change them; this loop is exactly the
    host/device cut DESIGN.md §5.7 draws).

    Mirrors ``run_serving``'s overflow state machine host-side (pending
    rebuild after an overflow epoch, edge-triggered near-full
    pressure), OR-ing in the controller's ``force_rebuild`` rung.
    Answers are bit-identical to the uncontrolled loop on contains-only
    batches: the actuators only ever change *where* queries are
    answered (lane boundaries, spill path, capacity), never what they
    answer (§5.6's exactness contract).

    Returns ``(st, plane, results[E, B], path_len[E, B],
    overflow[E], spill[E], occupancy[E, S], states)`` — the first seven
    exactly like ``run_serving`` (occupancy ``[E, 1]`` when meshless),
    plus the per-epoch :class:`ControllerState` trajectory (``states[e]``
    is the state *after* folding epoch ``e``; ``states[-1]`` seeds the
    next call).  On a meshless/indivisible run the controller observes
    but never actuates, so the loop degrades to exactly the replicated
    ``run_serving``."""
    from repro.core import splaylist as sx

    E, B = keys.shape
    width = plane.keys.shape[1]
    sharded = (mesh is not None and axis in mesh.shape
               and width % mesh.shape[axis] == 0)
    n_shards = int(mesh.shape[axis]) if sharded else 1
    if cfg is None:
        cfg, st0 = init_controller(n_shards)
        state = state if state is not None else st0
    elif state is None:
        _, state = init_controller(n_shards, slack_ladder=cfg.slack_ladder)
        state = state._replace(slack_idx=min(state.slack_idx,
                                             len(cfg.slack_ladder) - 1))

    res, plen, ovf, spl, occ, states = [], [], [], [], [], []
    pending, pressed = False, False
    for e in range(E):
        split = state.split if sharded else "lanes"
        out = sx.run_epoch(
            st, plane, kinds[e], keys[e], upd_mask[e],
            aggregate=aggregate, max_new=max_new,
            rebuild=bool(pending or state.force_rebuild),
            mesh=mesh, axis=axis, plane_search=plane_search, split=split,
            route_slack=state.slack_of(cfg) if sharded else None)
        st, plane, r, p, ov, sp, oc = out
        res.append(r); plen.append(p); ovf.append(ov)
        spl.append(sp); occ.append(oc)
        with jax.profiler.TraceAnnotation("splay.controller.step",
                                          epochs=1, batch=B):
            # host mirror of run_serving's overflow machine (§5.4)
            pending, pressed = overflow_machine_step(
                int(ov), int(st.size), B, width, pressed)
            state = controller_step(cfg, state, int(sp), np.asarray(oc),
                                    B)
        states.append(state)
    stack = lambda xs: np.stack([np.asarray(x) for x in xs])
    return (st, plane, stack(res), stack(plen), stack(ovf),
            stack(spl), stack(occ), states)

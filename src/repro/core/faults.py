"""Deterministic chaos: seeded fault plans for the serving stack.

The recovery guarantees in DESIGN.md §5.11 ("every injected corruption
detected within one audit epoch, zero wrong verdicts, bounded recovery")
are only testable if the faults themselves are reproducible.  A
``FaultPlan`` is a seeded schedule of ``FaultEvent``s keyed by the
pool's *lookup-epoch* counter; ``PagedKVPool`` consults it between the
mutation flush and the lookup answer — exactly the crash window the
snapshot/restore path must survive — and applies each event once.

Four fault families (the chaos probe gates all of them):

``FAULT_BITFLIP``     flip ``arg`` random bits in live lanes of the
                      device plane (keys / heights), leaving the state
                      untouched — the plane fsck must catch the
                      divergence.
``FAULT_SHARD_LOSS``  shrink the serving mesh to ``arg`` surviving
                      shards mid-serving (S -> S'); the pool rebuilds
                      the plane from the authoritative state via
                      ``train.elastic.remesh`` + re-layout.
``FAULT_TELEMETRY``   starve the routing controller of its
                      spill/occupancy feedback for ``arg`` epochs
                      (zero spill, stale occupancy) — serving must
                      stay correct, only adaptivity pauses.
``FAULT_CRASH``       raise ``InjectedCrash`` between flush and
                      lookup — the mid-epoch kill the crash-consistent
                      snapshot replays across.

Determinism: every event draws from ``numpy.random.default_rng``
seeded by ``(plan.seed, epoch, event index)``, so re-running a plan
against the same trace injects bit-identical corruption.
"""

from __future__ import annotations

from typing import List, NamedTuple, Sequence

import numpy as np

FAULT_BITFLIP = "bitflip"
FAULT_SHARD_LOSS = "shard_loss"
FAULT_TELEMETRY = "telemetry"
FAULT_CRASH = "crash"

FAULT_FAMILIES = (FAULT_BITFLIP, FAULT_SHARD_LOSS, FAULT_TELEMETRY,
                  FAULT_CRASH)

# plane fields a bit-flip may target (the [L, W] key rectangle the
# descent reads + the bottom height vector; widths/local_* corruption
# is covered by flipping the arrays they must agree with)
BITFLIP_FIELDS = ("keys", "heights")


class InjectedFault(RuntimeError):
    """Base class for faults a ``FaultPlan`` raises on purpose; the
    engine treats these as transient and retries with backoff."""


class InjectedCrash(InjectedFault):
    """Mid-epoch kill between mutation flush and lookup answer."""


class FaultEvent(NamedTuple):
    """One scheduled fault: fires when the pool's lookup-epoch counter
    reaches ``epoch``.  ``arg`` is family-specific: bit-flip count,
    surviving shard count, telemetry-blackout epochs; unused for
    ``crash``."""
    epoch: int
    family: str
    arg: int = 1


class FaultPlan:
    """A deterministic, seeded schedule of fault events.

    >>> plan = FaultPlan(seed=7, events=[
    ...     FaultEvent(3, FAULT_BITFLIP, 2),
    ...     FaultEvent(6, FAULT_TELEMETRY, 2),
    ...     FaultEvent(9, FAULT_SHARD_LOSS, 2),
    ...     FaultEvent(12, FAULT_CRASH)])

    ``events_at(epoch)`` returns that epoch's events in schedule
    order; ``rng_for(event)`` hands each a private deterministic
    generator.  Plans are immutable and replayable."""

    def __init__(self, seed: int = 0,
                 events: Sequence[FaultEvent] = ()):
        self.seed = int(seed)
        evs = []
        for ev in events:
            ev = FaultEvent(int(ev[0]), str(ev[1]), int(ev[2])
                            if len(ev) > 2 else 1)
            if ev.family not in FAULT_FAMILIES:
                raise ValueError(f"unknown fault family {ev.family!r} "
                                 f"(choose from {FAULT_FAMILIES})")
            if ev.epoch < 0:
                raise ValueError(f"fault epoch must be >= 0: {ev}")
            evs.append(ev)
        self.events: List[FaultEvent] = sorted(
            evs, key=lambda e: e.epoch)

    def events_at(self, epoch: int) -> List[FaultEvent]:
        return [e for e in self.events if e.epoch == int(epoch)]

    def rng_for(self, event: FaultEvent) -> np.random.Generator:
        # resolve by identity first: duplicate events (equal tuples,
        # e.g. two bitflips at one epoch) must still draw distinct
        # streams, which value-based .index() would collapse
        for i, e in enumerate(self.events):
            if e is event:
                return np.random.default_rng(
                    [self.seed, event.epoch, i])
        idx = self.events.index(event)
        return np.random.default_rng([self.seed, event.epoch, idx])

    def families(self) -> List[str]:
        return sorted({e.family for e in self.events})

    def __repr__(self) -> str:
        return (f"FaultPlan(seed={self.seed}, "
                f"events={len(self.events)})")


def flip_plane_bits(plane, rng: np.random.Generator, n_flips: int = 1,
                    fields: Sequence[str] = BITFLIP_FIELDS):
    """Return ``(corrupted_plane, records)``: ``n_flips`` single-bit
    XORs into *specified* (kernel-read) lanes of the plane, each
    logged as ``(field, index_tuple, bit)``.

    Targets live lanes only (pad-lane entries of ``slots`` are
    documented unspecified — flipping them must not count as
    corruption), and low-order bits (0..15) so a flipped key
    stays in-range rather than teleporting to a sentinel.  The
    corrupted arrays are re-placed with the original array's sharding,
    so sharded planes stay sharded."""
    import jax
    import numpy as np_

    from repro.core import device_index as dix

    plane_np = {f: np_.array(np_.asarray(getattr(plane, f)))
                for f in fields}
    keys = np_.asarray(plane.keys)
    L, W = keys.shape
    live = keys != dix.PAD_KEY
    records = []
    for _ in range(int(n_flips)):
        field = fields[int(rng.integers(len(fields)))]
        arr = plane_np[field]
        if arr.ndim == 2:
            rows, cols = np_.nonzero(live)
            if rows.size == 0:
                continue
            pick = int(rng.integers(rows.size))
            idx = (int(rows[pick]), int(cols[pick]))
        else:
            cols = np_.nonzero(live[L - 1])[0]
            if field == "heights":
                # a lane saturated above the top row keeps identical
                # membership under small flips — target unsaturated
                # lanes so the audit provably sees the corruption
                h = np_.asarray(plane.heights)
                unsat = cols[h[cols] < L - 1]
                cols = unsat if unsat.size else cols
            if cols.size == 0:
                continue
            idx = (int(cols[int(rng.integers(cols.size))]),)
        bit = int(rng.integers(16))
        arr[idx] ^= np_.array(1 << bit, arr.dtype)
        records.append((field, idx, bit))
    repl = {}
    for f, arr in plane_np.items():
        orig = getattr(plane, f)
        repl[f] = jax.device_put(arr, orig.sharding)
    return plane._replace(**repl), records


def mangle_telemetry(spill, occupancy, last_occupancy=None):
    """The controller-facing view of a telemetry blackout: spill
    reads zero, occupancy freezes at the last delivered sample (or
    zeros when none) — loss and delay in one shape.  Pure function so
    the pool (and tests) share one definition."""
    occ = np.asarray(occupancy)
    stale = (np.asarray(last_occupancy)
             if last_occupancy is not None else np.zeros_like(occ))
    return 0, stale


__all__ = [
    "FAULT_BITFLIP", "FAULT_SHARD_LOSS", "FAULT_TELEMETRY",
    "FAULT_CRASH", "FAULT_FAMILIES", "BITFLIP_FIELDS",
    "InjectedFault", "InjectedCrash", "FaultEvent", "FaultPlan",
    "flip_plane_bits", "mangle_telemetry",
]

"""Deterministic fault injection (DESIGN.md §5.11): plan validation
and ordering, per-event rng determinism, bit-flip record/replay
exactness, and the telemetry-blackout view.  The end-to-end chaos
loops (device pool vs host mirror under injected faults) run in
``benchmarks/chaos_probe.py --parity``; here are the pure host
contracts those loops rely on."""

import numpy as np
import pytest

from repro.core import device_index as dix
from repro.core import faults as fl

from conftest import seed_splay_state as _seed_state  # noqa: E402

POOL = np.arange(0, 80, 2, dtype=np.int32)


def _plane():
    st = _seed_state(POOL, cap=66, ml=8)
    return dix.from_state_device(st, n_levels=8, width=64)


def test_plan_validates_and_sorts():
    plan = fl.FaultPlan(seed=3, events=[
        fl.FaultEvent(9, fl.FAULT_CRASH),
        fl.FaultEvent(2, fl.FAULT_BITFLIP, 2),
        fl.FaultEvent(2, fl.FAULT_TELEMETRY, 4)])
    assert [e.epoch for e in plan.events] == [2, 2, 9]
    assert plan.families() == ["bitflip", "crash", "telemetry"]
    assert len(plan.events_at(2)) == 2 and plan.events_at(5) == []
    with pytest.raises(ValueError, match="unknown fault family"):
        fl.FaultPlan(events=[fl.FaultEvent(0, "gamma_ray")])
    with pytest.raises(ValueError, match="epoch must be >= 0"):
        fl.FaultPlan(events=[fl.FaultEvent(-1, fl.FAULT_CRASH)])


def test_rng_per_event_is_deterministic_and_distinct():
    mk = lambda: fl.FaultPlan(seed=11, events=[          # noqa: E731
        fl.FaultEvent(4, fl.FAULT_BITFLIP),
        fl.FaultEvent(4, fl.FAULT_BITFLIP)])
    p1, p2 = mk(), mk()
    a1 = p1.rng_for(p1.events[0]).integers(1 << 30, size=4)
    a2 = p2.rng_for(p2.events[0]).integers(1 << 30, size=4)
    np.testing.assert_array_equal(a1, a2)      # replayable
    b = p1.rng_for(p1.events[1]).integers(1 << 30, size=4)
    assert not np.array_equal(a1, b)           # index-keyed, distinct


def test_flip_plane_bits_records_replay_exactly():
    plane = _plane()
    flips = lambda seed: fl.flip_plane_bits(                 # noqa: E731
        plane, np.random.default_rng(seed), n_flips=3)
    bad1, rec1 = flips(5)
    bad2, rec2 = flips(5)
    assert rec1 == rec2 and len(rec1) == 3
    for f in fl.BITFLIP_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(bad1, f)),
                                      np.asarray(getattr(bad2, f)))
    # records describe the corruption exactly: XOR-ing them back
    # recovers the clean plane
    arrs = {f: np.array(np.asarray(getattr(bad1, f)))
            for f in fl.BITFLIP_FIELDS}
    for field, idx, bit in rec1:
        arrs[field][idx] ^= np.array(1 << bit, arrs[field].dtype)
    for f in fl.BITFLIP_FIELDS:
        np.testing.assert_array_equal(arrs[f],
                                      np.asarray(getattr(plane, f)))


def test_flips_target_live_lanes_only():
    plane = _plane()
    live = np.asarray(plane.keys) != dix.PAD_KEY
    for seed in range(10):
        _, recs = fl.flip_plane_bits(plane,
                                     np.random.default_rng(seed), 2)
        for field, idx, _ in recs:
            if field == "heights":
                assert live[-1][idx[0]]
            else:
                assert live[idx]


def test_mangle_telemetry_blackout_view():
    spill, occ = fl.mangle_telemetry(17, np.array([5, 9]),
                                     np.array([3, 3]))
    assert spill == 0
    np.testing.assert_array_equal(occ, [3, 3])       # stale sample
    _, occ0 = fl.mangle_telemetry(17, np.array([5, 9]))
    np.testing.assert_array_equal(occ0, [0, 0])      # none delivered


def test_crash_is_a_transient_fault():
    assert issubclass(fl.InjectedCrash, fl.InjectedFault)
    assert issubclass(fl.InjectedFault, RuntimeError)

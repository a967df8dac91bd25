"""The plane fsck (DESIGN.md §5.11) — meshless tier-1 battery.

Clean planes from the real build/refresh paths audit all-zero (packed
AND a hand-built 2-segment mass layout); every bit-flip family is
detected; state<->plane drift, counter violations, and the saturation
warning are each exercised.  The sharded-layout audits (lanes/mass on
a forced 1x4 mesh) run in the ``benchmarks/chaos_probe.py --parity``
subprocess, invoked by CI's "Chaos recovery" step.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import device_index as dix
from repro.core import faults as fl
from repro.core import plane_check as pc
from repro.core import splaylist as sx

from conftest import seed_splay_state as _seed_state  # noqa: E402

W, L = 64, 8
POOL = np.arange(10, 10 + 2 * 48, 2, dtype=np.int32)      # 48 live keys


def _clean():
    st = _seed_state(POOL, cap=W + 2, ml=L)
    return st, dix.from_state_device(st, n_levels=L, width=W)


def test_clean_packed_plane_audits_ok():
    st, plane = _clean()
    a = pc.audit_plane(st, plane, n_segments=1)
    assert a == pc.PlaneAudit(*([0] * len(pc.PlaneAudit._fields)))
    assert pc.audit_ok(a)
    assert pc.audit_summary(a) == "audit OK"


def test_epoch_refreshed_plane_audits_ok():
    st, plane = _clean()
    rng = np.random.default_rng(0)
    kinds = rng.choice([sx.OP_CONTAINS, sx.OP_INSERT, sx.OP_DELETE],
                       16, p=[0.6, 0.3, 0.1]).astype(np.int32)
    keys = rng.choice(np.arange(0, 200, 1, np.int32), 16)
    st2, plane2, *_ = sx.run_epoch(
        st, plane, jnp.asarray(kinds), jnp.asarray(keys),
        jnp.ones(16, bool))
    assert pc.audit_ok(pc.audit_plane(st2, plane2, n_segments=1))


def _two_segment_plane(plane):
    """Hand-build the §5.6 mass layout meshless: split the packed
    bottom row into two per-block local assemblies and concatenate —
    the same per-segment `_assemble_device` construction the sharded
    mass refresh runs under shard_map."""
    wl = W // 2
    bot = np.asarray(plane.keys[L - 1])
    h = np.asarray(plane.heights)
    sl = np.asarray(plane.slots)
    live = np.nonzero(bot != dix.PAD_KEY)[0]
    cut = (live.size + 1) // 2
    blocks = []
    for lanes in (live[:cut], live[cut:]):
        k = np.full(wl, dix.PAD_KEY, np.int32)
        hh = np.zeros(wl, np.int32)
        ss = np.full(wl, -1, np.int32)
        k[:lanes.size] = bot[lanes]
        hh[:lanes.size] = h[lanes]
        ss[:lanes.size] = sl[lanes]
        local = dix._assemble_device(jnp.asarray(k), jnp.asarray(hh),
                                     jnp.asarray(ss), L)
        blocks.append(local._replace(
            local_bot=jnp.asarray(k), local_heights=local.heights,
            local_live=(jnp.asarray(k) != dix.PAD_KEY).astype(
                jnp.int32),
            local_ok=jnp.ones((1,), jnp.int32)))
    a, b = blocks
    cat = lambda f: jnp.concatenate(    # noqa: E731
        [getattr(a, f), getattr(b, f)], axis=-1)
    return dix.DeviceLevelArrays(
        keys=cat("keys"), widths=a.widths + b.widths,
        heights=cat("heights"), slots=cat("slots"),
        local_bot=cat("local_bot"), local_heights=cat("local_heights"),
        local_live=cat("local_live"), local_ok=a.local_ok)


def test_hand_built_two_segment_plane_audits_ok():
    st, plane = _clean()
    seg = _two_segment_plane(plane)
    assert dix.plane_is_segmented(seg)
    a = pc.audit_plane(st, seg, n_segments=2)
    assert pc.audit_ok(a), a
    # the same arrays audited as ONE segment must fail: interior pads
    # violate the packed invariants
    assert not pc.audit_ok(pc.audit_plane(st, seg, n_segments=1))


@pytest.mark.parametrize("field", fl.BITFLIP_FIELDS)
def test_bitflip_family_detected(field):
    st, plane = _clean()
    for seed in range(8):
        bad, recs = fl.flip_plane_bits(
            plane, np.random.default_rng(seed), 1, fields=(field,))
        assert recs, f"no flip landed for {field}"
        a = pc.audit_plane(st, bad, n_segments=1)
        assert not pc.audit_ok(a), (field, seed, a)
    # the clean plane still audits OK (flips copied, never in place)
    assert pc.audit_ok(pc.audit_plane(st, plane, n_segments=1))


def _tall():
    """The clean fixture's key set with heights cycling through every
    row, so row 0 holds keys (the state-built plane's row 0 is empty)."""
    st, _ = _clean()
    kk = np.full(W, dix.PAD_KEY, np.int32)
    hh = np.zeros(W, np.int32)
    kk[:POOL.size] = POOL
    hh[:POOL.size] = np.arange(POOL.size) % L
    return st, dix.build_device(jnp.asarray(kk), jnp.asarray(hh), L)


def test_upper_row_key_absent_from_bottom_row_detected():
    """A key of row 0 changed to a value the bottom row does not hold,
    with the row's order kept: only the upper-row membership check can
    see it (the row stays sorted and its live count stays right)."""
    st, plane = _tall()
    assert pc.audit_ok(pc.audit_plane(st, plane, n_segments=1))
    keys = np.asarray(plane.keys).copy()
    live = np.nonzero(keys[0] != dix.PAD_KEY)[0]
    assert live.size >= 2, "fixture has fewer than two top-row keys"
    j = int(live[0])
    keys[0, j] = keys[0, j] - 1            # POOL is even: k - 1 is absent
    assert keys[0, j] not in np.asarray(plane.keys[L - 1])
    a = pc.audit_plane(st, plane._replace(keys=jnp.asarray(keys)),
                       n_segments=1)
    assert a.upper_bad == 1 and not pc.audit_ok(a), a
    assert a.row_unsorted == 0 and a.heights_bad == 0, a


def test_upper_row_key_too_short_for_its_row_detected():
    """A key of row 0 swapped for a bottom-row key whose height keeps it
    out of row 0, the row kept sorted: the key is in the bottom row, but
    not at the height row 0 requires."""
    st, plane = _tall()
    keys = np.asarray(plane.keys).copy()
    h = np.asarray(plane.heights)
    bot = keys[L - 1]
    top = keys[0][keys[0] != dix.PAD_KEY]
    assert top.size, "fixture has no top-row keys"
    j = int(np.nonzero(keys[0] == top[0])[0][0])
    short = bot[(bot < top[0]) & (h < L - 1)]
    assert short.size, "no short key below the smallest top-row key"
    keys[0, j] = short[-1]
    a = pc.audit_plane(st, plane._replace(keys=jnp.asarray(keys)),
                       n_segments=1)
    assert a.upper_bad == 1 and not pc.audit_ok(a), a


def test_bitflips_detected_on_segmented_layout():
    st, plane = _clean()
    seg = _two_segment_plane(plane)
    for seed in range(8):
        bad, recs = fl.flip_plane_bits(seg, np.random.default_rng(seed),
                                       1)
        assert recs
        assert not pc.audit_ok(pc.audit_plane(st, bad, n_segments=2))


def test_state_plane_drift_detected_both_directions():
    st, plane = _clean()
    # state moves on, plane goes stale: a new key -> missing from the
    # plane; a deleted key -> extra on the plane
    st2, _, _ = sx.run_ops(
        st, jnp.asarray([sx.OP_INSERT], jnp.int32),
        jnp.asarray([11], jnp.int32), jnp.ones(1, bool))
    a = pc.audit_plane(st2, plane, n_segments=1)
    assert a.state_missing >= 1 and pc.audit_ok(a) is False
    st3, _, _ = sx.run_ops(
        st, jnp.asarray([sx.OP_DELETE], jnp.int32),
        jnp.asarray([int(POOL[0])], jnp.int32), jnp.ones(1, bool))
    a = pc.audit_plane(st3, plane, n_segments=1)
    assert a.state_extra >= 1


def test_counter_violations_fatal_saturation_warns():
    st, plane = _clean()
    bad = st._replace(dhits=st.m + jnp.int32(1))
    a = pc.audit_plane(bad, plane, n_segments=1)
    assert a.counter_bad >= 1 and not pc.audit_ok(a)
    hot = st._replace(m=jnp.int32(pc.SATURATION_LIMIT + 1))
    a = pc.audit_plane(hot, plane, n_segments=1)
    assert a.counter_saturated == 1
    assert pc.audit_ok(a)                      # warning, not fatal
    assert pc.audit_summary(a) == "audit OK warn:counter_saturated"


def test_audit_summary_names_violations():
    st, plane = _clean()
    bad, _ = fl.flip_plane_bits(plane, np.random.default_rng(0), 1,
                                fields=("heights",))
    s = pc.audit_summary(pc.audit_plane(st, bad, n_segments=1))
    assert s.startswith("audit FAIL[") and "heights_bad" in s


def test_infer_segments_and_validation():
    st, plane = _clean()
    assert pc.infer_segments(plane) == 1
    with pytest.raises(ValueError, match="not divisible"):
        pc.audit_plane(st, plane, n_segments=7)
    # hand-built segmented plane carries no sharded layout: inference
    # must refuse rather than guess
    with pytest.raises(ValueError, match="n_segments explicitly"):
        pc.infer_segments(_two_segment_plane(plane))

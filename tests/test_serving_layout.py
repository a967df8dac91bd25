"""The serving layout follows from the plane's width and the devices
present: ``splaylist.run_serving`` / ``run_epoch`` lay a plane wider than
``splay_search.MAX_DESCENT_WIDTH`` out width-sharded over the local
devices on the first call, ``mesh=None`` means the plane's own mesh, a
plane that fits is left where it is, and a plane too wide for the
devices present raises.

The four-device checks need ``--xla_force_host_platform_device_count``
before JAX starts, so they run once in a subprocess (this file run as a
script, with the limit lowered so that a 256-lane plane is "too wide"
for one device) that prints its findings as one JSON line; each test
below reads one of them.  The one-device checks run in process.
"""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
L, W, CAP, B = 10, 256, 258, 32
# an HLO instruction whose opcode is a cross-device collective
COLLECTIVE = re.compile(r"\s(all-to-all|all-gather|all-reduce|"
                        r"collective-permute|reduce-scatter)"
                        r"(-start|-done)?\(")


# ---------------------------------------------------------------------------
# the four-device child
# ---------------------------------------------------------------------------

def _load(rng):
    from bench import load
    keys = np.sort(rng.choice(np.arange(1, 3000), 180, replace=False)
                   ).astype(np.int32)
    hits = (1 + rng.integers(0, 4, len(keys))).astype(np.int32)
    st, plane = load.bulk_load(keys, hits, CAP, L, W)
    return keys, hits, st, plane


def _batches(rng, keys):
    """Three read-only batches (plane path) and three mixed ones."""
    out = []
    for read_only in (True, True, True, False, False, False):
        k = np.where(rng.random(B) < 0.8, rng.choice(keys, B),
                     rng.integers(0, 3000, B)).astype(np.int32)
        kinds = (np.zeros(B, np.int32) if read_only
                 else rng.choice([0, 0, 1, 2], B).astype(np.int32))
        out.append((read_only, kinds, k, rng.random(B) < 0.5))
    return out


def _serve(st, plane, batches, **extra):
    """The harness's loop: one ``run_serving`` call per batch, the
    read-only ones on the plane path.  Returns the final state and plane,
    the answers, path lengths and spills, and whether the plane entering
    each call after the first was width-sharded over four devices."""
    from repro.core import splaylist as sx
    from repro.parallel import sharding as shd
    answers, plens, spills, sharded_in = [], [], [], []
    for i, (read_only, kinds, keys, upd) in enumerate(batches):
        if i:
            mesh = shd.plane_width_mesh(plane)
            sharded_in.append(mesh is not None
                              and mesh.shape["model"] == 4)
        flags = (dict(aggregate=True, plane_search=True) if read_only
                 else {})
        st, plane, res, plen, _, spill, _ = sx.run_serving(
            st, plane, kinds[None], keys[None], upd[None], **flags,
            **extra)
        answers.append(np.asarray(res)[0].tolist())
        plens.append(np.asarray(plen)[0].tolist())
        spills.append(int(np.asarray(spill).sum()))
    return st, plane, answers, plens, spills, sharded_in


def _program_names(st, plane, mesh):
    """``(op_name, is collective)`` of every op of the compiled
    four-shard serving program on the plane path."""
    from repro.core import splaylist as sx
    z = np.zeros((1, B), np.int32)
    text = sx._run_serving.lower(st, plane, z, z, z.astype(bool),
                                 aggregate=True, plane_search=True,
                                 mesh=mesh).compile().as_text()
    out = []
    for line in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if m:
            head = line.split("metadata=")[0]
            out.append((m.group(1), bool(COLLECTIVE.search(head))))
    return out


def child() -> dict:
    import jax
    from bench import reference, trace_reduce
    from repro.core import device_index as dix
    from repro.core import splaylist as sx
    from repro.kernels import splay_search as ssk

    assert len(jax.devices()) == 4, jax.devices()
    rng = np.random.default_rng(11)
    keys, hits, st0, plane0 = _load(rng)
    batches = _batches(rng, keys)
    found = {}

    # the replicated path at the real limit, then the same stream with
    # the limit lowered to a quarter of the plane
    st_r, pl_r, ans_r, plen_r, spill_r, _ = _serve(st0, plane0, batches)
    ssk.MAX_DESCENT_WIDTH = W // 4
    st_s, pl_s, ans_s, plen_s, spill_s, sharded_in = _serve(
        st0, plane0, batches)
    found["sharded_every_call"] = sharded_in == [True] * 5
    found["state_replicated"] = (
        len(st_s.key.sharding.device_set) == 4
        and st_s.key.sharding.is_fully_replicated)
    found["answers_equal"] = ans_s == ans_r
    found["path_len_equal"] = plen_s == plen_r
    found["spills"] = spill_s
    a, b = sx.to_numpy(st_s), sx.to_numpy(st_r)
    found["state_fields_differing"] = [
        f for f in a if f != "counters" and not np.array_equal(a[f], b[f])]
    found["plane_fields_differing"] = [
        f for f in ("keys", "widths", "heights")
        if not np.array_equal(np.asarray(getattr(pl_s, f)),
                              np.asarray(getattr(pl_r, f)))]
    ref = reference.KeySet(keys, hits)
    found["reference_mismatches"] = sum(
        int((ref.apply(k, q, u) != np.asarray(got)).sum())
        for (_, k, q, u), got in zip(batches, ans_s))
    want = ref.sorted_keys()
    bottom = np.asarray(pl_s.keys)[-1]
    found["bottom_row_matches"] = bool(
        (bottom[:len(want)] == want).all()
        and (bottom[len(want):] == dix.PAD_KEY).all())

    # counters: every plane-path lane went through the exchange, none
    # spilled; a two-lane receive block makes lanes spill
    n_ro = sum(ro for ro, *_ in batches)
    found["counters_sharded"] = {
        c: sx.serving_counters(st_s)[c]
        for c in ("route_queries", "route_spilled")}
    found["counters_replicated"] = {
        c: sx.serving_counters(st_r)[c]
        for c in ("route_queries", "route_spilled")}
    found["lanes_routed"] = n_ro * B
    ro = [x for x in batches if x[0]]
    before = sx.serving_counters(st_s)
    st_c, _, ans_c, _, spill_c, _ = _serve(st_s, pl_s, ro,
                                           route_capacity=2)
    ans_u = _serve(st_s, pl_s, ro)[2]
    after = sx.serving_counters(st_c)
    found["forced"] = {
        "spill": sum(spill_c),
        "route_spilled": after["route_spilled"] - before["route_spilled"],
        "route_queries": after["route_queries"] - before["route_queries"],
        "lanes": len(ro) * B, "answers_equal": ans_c == ans_u}

    # a plane at the limit stays where it is with four devices visible,
    # and so does the benchmark's 2^17-lane plane at the real limit
    ssk.MAX_DESCENT_WIDTH = W
    st_k, pl_k, mesh_k = sx._place(st0, plane0, None, "model")
    found["at_limit_untouched"] = (st_k is st0 and pl_k is plane0
                                   and mesh_k is None)
    ssk.MAX_DESCENT_WIDTH = 2 ** 20
    big = dix.build_device(
        np.arange(0, 2 * 131072, 2, dtype=np.int32),
        np.zeros(131072, np.int32), n_levels=17)
    st_b = sx.make(131074, 17)
    st_k, pl_k, mesh_k = sx._place(st_b, big, None, "model")
    found["paper_plane_untouched"] = (
        st_k is st_b and pl_k is big and mesh_k is None
        and len(pl_k.keys.sharding.device_set) == 1)

    # too wide for the devices present: four blocks of 64 lanes against
    # a limit of 32
    ssk.MAX_DESCENT_WIDTH = W // 8
    try:
        sx.run_serving(st0, plane0, *(x[None] for x in batches[0][1:]),
                       aggregate=True, plane_search=True)
        found["too_wide_raised"] = ""
    except ValueError as e:
        found["too_wide_raised"] = str(e)

    # the scopes of the compiled four-shard program
    ssk.MAX_DESCENT_WIDTH = W // 4
    mesh = sx._place(st0, plane0, None, "model")[2]
    names = _program_names(st_s, pl_s, mesh)
    found["scopes"] = sorted({s for n, _ in names
                              for s in re.findall(r"splay\.[a-z_.]+", n)})
    found["fold_collectives"] = [n for n, c in names
                                 if c and "splay.fold" in n]
    for scope in ("redistribute", "route"):
        found[scope + "_collective_layers"] = sorted(
            {trace_reduce.layer_of(n) for n, c in names
             if c and f"splay.{scope}" in n})
    return found


def _four_devices() -> dict:
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(["src", "."]),
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def four():
    return _four_devices()


# ---------------------------------------------------------------------------
# four devices
# ---------------------------------------------------------------------------

def test_wide_plane_is_served_width_sharded_over_the_devices(four):
    assert four["sharded_every_call"]
    assert four["state_replicated"]


def test_routed_serving_matches_the_replicated_path_bit_for_bit(four):
    assert four["answers_equal"] and four["path_len_equal"]
    assert four["state_fields_differing"] == []
    assert four["plane_fields_differing"] == []


def test_routed_serving_agrees_with_the_reference(four):
    assert four["reference_mismatches"] == 0
    assert four["bottom_row_matches"]


def test_route_counters_count_the_exchange(four):
    assert four["counters_sharded"] == {
        "route_queries": four["lanes_routed"],
        "route_spilled": sum(four["spills"])}
    assert four["counters_replicated"] == {"route_queries": 0,
                                           "route_spilled": 0}
    forced = four["forced"]
    assert forced["route_queries"] == forced["lanes"]
    assert forced["route_spilled"] == forced["spill"] > 0
    assert forced["answers_equal"]


def test_plane_within_the_limit_is_left_where_it_is(four):
    assert four["at_limit_untouched"]
    assert four["paper_plane_untouched"]


def test_plane_too_wide_for_the_devices_raises(four):
    assert "lanes" in four["too_wide_raised"]


def test_exchange_scopes_in_the_compiled_program(four):
    from bench import trace_reduce
    assert {"splay.route", "splay.redistribute"} <= set(four["scopes"])
    for scope in ("splay.route", "splay.redistribute"):
        for _, patterns in trace_reduce.LAYERS:
            assert not any(p in scope for p in patterns)
    # the fold stays whole on every device; the refresh's collectives
    # read as the refresh, the query exchange's as no layer of its own
    assert four["fold_collectives"] == []
    assert four["redistribute_collective_layers"] == ["refresh"]
    assert four["route_collective_layers"] == ["other"]


# ---------------------------------------------------------------------------
# one device
# ---------------------------------------------------------------------------

def test_width_shards_picks_the_fewest_fitting_blocks():
    from repro.parallel import sharding as shd
    assert shd.width_shards(2 ** 22, 4, 2 ** 20) == 4
    assert shd.width_shards(2 ** 21, 4, 2 ** 20) == 2
    assert shd.width_shards(2 ** 17, 4, 2 ** 20) == 1
    assert shd.width_shards(2 ** 20, 1, 2 ** 20) == 1
    assert shd.width_shards(96, 6, 40) == 3
    for args in ((2 ** 22, 1, 2 ** 20), (2 ** 22, 2, 2 ** 20),
                 (2 ** 22, 3, 2 ** 20)):
        with pytest.raises(ValueError, match="too wide|cannot hold"):
            shd.width_shards(*args)


def test_one_device_leaves_a_fitting_plane_and_refuses_a_wide_one(
        monkeypatch):
    from bench import load
    from repro.core import splaylist as sx
    from repro.kernels import splay_search as ssk
    keys = np.arange(1, 200, 3, dtype=np.int32)
    st, plane = load.bulk_load(keys, np.ones(len(keys), np.int32), 130,
                               8, 128)
    assert sx._place(st, plane, None, "model") == (st, plane, None)
    monkeypatch.setattr(ssk, "MAX_DESCENT_WIDTH", 64)
    z = np.zeros((1, 8), np.int32)
    with pytest.raises(ValueError, match="lanes"):
        sx.run_serving(st, plane, z, z, z.astype(bool), aggregate=True,
                       plane_search=True)
    with pytest.raises(ValueError, match="lanes"):
        sx.run_epoch(st, plane, z[0], z[0], z[0].astype(bool))


if __name__ == "__main__":
    print(json.dumps(child()))

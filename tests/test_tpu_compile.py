"""The main-path descent compiled for a described TPU v5e (no chip).

Interpret mode runs a Pallas kernel without ever asking the TPU
compiler, so these tests compile the kernels ahead of time for a
``v5e:2x2`` topology: the tiered descent at the paper's deployment
width (``W = 131072``, ``L = 17``) and at ``W = 4096``, the routed
sharded search on a 4-device mesh of the same topology, and both the
routed search and the sharded refresh at the four-chip cell's shape
(``W = 2^22``, ``L = 22``), whose rows each shard lays out by a sort,
with no binary search per lane into a ``W``-wide row.  The widest plane
one device's descent compiles, ``splay_search.MAX_DESCENT_WIDTH``, is
checked against the compiler: twice as wide runs out of VMEM.  Each
asserts that the kernel made it into the executable
(``tpu_custom_call``).  The plane refresh is compiled at the same
deployment shape, to check that its row compaction stays one
single-operand sort with no per-lane gather, and the four-chip refresh
to check that it gathers one run per level row.

The topology is described inside a fixture (never at import), so every
test worker collects the same tests and only the one running this file
loads the TPU compiler; the fixture skips where no topology can be
described.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import device_index as dix
from repro.core import splaylist as sx
from repro.kernels import splay_search as ssk
from repro.launch.mesh import make_auto_mesh
from repro.parallel import sharding as shd


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A described-topology compile cannot be read back without a chip:
    keep it out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _plane_shapes(n_levels: int, width: int):
    return jax.eval_shape(
        lambda: dix.build_device(jnp.zeros((width,), jnp.int32),
                                 jnp.zeros((width,), jnp.int32),
                                 n_levels=n_levels))


@pytest.mark.parametrize("n_levels,width,nq", [
    (17, 131072, 1024),          # the paper's 10^5-key deployment
    (6, 4096, 1000),             # a plane that fits on-chip memory
])
def test_tiered_descent_compiles(topo, n_levels, width, nq):
    one = SingleDeviceSharding(topo.devices[0])
    keys = jax.ShapeDtypeStruct((n_levels, width), jnp.int32, sharding=one)
    widths = jax.ShapeDtypeStruct((n_levels,), jnp.int32, sharding=one)
    queries = jax.ShapeDtypeStruct((nq,), jnp.int32, sharding=one)
    fn = jax.jit(lambda k, q, w: ssk._splay_search_arrays(
        k, q, query_block=ssk.DEFAULT_QUERY_BLOCK, interpret=False,
        widths=w))
    compiled = fn.lower(keys, queries, widths).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_routed_sharded_search_compiles(topo):
    n_levels, width, nq, axis = 17, 16384, 4096, "model"
    mesh = make_auto_mesh((1, 4), ("data", axis), devices=topo.devices)
    specs = shd.index_plane_specs(dix.DeviceLevelArrays, axis)
    plane = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=NamedSharding(mesh, s)),
        _plane_shapes(n_levels, width), specs)
    queries = jax.ShapeDtypeStruct((nq,), jnp.int32,
                                   sharding=NamedSharding(mesh, P(axis)))
    fn = ssk._routed_search_fn(
        mesh, axis, n_levels, ssk.DEFAULT_QUERY_BLOCK, False,
        ssk.route_capacity(nq, 4), nq)
    compiled = fn.lower(plane, queries).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-to-all" in text


def _sharded_plane_shapes(topo, n_levels: int, width: int, axis: str):
    mesh = make_auto_mesh((1, 4), ("data", axis), devices=topo.devices)
    specs = shd.index_plane_specs(dix.DeviceLevelArrays, axis)
    plane = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=NamedSharding(mesh, s)),
        _plane_shapes(n_levels, width), specs)
    return mesh, plane


def test_descent_limit_is_the_widest_plane_one_device_compiles(topo):
    """``MAX_DESCENT_WIDTH`` lanes compile on one v5e; twice as many run
    out of VMEM, which is why wider planes are served width-sharded."""
    one = SingleDeviceSharding(topo.devices[0])
    fn = jax.jit(lambda k, q, w: ssk._splay_search_arrays(
        k, q, query_block=ssk.DEFAULT_QUERY_BLOCK, interpret=False,
        widths=w))

    def lower(width):
        return fn.lower(
            jax.ShapeDtypeStruct((22, width), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((1024,), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((22,), jnp.int32, sharding=one))

    assert "tpu_custom_call" in lower(
        ssk.MAX_DESCENT_WIDTH).compile().as_text()
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED.*vmem"):
        lower(2 * ssk.MAX_DESCENT_WIDTH).compile()


def test_routed_sharded_search_compiles_at_the_four_chip_cell_shape(topo):
    """The routed search of ``paper4m-ro-99-1``: a 2^22-lane plane of 22
    levels over four devices, 2^20 lanes a shard, one 1024-op batch —
    its spill path (the masked trace over the whole batch) included."""
    n_levels, width, nq, axis = 22, 2 ** 22, 1024, "model"
    mesh, plane = _sharded_plane_shapes(topo, n_levels, width, axis)
    queries = jax.ShapeDtypeStruct((nq,), jnp.int32,
                                   sharding=NamedSharding(mesh, P(axis)))
    fn = ssk._routed_search_fn(
        mesh, axis, n_levels, ssk.DEFAULT_QUERY_BLOCK, False,
        ssk.route_capacity(nq, 4), nq)
    text = fn.lower(plane, queries).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-to-all" in text
    assert width // 4 == ssk.MAX_DESCENT_WIDTH


@pytest.fixture(scope="module")
def four_chip_refresh_text(topo):
    """The lanes-split sharded refresh of ``paper4m-ro-99-1``, compiled
    once for the tests below: the replicated 4,194,306-slot state
    against the 2^22-lane plane of 22 levels over four devices."""
    n_levels, width, axis = 22, 2 ** 22, "model"
    mesh, plane = _sharded_plane_shapes(topo, n_levels, width, axis)
    st = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=NamedSharding(mesh, P())),
        jax.eval_shape(lambda: sx.make(width + 2, n_levels)))
    fn = dix._sharded_refresh_fn(mesh, axis, n_levels, width, 1024,
                                 "lanes")
    return fn.lower(st, plane).compile().as_text()


def test_sharded_refresh_compiles_at_the_four_chip_cell_shape(
        four_chip_refresh_text):
    """The sharded refresh at the four-chip cell's shape compiles, each
    level row's runs placed across the shards by an all_gather."""
    assert "all-gather" in four_chip_refresh_text


def test_sharded_refresh_composes_rows_by_a_sort_without_per_lane_search(
        four_chip_refresh_text):
    """Each shard lays out its ``[L, W/S]`` block of the plane by one
    sort along the width axis, and no gather reads a ``W``-wide row (a
    binary search per lane into the composed prefix sum of a level, or
    a take of the composed bottom row, does) or emits one index per
    lane of the block."""
    n_levels, width, lanes = 22, 2 ** 22, 2 ** 20
    text = four_chip_refresh_text

    def size(dims):
        return math.prod(int(d) for d in dims.split(",") if d)

    shapes = dict(re.findall(r"%([\w.\-]+) = \w+\[([\d,]*)\]", text))
    gathers = re.findall(
        r"= \w+\[([\d,]*)\]\S* gather\(%([\w.\-]+)", text)
    assert gathers, "no gather parsed: the HLO text format changed"
    assert width not in [size(shapes[op]) for _, op in gathers]
    assert n_levels * lanes not in [size(dims) for dims, _ in gathers]
    assert re.search(_sort_of(rf"s32\[{n_levels},{lanes}\]"), text)


def test_sharded_refresh_gathers_one_run_per_level_row(
        four_chip_refresh_text):
    """The placement all-gathers each level row's keys alone: one
    ``[S, W/S]`` run per row, never the ``[S, 3, W/S]`` stack a plane
    carrying per-row rank companions would ship."""
    text, lanes = four_chip_refresh_text, 2 ** 20
    # output shapes of every all-gather, unit dimensions dropped
    gathered = [tuple(int(d) for d in dims.split(",") if d and d != "1")
                for dims in re.findall(
                    r"= s32\[([\d,]*)\]\S* all-gather\(", text)]
    assert gathered, "no all-gather parsed: the HLO text format changed"
    assert (4, lanes) in gathered, gathered
    assert (4, 3, lanes) not in gathered, gathered


def _sort_of(rect: str) -> str:
    """A sort whose output is the rectangle ``rect``: one operand, or a
    tuple of operands of that shape."""
    return rf"= ({rect}|\({rect}\S*(, {rect}\S*)*\))\S* sort\("


@pytest.fixture(scope="module")
def one_chip_refresh_text(topo):
    """The plane refresh at the paper's cell shape (``L = 17``,
    ``W = 131072``) on one device, compiled once for both tests below."""
    n_levels, width, capacity, max_new = 17, 131072, 131074, 1024
    one = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)

    st = place(jax.eval_shape(lambda: sx.make(capacity, n_levels)))
    plane = place(_plane_shapes(n_levels, width))
    return dix.refresh_device.lower(
        st, plane, max_new=max_new).compile().as_text()


def test_refresh_compaction_sorts_without_per_lane_gathers(
        one_chip_refresh_text):
    """The plane refresh at the paper's cell shape lays its rows out by
    a sort along the width axis: no gather in the compiled program has
    one index per lane of the ``[L, W]`` rectangle (a binary search per
    output lane, or a gather through a compaction permutation, has)."""
    n_levels, width = 17, 131072
    text = one_chip_refresh_text
    gathers = [math.prod(int(d) for d in dims.split(",") if d)
               for dims in re.findall(r"= \w+\[([\d,]*)\]\S* gather\(",
                                      text)]
    assert gathers, "no gather parsed: the HLO text format changed"
    assert n_levels * width not in gathers
    assert re.search(_sort_of(rf"s32\[{n_levels},{width}\]"), text)


def test_refresh_compaction_is_one_single_operand_sort(
        one_chip_refresh_text):
    """The row compaction sorts the key rectangle alone: one
    ``s32[17,131072]`` operand, no payload carried beside it."""
    rect = r"s32\[17,131072\]"
    text = one_chip_refresh_text
    assert len(re.findall(rf"= {rect}\S* sort\(", text)) == 1
    assert not re.search(rf"= \({rect}[^\n]*\) sort\(", text)

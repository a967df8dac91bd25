"""Logical-axis sharding: names in model code, meshes at launch.

Model code annotates tensors with *logical* axis names ("batch", "heads",
"mlp", ...).  At launch, a rule table maps logical names to mesh axes
(DP/TP/EP/SP over ``(pod, data, model)``).  Resolution checks divisibility:
a dimension that does not divide by the mesh-axis product falls back to
replication (e.g. qwen2's 14 heads on a 16-way model axis -> heads
replicated, and the contraction-dim rule kicks in instead — row-parallel
TP).  This keeps every (arch x mesh) cell compilable without per-arch
special cases.

Splay index plane (DESIGN.md §5.3–§5.4): the ``[L, W]`` rectangle carries
the logical axes ``("splay_level", "splay_width")`` — levels replicated,
width sharded over ``model`` when ``W`` divides the axis.  Four helpers
cover its lifecycle: :func:`constrain_index_plane` (sharding constraints
inside jit), :func:`index_plane_specs` (the ``PartitionSpec`` pytree the
sharded refresh's and sharded search's ``shard_map`` use),
:func:`shard_index_plane` (``device_put`` a host-built plane into the
width-sharded layout), and :func:`plane_width_mesh` (detect that layout
on a concrete plane — the search wrapper's dispatch seam).
:func:`width_shards` / :func:`width_mesh` pick the mesh a plane too wide
for one device's descent is laid out on, from its width and the devices
present.
:func:`mass_split_bounds` solves the §5.6 mass-weighted shard-boundary
placement (the access-balanced alternative to equal lane counts).
Every shard_map in the repo is ``jax.shard_map(..., check_vma=False)``:
the bodies return deliberately-replicated outputs (all-reduced scalars,
all-gathered widths) that the static checker cannot prove.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_auto_mesh

Rules = Dict[str, Optional[Tuple[str, ...]]]

# -- default rule tables -----------------------------------------------------

def default_rules(multi_pod: bool = False,
                  seq_sharded: bool = False,
                  fsdp: bool = True) -> Rules:
    dp: Tuple[str, ...] = ("pod", "data") if multi_pod else ("data",)
    rules: Rules = {
        "batch": dp,
        "seq": ("data",) if seq_sharded else None,
        "kvseq": ("data",) if seq_sharded else None,
        "cp_seq": None,   # Megatron-SP residual stream (train/prefill)
        "cp_q": None,     # context-parallel attention q (set when heads
                          # cannot shard over `model`)
        "embed": None,
        "heads": ("model",),
        "kv": ("model",),
        "head_dim": None,
        "mlp": ("model",),
        "expert": ("model",),
        "expert_cap": None,
        "vocab": ("model",),
        "fsdp": dp if fsdp else None,     # ZeRO-style second-axis sharding
        "layers": None,
        "ssm_heads": ("model",),
        "ssm_proj": ("model",),
        "state": None,
        "conv": None,
        "frames": None,
        # splay index plane (core/device_index.py, DESIGN.md §5.3): the
        # [L, W] rectangle replicates over levels and width-shards over
        # the model axis; divisibility fallback replicates small planes.
        "splay_level": None,
        "splay_width": ("model",),
        None: None,
    }
    return rules


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Rules = {}


_CTX = _Ctx()


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], rules: Optional[Rules] = None):
    """Activate (mesh, rules) for logical-axis resolution.  With mesh=None
    all constraints become no-ops (single-host smoke tests).  Thread-local
    and reentrant; the previous (mesh, rules) pair is restored on exit
    even when the body raises."""
    old = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, (rules or {})
    try:
        if mesh is not None:
            with mesh:
                yield
        else:
            yield
    finally:
        _CTX.mesh, _CTX.rules = old


def active_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def _axes_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def resolve_spec(shape: Sequence[int], names: Sequence[Optional[str]],
                 mesh: Optional[Mesh] = None,
                 rules: Optional[Rules] = None) -> P:
    """Logical names -> PartitionSpec with divisibility fallback.  A mesh
    axis is never used twice in one spec (first dim wins).  Never raises:
    unknown names, rule axes absent from the mesh, and indivisible
    dimensions all resolve to replication for that dimension — the
    constraint degrades, the program still compiles."""
    mesh = mesh or _CTX.mesh
    rules = rules if rules is not None else _CTX.rules
    if mesh is None:
        return P()
    used = set()
    out = []
    for dim, name in zip(shape, names):
        axes = rules.get(name) if name is not None else None
        if not axes:
            out.append(None)
            continue
        axes = tuple(a for a in axes if a in mesh.shape and a not in used)
        if not axes or dim % _axes_size(mesh, axes) != 0:
            out.append(None)
            continue
        used.update(axes)
        out.append(axes if len(axes) > 1 else axes[0])
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def constrain(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """with_sharding_constraint under the active (mesh, rules); no-op when
    no mesh is active.  One logical name per dimension of ``x`` (trailing
    names may be omitted — unnamed dims replicate)."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    spec = resolve_spec(x.shape, names)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(shape: Sequence[int],
                   names: Sequence[Optional[str]]) -> Optional[NamedSharding]:
    mesh = _CTX.mesh
    if mesh is None:
        return None
    return NamedSharding(mesh, resolve_spec(shape, names))


def constrain_index_plane(plane):
    """Apply the splay index-plane rules to a level-array pytree
    (``device_index.DeviceLevelArrays``): the [L, W] key rectangle
    follows ("splay_level", "splay_width") — width-sharded when W
    divides the model axis, replicated otherwise — and the 1-D
    widths/heights companions follow their own axis.  No-op without an
    active mesh, so serving loops can call it unconditionally.

    Failure modes: none raised here — an indivisible width silently
    falls back to replication (by design, so every plane size stays
    compilable on every mesh).  Callers that *require* the sharded
    layout (``device_index.refresh_device_sharded``) check divisibility
    themselves and fall back to the replicated refresh."""
    fields = {
        "keys": constrain(plane.keys, "splay_level", "splay_width"),
        "widths": constrain(plane.widths, "splay_level"),
        "heights": constrain(plane.heights, "splay_width"),
        "slots": constrain(plane.slots, "splay_width"),
    }
    if hasattr(plane, "local_ok"):     # DeviceLevelArrays residency set
        fields.update(
            local_bot=constrain(plane.local_bot, "splay_width"),
            local_heights=constrain(plane.local_heights, "splay_width"),
            local_live=constrain(plane.local_live, "splay_width"),
            local_ok=constrain(plane.local_ok))
    return type(plane)(**fields)


# spec of every known index-plane field on a width-sharded layout; the
# builder below filters by the plane class's actual fields so the host
# 3-field LevelArrays and the device 8-field DeviceLevelArrays both
# resolve (DESIGN.md §5.8: the residency set rides the same layout —
# local_* blocks are per-shard, the validity bit replicates)
def _plane_field_specs(axis: str):
    return {
        "keys": P(None, axis), "widths": P(), "heights": P(axis),
        "slots": P(axis),
        "local_bot": P(axis), "local_heights": P(axis),
        "local_live": P(axis), "local_ok": P(),
    }


def index_plane_specs(plane_cls, axis: str = "model"):
    """The ``PartitionSpec`` pytree of a width-sharded index plane, in
    the shape of ``plane_cls`` (``device_index.DeviceLevelArrays``):
    ``keys`` splits its width (last) dimension over ``axis``;
    ``heights``/``slots`` and the §5.8
    residency companions ``local_bot``/``local_heights``/``local_live``
    split their only dimension; the per-level ``widths`` vector and the
    ``local_ok`` staleness bit are replicated (every shard needs every
    row's global live count, and residency is a global verdict).  This
    is the in/out contract of ``device_index.refresh_device_sharded``'s
    and ``kernels.splay_search``'s sharded ``shard_map``s."""
    by_field = _plane_field_specs(axis)
    return plane_cls(**{f: by_field[f] for f in plane_cls._fields})


def plane_width_mesh(plane, axis: str = "model") -> Optional[Mesh]:
    """The mesh a *concrete* width-sharded plane is laid out on, or None.

    Detection (not resolution): returns ``plane.keys``'s mesh exactly
    when the plane is materialized in the :func:`shard_index_plane`
    layout — last dimension split over ``axis``, more than one shard,
    width divisible.  Everything else is None: tracers (inside jit the
    caller knows its own mesh and passes it explicitly), replicated
    arrays, single-shard meshes, foreign layouts.  This is the dispatch
    seam of ``kernels.splay_search.splay_search``: a plane that *is*
    width-sharded routes to the sharded search instead of being
    gathered to replicated."""
    keys = getattr(plane, "keys", None)
    if (not isinstance(keys, jax.Array)
            or isinstance(keys, jax.core.Tracer)):
        return None
    sharding = getattr(keys, "sharding", None)
    if not isinstance(sharding, NamedSharding):
        return None
    mesh = sharding.mesh
    if axis not in mesh.shape or mesh.shape[axis] <= 1:
        return None
    spec = tuple(sharding.spec)
    if len(spec) < 2:
        return None
    width_axes = spec[-1] if isinstance(spec[-1], tuple) else (spec[-1],)
    if width_axes != (axis,):
        return None
    if keys.shape[-1] % mesh.shape[axis]:
        return None
    return mesh


def width_shards(width: int, n_devices: int, max_width: int) -> int:
    """The fewest shards ``S`` a ``width``-lane plane splits into so
    that each block holds at most ``max_width`` lanes: the smallest
    common divisor of ``width`` and ``n_devices`` with
    ``width / S <= max_width`` (1 when the plane fits whole).  Raises
    ``ValueError`` when no such divisor exists — the plane is too wide
    for the devices present."""
    for s in range(1, n_devices + 1):
        if n_devices % s == 0 and width % s == 0 and width // s <= max_width:
            return s
    raise ValueError(
        f"a {width}-lane plane needs blocks of at most {max_width} lanes, "
        f"and {n_devices} device(s) cannot hold it in equal blocks that "
        f"small: serve it on more devices or build a narrower plane")


def width_mesh(width: int, max_width: int, axis: str = "model",
               devices=None) -> Mesh:
    """The ``(1, S)`` Auto mesh (axes ``("data", axis)``) over the first
    ``S = width_shards(...)`` of ``devices`` (the local devices by
    default) that a ``width``-lane plane is laid out on when it is wider
    than ``max_width``.  Raises like :func:`width_shards`."""
    devices = list(jax.local_devices() if devices is None else devices)
    s = width_shards(width, len(devices), max_width)
    return make_auto_mesh((1, s), ("data", axis), devices=devices[:s])


def shard_index_plane(plane, mesh: Optional[Mesh] = None,
                      axis: str = "model"):
    """``device_put`` a plane into the width-sharded layout on ``mesh``
    (the active mesh when omitted).  Returns the plane unchanged when no
    mesh is available or the width does not divide ``mesh.shape[axis]``
    (the universal replication fallback).  The arrays stay *global* —
    consumers index them exactly as before; only the placement changes."""
    mesh = mesh if mesh is not None else _CTX.mesh
    if mesh is None or axis not in mesh.shape:
        return plane
    if plane.keys.shape[1] % mesh.shape[axis]:
        return plane
    specs = index_plane_specs(type(plane), axis)
    return type(plane)(*(
        jax.device_put(x, NamedSharding(mesh, s))
        for x, s in zip(plane, specs)))


def suffix_min_bounds(block_firsts: jax.Array) -> jax.Array:
    """Monotonize per-shard block-first bottom-row keys into the
    §5.4/§5.6 ownership boundary table: entry s becomes
    ``min(block_firsts[s:])``, so an *empty* block's +INF first key
    never shadows the live blocks to its right (possible on segmented
    mass-split planes; on packed planes only trailing blocks are empty
    and this is the identity).  The sharded refresh's key routing and
    the sharded search's query routing both build their table through
    this one function — the two MUST agree on every plane layout, or a
    key refreshes into one shard while its queries route to another."""
    return jax.lax.associative_scan(jnp.minimum, block_firsts,
                                    reverse=True)


def mass_split_bounds(cum_mass: jax.Array, total: jax.Array,
                      n_shards: int, lane_cap: int) -> jax.Array:
    """Feasible mass-balanced shard boundaries over a packed sorted row
    (DESIGN.md §5.6): ranks ``b[0..S]`` with ``b[0] = 0``,
    ``b[S] = total``, each segment ``[b[s], b[s+1])`` holding at most
    ``lane_cap`` keys, and interior boundaries at the access-mass
    quantiles ``s·M/S`` of ``cum_mass`` (the inclusive prefix sum of
    per-key access mass over the packed row; constant past ``total``)
    whenever the lane cap allows.

    Each interior boundary is the mass quantile clamped into the
    feasibility window ``[max(b[s-1], total − (S−s)·lane_cap),
    min(b[s-1] + lane_cap, total)]`` — the lower bound guarantees the
    *remaining* shards can still hold the remaining keys, the upper
    bound caps this shard's segment, so the result is always monotone
    and representable whenever ``total <= S · lane_cap`` (the plane's
    own width bound).  The quantile targets are computed in exact int32
    arithmetic (``floor(s·M/S) = s·(M//S) + (s·(M%S))//S`` avoids the
    ``s·M`` overflow).  Pure replicated math — every shard computes the
    same table.  With uniform mass the quantiles ARE the equal-lane
    boundaries, so an unskewed plane re-splits to the packed layout."""
    cum_mass = cum_mass.astype(jnp.int32)
    total = jnp.asarray(total, jnp.int32)
    S = int(n_shards)
    M = cum_mass[-1]

    def step(b_prev, s):
        tgt = (M // S) * s + ((M % S) * s) // S
        # count of keys whose inclusive prefix mass stays <= the
        # target: the left segment reaches the quantile, the next key
        # crosses it (side="left" would stop one key short whenever a
        # prefix hits the target exactly — e.g. uniform mass)
        ideal = jnp.searchsorted(cum_mass, tgt,
                                 side="right").astype(jnp.int32)
        lo = jnp.maximum(b_prev, total - (S - s) * lane_cap)
        hi = jnp.minimum(b_prev + lane_cap, total)
        b = jnp.clip(ideal, lo, hi)
        return b, b

    _, interior = jax.lax.scan(
        step, jnp.zeros((), jnp.int32),
        jnp.arange(1, S, dtype=jnp.int32))
    return jnp.concatenate([jnp.zeros((1,), jnp.int32), interior,
                            total[None]])


def gather_param(w: jax.Array, *storage_names: Optional[str]) -> jax.Array:
    """ZeRO-3 semantics: force an all-gather of the fsdp-sharded storage
    axes at compute time (TP axes kept).  Without this, XLA resolves the
    fsdp-on-contraction-dim mismatch with row-parallel *activation*
    all-reduces — orders of magnitude more wire than gathering the weight
    (measured in EXPERIMENTS.md §Perf iteration 1)."""
    names = [None if n == "fsdp" else n for n in storage_names]
    return constrain(w, *names)

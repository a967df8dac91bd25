"""Sharding rule resolution, roofline parsing, dry-run unit logic, and
the shard_map pipeline (subprocess with 8 fake devices)."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.parallel import sharding as shd
from repro.launch import roofline as rf
from repro.launch.mesh import make_auto_mesh


def _mesh11():
    return make_auto_mesh((1, 1), ("data", "model"))


def test_resolve_spec_divisibility_fallback():
    mesh = _mesh11()
    rules = shd.default_rules()
    # with axis sizes 1 everything divides; check rule mapping
    spec = shd.resolve_spec((32, 64), ("batch", "mlp"), mesh, rules)
    assert spec == jax.sharding.PartitionSpec("data", "model")
    # unknown name -> replicated
    spec = shd.resolve_spec((32,), ("nope",), mesh, rules)
    assert spec == jax.sharding.PartitionSpec()


def test_resolve_spec_no_axis_reuse():
    mesh = _mesh11()
    rules = {"a": ("data",), "b": ("data",)}
    spec = shd.resolve_spec((4, 4), ("a", "b"), mesh, rules)
    assert spec == jax.sharding.PartitionSpec("data")  # b falls back


def test_splay_index_plane_rules():
    """The index plane resolves to (replicated, width-sharded) and falls
    back to full replication when the width doesn't divide."""
    mesh = _mesh11()
    rules = shd.default_rules()
    spec = shd.resolve_spec((6, 4096), ("splay_level", "splay_width"),
                            mesh, rules)
    assert spec == jax.sharding.PartitionSpec(None, "model")
    # width rule pointing at an axis absent from the mesh -> replicate
    spec = shd.resolve_spec(
        (6, 4096), ("splay_level", "splay_width"), mesh,
        {"splay_level": None, "splay_width": ("expert_axis",)})
    assert spec == jax.sharding.PartitionSpec()


def test_constrain_index_plane_roundtrip():
    import jax.numpy as jnp
    from repro.core import device_index as dix
    plane = dix.build_device(
        jnp.asarray(np.arange(0, 128, 2, dtype=np.int32)),
        jnp.asarray(np.zeros(64, np.int32)), n_levels=3)
    # no mesh: identity
    out = shd.constrain_index_plane(plane)
    np.testing.assert_array_equal(np.asarray(out.keys),
                                  np.asarray(plane.keys))
    with shd.use_mesh(_mesh11(), shd.default_rules()):
        out = shd.constrain_index_plane(plane)
    np.testing.assert_array_equal(np.asarray(out.keys),
                                  np.asarray(plane.keys))
    np.testing.assert_array_equal(np.asarray(out.heights),
                                  np.asarray(plane.heights))


def test_constrain_noop_without_mesh():
    import jax.numpy as jnp
    x = jnp.ones((4, 4))
    y = shd.constrain(x, "batch", "mlp")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_parse_collectives_ring_model():
    hlo = """
  %ag = f32[16,128] all-gather(f32[1,128] %x), replica_groups=[16,16]
  %ar = bf16[1024] all-reduce(bf16[1024] %y), replica_groups={{0,1,2,3}}
  %cp = f32[8,8] collective-permute(f32[8,8] %z), source_target_pairs={{0,1}}
"""
    out = rf.parse_collectives(hlo)
    assert out["all-gather"]["count"] == 1
    # all-gather result 16*128*4 bytes * (g-1)/g with g=16
    assert out["all-gather"]["wire_bytes"] == 16 * 128 * 4 * 15 // 16
    assert out["all-reduce"]["wire_bytes"] == 2 * 1024 * 2 * 3 // 4
    assert out["collective-permute"]["wire_bytes"] == 8 * 8 * 4


def test_roofline_terms_dominant():
    t = rf.roofline_terms(197e12, 819e9 * 2, 0.0)   # 1s compute, 2s mem
    assert t["dominant"] == "memory"
    assert abs(t["compute_s"] - 1.0) < 1e-6
    assert abs(t["roofline_fraction"] - 0.5) < 1e-6


def test_model_flops_moe_uses_active():
    from repro.configs import registry
    cfg = registry.get("phi3.5-moe-42b-a6.6b")
    assert cfg.n_active_params() < 0.3 * cfg.n_params()
    f_train = rf.model_flops(cfg, 4096, 256, "train")
    f_dec = rf.model_flops(cfg, 32768, 128, "decode")
    assert f_train > f_dec


def test_cell_enumeration_skips_long500k_for_quadratic():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--list-cells"],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    cells = [tuple(line.split()) for line in r.stdout.strip().splitlines()]
    assert len(cells) == 32
    long_archs = {a for a, s in cells if s == "long_500k"}
    assert long_archs == {"zamba2-7b", "mamba2-1.3b"}


PIPE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_auto_mesh
from repro.parallel.pipeline import pipeline_forward, split_stages

mesh = make_auto_mesh((4, 2), ("pod", "model"))
L, D, B = 8, 16, 8
rng = np.random.default_rng(0)
w = jnp.asarray(rng.normal(size=(L, D, D)).astype(np.float32) * 0.3)
x = jnp.asarray(rng.normal(size=(B, D)).astype(np.float32))

def layer(wi, h):
    return jnp.tanh(h @ wi)

# sequential reference
ref = x
for i in range(L):
    ref = layer(w[i], ref)

def stage_fn(params_i, h):
    def body(h, wi):
        return layer(wi, h), None
    h, _ = jax.lax.scan(body, h, params_i)
    return h

stages = split_stages(w, 4)
out = pipeline_forward(x, stages, stage_fn, mesh, n_microbatches=4,
                       axis="pod")
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                           atol=2e-4)
print("PIPELINE_OK")
"""


def test_pipeline_equivalence_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", PIPE_SCRIPT],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=300)
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr

"""GPipe-style pipeline parallelism over the `pod` axis (optional).

For depth-dominated models the multi-pod mesh can carry pipeline stages
instead of extra DP: layers split into ``n_stages`` contiguous stages (one
per pod), microbatches stream through with lax.ppermute handoffs under
shard_map.  The schedule is classic GPipe (fill, steady state, drain):
bubble fraction = (S-1)/(S-1+M) for S stages, M microbatches.

This module is self-contained (own stage runner) and is exercised by
tests/test_pipeline.py for numerical equivalence against the sequential
stack, and by the dry-run flag --pipeline for compilability.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(x, stage_params, stage_fn: Callable, mesh,
                     n_microbatches: int, axis: str = "pod"):
    """Run ``stage_fn(params_i, x)`` over pipeline stages laid on `axis`.

    x:            [B, ...] global batch (B % n_microbatches == 0)
    stage_params: pytree with leading stage axis [S, ...] sharded on
                  `axis`.
    Returns the final-stage output with the same layout as x.
    """
    n_stages = mesh.shape[axis]

    def stage_worker(params_local, x_local):
        """One stage's loop (shard_map body; params_local has the [1,...]
        stage slice)."""
        params_i = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        mb = jnp.split(x_local, n_microbatches, axis=0)
        mb = jnp.stack(mb)                     # [M, b, ...]
        n_ticks = n_stages + n_microbatches - 1

        def tick(carry, t):
            outputs, buf = carry
            # receive from previous stage (stage 0 pulls from the batch)
            mb_idx = jnp.clip(t - stage, 0, n_microbatches - 1)
            own = mb[mb_idx]
            inp = jnp.where(stage == 0, own, buf)
            active = (t >= stage) & (t < stage + n_microbatches)
            out = jnp.where(active, stage_fn(params_i, inp), inp)
            # hand to next stage
            buf_next = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % n_stages) for i in
                            range(n_stages)])
            # last stage records finished microbatches
            done_idx = jnp.clip(t - (n_stages - 1), 0,
                                n_microbatches - 1)
            is_done = (stage == n_stages - 1) & active
            outputs = jax.lax.cond(
                is_done,
                lambda o: o.at[done_idx].set(out),
                lambda o: o, outputs)
            return (outputs, buf_next), None

        outputs0 = jnp.zeros_like(mb)
        buf0 = jnp.zeros_like(mb[0])
        (outputs, _), _ = jax.lax.scan(
            tick, (outputs0, buf0), jnp.arange(n_ticks))
        # broadcast final outputs from the last stage to all stages
        # (psum of the masked tensor — ppermute cannot fan out 1->N)
        outputs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outputs, 0.0), axis)
        return outputs.reshape(x_local.shape)

    spec_x = P()          # batch replicated across the pipe axis
    spec_p = P(axis)
    fn = jax.shard_map(
        stage_worker, mesh=mesh,
        in_specs=(spec_p, spec_x), out_specs=spec_x, check_vma=False)
    return fn(stage_params, x)


def split_stages(stacked_params, n_stages: int):
    """Reshape per-layer stacked params [L, ...] -> [S, L//S, ...]."""
    def f(a):
        L = a.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return a.reshape(n_stages, L // n_stages, *a.shape[1:])
    return jax.tree.map(f, stacked_params)

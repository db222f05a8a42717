"""Pipeline parallelism: GPipe-style microbatch schedule on a "pipe" mesh
axis via shard_map + lax.ppermute.

Stage parameters are stacked on a leading (n_stages) axis sharded over the
pipe axis; inside the shard_map each device group holds one stage.  The
static tick loop runs M + S - 1 ticks: stage 0 injects a fresh microbatch
per tick, every stage applies its layer stack, activations hop one stage
per tick via collective_permute.  The last stage accumulates outputs.

Opt-in (1000+-node scaling feature, DESIGN.md §5): the assigned production
mesh uses DP x TP, so the baseline dry-runs don't engage this module; it is
exercised by tests/test_pipeline.py on 8 host devices and composes with the
mesh as an extra leading axis.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable,         # (stage_params, x) -> y   (same shape)
    stage_params,               # pytree, leaves (n_stages, ...)
    microbatches: jax.Array,    # (M, mb, ...) input activations
    mesh: Mesh,
    axis_name: str = "pipe",
):
    """Run the GPipe schedule. Returns (M, mb, ...) outputs (last stage)."""
    n_stages = mesh.shape[axis_name]
    m = microbatches.shape[0]
    assert m >= n_stages, (m, n_stages)

    param_specs = jax.tree_util.tree_map(
        lambda l: P(axis_name, *([None] * (l.ndim - 1))), stage_params)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(param_specs, P()),       # microbatches replicated
        out_specs=P(),
        check_vma=False)
    def run(params, mbs):
        params = jax.tree_util.tree_map(lambda l: l[0], params)
        idx = jax.lax.axis_index(axis_name)
        is_first = idx == 0
        is_last = idx == n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        x_shape = mbs.shape[1:]
        carry = jnp.zeros(x_shape, mbs.dtype)
        outputs = jnp.zeros(mbs.shape, mbs.dtype)

        def tick(t, state):
            carry, outputs = state
            inject_idx = jnp.minimum(t, m - 1)
            x_in = jnp.where(is_first, mbs[inject_idx], carry)
            y = stage_fn(params, x_in)
            # Collect finished microbatch at the last stage.
            out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            take = jnp.logical_and(is_last, t >= n_stages - 1)
            outputs = jax.lax.cond(
                take,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, out_idx, 0),
                lambda o: o,
                outputs)
            carry = jax.lax.ppermute(y, axis_name, perm)
            return carry, outputs

        _, outputs = jax.lax.fori_loop(
            0, m + n_stages - 1, tick, (carry, outputs))
        # Broadcast the last stage's outputs to every stage (so out_specs
        # P() — replicated — is truthful).
        outputs = jax.lax.psum(
            jnp.where(is_last, outputs, jnp.zeros_like(outputs)), axis_name)
        return outputs

    return run(stage_params, microbatches)


def make_pipe_mesh(n_stages: int) -> Mesh:
    devs = jax.devices()[:n_stages]
    import numpy as np
    return Mesh(np.asarray(devs).reshape(n_stages), ("pipe",))

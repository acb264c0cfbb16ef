"""GPipe-style pipeline parallelism over a mesh axis.

The layer stack is split into ``n_stages`` contiguous stages (stage s
holds layers [s·L/P, (s+1)·L/P)); microbatches stream through the
pipeline with ``collective_permute`` (ppermute) stage hand-offs.  The
schedule is the classic GPipe fill-run-drain: ``n_micro + P - 1`` ticks,
bubble fraction (P-1)/(n_micro+P-1).

Forward-only scheduling is written here; jax autodiff through ppermute
yields the GPipe backward (all-forward-then-all-backward) automatically,
so the same function trains.

This is offered as the alternative use of the "pod" axis (DP across pods
is the default recipe); the dry-run exercises it via
``examples``/tests on a small mesh and it composes with in-stage
FSDP/TP shardings on the remaining axes.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(
    layer_apply: Callable,  # (layer_params, x) -> x
    stacked_params,  # pytree, leaves (L, ...)
    x: jax.Array,  # (n_micro, mb, ...) microbatched input
    mesh: Mesh,
    axis: str = "pod",
) -> jax.Array:
    """Run the layer stack as a pipeline over ``axis``.

    Returns the full (n_micro, mb, ...) output (valid on every device —
    the last stage's results are broadcast with a psum at the end).
    """
    n_stages = mesh.shape[axis]
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    assert L % n_stages == 0, (L, n_stages)
    per_stage = L // n_stages
    n_micro = x.shape[0]

    # stage-shard the stacked params along the layer axis
    param_specs = jax.tree.map(lambda _: P(axis), stacked_params)
    x_spec = P()  # microbatches replicated into the pipe

    def stage_fn(params_stage, x_all):
        sid = jax.lax.axis_index(axis)

        def apply_stage(h):
            def body(hh, lp):
                return layer_apply(lp, hh), None

            h2, _ = jax.lax.scan(body, h, params_stage)
            return h2

        perm_fwd = [(i, i + 1) for i in range(n_stages - 1)]
        buf = jnp.zeros_like(x_all[0])
        out = jnp.zeros_like(x_all)
        T = n_micro + n_stages - 1
        for t in range(T):
            feed = x_all[min(t, n_micro - 1)]
            inp = jnp.where(sid == 0, feed, buf)
            act = apply_stage(inp)
            if t >= n_stages - 1:
                mb = t - (n_stages - 1)
                last = jnp.where(sid == n_stages - 1, act, jnp.zeros_like(act))
                out = out.at[mb].set(last)
            if n_stages > 1:
                buf = jax.lax.ppermute(act, axis, perm_fwd)
        # broadcast the last stage's outputs to every pipeline rank
        return jax.lax.psum(out, axis)

    return jax.shard_map(
        stage_fn,
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=P(),
        check_vma=False,
    )(stacked_params, x)


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)

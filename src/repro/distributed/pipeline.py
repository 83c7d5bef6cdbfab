"""GPipe pipeline parallelism over the ``pod`` axis (scan + ppermute SPMD).

The pipeline is expressed as a differentiable program: one `lax.scan` over
``n_micro + n_stages - 1`` ticks inside `shard_map`; at each tick every stage
runs its layer group on the activation it holds and ppermutes the result to
its successor. Reverse-mode autodiff through the scan yields the backward
pipeline automatically (activation stash = GPipe memory profile; compose with
jax.checkpoint on the stage fn for a 1F1B-like footprint).

Cross-pod traffic per tick = one microbatch of activations — the inter-pod
DCN-friendly pattern (activations, not weight shards, cross the pod boundary).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array


def gpipe_apply(
    stage_fn: Callable[[dict, Array], Array],
    stage_params: dict,
    x_micro: Array,
    *,
    axis_name: str,
    n_micro: int,
    remat: bool = True,
) -> Array:
    """Run inside shard_map. ``stage_params``: this stage's slice of a
    stacked (n_stages, ...) pytree sharded over ``axis_name`` (leading dim 1,
    squeezed here). ``x_micro``: (n_micro, mb, ...) inputs, replicated across
    the pipeline axis; only stage 0 consumes them. Returns (n_micro, mb, ...)
    outputs, replicated across stages after a final masked psum.
    """
    n_stages = lax.axis_size(axis_name)  # static python int inside shard_map
    s_idx = lax.axis_index(axis_name)
    fn = jax.checkpoint(stage_fn) if remat else stage_fn
    ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        state, outputs = carry  # state: (mb, ...) activation resident here
        feed = x_micro[jnp.clip(t, 0, n_micro - 1)]
        x_in = jnp.where(s_idx == 0, feed, state)
        y = fn(stage_params, x_in)
        # the last stage emits microbatch t - (S-1)
        out_t = t - (n_stages - 1)
        emit = (s_idx == n_stages - 1) & (out_t >= 0) & (out_t < n_micro)
        idx = jnp.clip(out_t, 0, n_micro - 1)
        cur = lax.dynamic_index_in_dim(outputs, idx, 0, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(emit, y, cur), idx, 0
        )
        state = lax.ppermute(y, axis_name, ring)  # hand off to successor
        return (state, outputs), None

    mb_shape = x_micro.shape[1:]
    state0 = jnp.zeros(mb_shape, x_micro.dtype)
    out0 = jnp.zeros((n_micro, *mb_shape), x_micro.dtype)
    n_ticks = n_micro + n_stages - 1
    (_, outputs), _ = lax.scan(tick, (state0, out0), jnp.arange(n_ticks))
    # broadcast outputs from the last stage to every stage
    mask = (s_idx == n_stages - 1).astype(outputs.dtype)
    return lax.psum(outputs * mask, axis_name)


def stack_stage_params(init_fn: Callable[[jax.Array], dict], key: jax.Array,
                       n_stages: int) -> dict:
    """Stacked per-stage params; shard the leading axis over the pipe axis."""
    keys = jax.random.split(key, n_stages)
    return jax.vmap(init_fn)(keys)

"""Pipeline parallelism (GPipe schedule) via shard_map + ppermute.

Stage weights live sharded over a ``stage`` mesh axis; microbatches flow
through stages with collective_permute between neighbours.  The classic
SPMD formulation: every device runs the same program; at tick t, stage s
holds microbatch (t - s) — a rotating buffer of live activations.  Total
ticks = n_micro + n_stages - 1 (the pipeline bubble).

This is the manual-collective counterpart of the GSPMD paths used by the
main models: available for hillclimbing the pod axis (DESIGN.md §5) and
exercised by tests/test_distributed.py for exact equivalence with the
sequential execution.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P



def pipeline_apply(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,          # leaves [n_stages, ...] sharded over axis
    x: jnp.ndarray,             # [n_micro, micro_batch, ...]
    mesh: Mesh,
    axis: str = "stage",
) -> jnp.ndarray:
    """Run x through n_stages of stage_fn in a GPipe schedule."""
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    assert n_micro % 1 == 0

    def body(params_local, x_local):
        # params_local: stage-local params (leading dim 1); x_local: this
        # stage's slice of the microbatch queue [n_micro/n_stages, ...].
        # We all-gather the queue so stage 0 can feed any microbatch
        # (queue is small relative to activations in real use).
        p_loc = jax.tree.map(lambda a: a[0], params_local)
        stage_id = jax.lax.axis_index(axis)
        xq = jax.lax.all_gather(x_local, axis, axis=0, tiled=True)
        n_ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(xq[0])
        out = jnp.zeros_like(xq[: n_micro])

        def tick(t, carry):
            buf, out = carry
            # stage 0 ingests microbatch t (if any); others use the
            # activation permuted from the previous stage.
            feed = jnp.where(
                t < n_micro,
                jax.lax.dynamic_index_in_dim(xq, jnp.minimum(t, n_micro - 1),
                                             axis=0, keepdims=False),
                jnp.zeros_like(buf))
            cur = jnp.where(stage_id == 0, feed, buf)
            y = stage_fn(p_loc, cur)
            # pass to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, axis, perm)
            # last stage emits microbatch (t - n_stages + 1)
            mb = t - (n_stages - 1)
            emit = jnp.logical_and(stage_id == n_stages - 1, mb >= 0)
            out = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(mb, 0), axis=0),
                lambda o: o, out)
            return buf, out

        _, out = jax.lax.fori_loop(0, n_ticks, tick, (buf, out))
        # result lives on the last stage; psum broadcasts it (all other
        # stages contribute zeros), so out_specs can be replicated.
        return jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, out, jnp.zeros_like(out)),
            axis)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)

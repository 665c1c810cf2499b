"""Pipeline parallelism over the ``pod`` axis (GPipe-style microbatching).

At 2 pods the default posture is DP over ``pod`` (bubble-free, and the
cross-pod gradient traffic can be PVQ-compressed — see optim/grad_compress);
this module provides the PP alternative for deeper pod counts or models whose
layers do not fit a single pod even fully sharded.

Schedule: the L layer-groups are split into S stages (one per pod rank);
microbatches flow stage-to-stage with ``jax.lax.ppermute`` inside a
``shard_map`` over the ``pod`` axis.  GPipe schedule: all microbatches
forward, then backward (handled by jax.grad through the scan); bubble
fraction = (S-1)/(S-1+M) for M microbatches.

The implementation is deliberately generic: ``stage_fn(stage_params, x)``
is any per-stage function; weights are expected pre-partitioned with a
leading stage axis (one stage per pod rank via P('pod', ...)).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,  # leaves with leading [n_stages] axis, sharded P('pod')
    x_microbatches: jax.Array,  # (n_micro, mb, ...) input microbatches
    *,
    mesh: Mesh,
    axis: str = "pod",
) -> jax.Array:
    """Run the pipeline; returns the final-stage outputs (n_micro, mb, ...).

    Implemented as a shard_map over ``axis``: each rank holds one stage's
    params; a rotating buffer carries microbatch activations rank-to-rank
    with ppermute.  Total ticks = n_micro + n_stages - 1.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_microbatches.shape[0]

    def per_pod(params_local, x_local):
        # params_local: stage params with leading axis 1 (this rank's stage);
        # x_local: the full (n_micro, mb, ...) batch (replicated input)
        params_here = jax.tree.map(lambda t: t[0], params_local)
        stage_idx = jax.lax.axis_index(axis)
        total = n_micro + n_stages - 1

        def tick(carry, t):
            buf, outputs = carry  # buf: (mb, ...) activation from prev stage
            # stage 0 injects microbatch t (when in range)
            inject = jnp.where(t < n_micro, t, n_micro - 1)
            x_in = jnp.where((stage_idx == 0) & (t < n_micro), x_local[inject], buf)
            y = stage_fn(params_here, x_in)
            # last stage collects its result for microbatch (t - S + 1)
            out_slot = t - (n_stages - 1)
            write = (stage_idx == n_stages - 1) & (out_slot >= 0)
            outputs = jax.lax.cond(
                write,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_slot, 0), 0
                ),
                lambda o: o,
                outputs,
            )
            # rotate activations to the next stage
            buf = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return (buf, outputs), None

        buf0 = jnp.zeros_like(x_local[0])
        outputs0 = jnp.zeros((n_micro,) + x_local.shape[1:], x_local.dtype)
        (buf, outputs), _ = jax.lax.scan(tick, (buf0, outputs0), jnp.arange(total))
        # rotate once more: rank 0 ends up holding the last stage's outputs
        outputs = jax.lax.ppermute(
            outputs, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
        )
        return outputs[None]

    fn = jax.shard_map(
        per_pod,
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stage_params), P(None)),
        out_specs=P(axis),
        check_vma=False,
    )
    out = fn(stage_params, x_microbatches)  # (n_stages, n_micro, mb, ...)
    return out[0]


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe bubble overhead: idle ticks / total ticks."""
    return (n_stages - 1) / (n_stages - 1 + n_micro)

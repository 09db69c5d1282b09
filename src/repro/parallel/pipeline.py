"""Pipeline parallelism: GPipe-style microbatch pipeline over an axis.

``pipeline_forward`` runs a layer-stack forward as a collective_permute
rotation inside ``shard_map``: each device along ``stage_axis`` owns a
contiguous slab of layers; microbatches enter at stage 0 and activations
hop stage-to-stage with ``collective_permute`` (the paper's peer-to-peer,
buffer-state-coordinated transfer — no global scheduler, each stage
simply services whatever sits in its inbound slot).

Steady-state utilization is ``m / (m + s - 1)`` for m microbatches and s
stages; the schedule loop below is exactly that bubble.  Used as the PP
option for the deepest assigned arch (mistral-large-123b) where the pod
axis becomes the stage axis — see EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def pipeline_forward(
    layer_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,           # leaves with leading dim n_layers
    x: jax.Array,                  # (n_micro, micro_batch, ...) microbatched input
    *,
    mesh: Mesh,
    stage_axis: str = "pod",
    layers_per_stage: int,
) -> jax.Array:
    """Forward x through all stages.  Returns (n_micro, micro_batch, ...).

    ``layer_fn(stage_params, h) -> h`` applies one stage's slab (typically
    an inner lax.scan over ``layers_per_stage`` layers).
    """
    n_stages = mesh.shape[stage_axis]
    n_micro = x.shape[0]
    perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def staged(params_local, x_local):
        # params_local: this stage's slab (layers_per_stage, ...)
        # x_local: full microbatch stream, present on stage 0
        stage_id = jax.lax.axis_index(stage_axis)
        mb_shape = x_local.shape[1:]
        # carries must be marked device-varying over the stage axis up
        # front (ppermute outputs are varying; fori_loop carries need
        # matching types)
        buf = jax.lax.pcast(jnp.zeros(mb_shape, x_local.dtype), stage_axis,
                            to="varying")
        outs = jax.lax.pcast(jnp.zeros_like(x_local), stage_axis,
                             to="varying")

        def tick(t, carry):
            buf, outs = carry
            # stage 0 injects microbatch t (if any remain)
            inject = jnp.where(t < n_micro,
                               x_local[jnp.minimum(t, n_micro - 1)],
                               jnp.zeros(mb_shape, x_local.dtype))
            h = jnp.where(stage_id == 0, inject, buf)
            h = layer_fn(params_local, h)
            # last stage banks the finished microbatch (entered at t-s+1);
            # select-based update (lax.cond branches would need matching
            # varying-manual-axes types inside shard_map)
            done_idx = t - (n_stages - 1)
            valid = jnp.logical_and(done_idx >= 0, done_idx < n_micro)
            idx = jnp.clip(done_idx, 0, n_micro - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, idx, 0, keepdims=False)
            new = jnp.where(valid, h.astype(outs.dtype), cur)
            outs = jax.lax.dynamic_update_index_in_dim(outs, new, idx, 0)
            # rotate activations one stage forward
            buf = jax.lax.ppermute(h, stage_axis, perm_fwd)
            return buf, outs

        buf, outs = jax.lax.fori_loop(0, n_micro + n_stages - 1, tick,
                                      (buf, outs))
        # result lives on the last stage; broadcast so out_specs can be
        # stage-replicated (callers usually reduce immediately anyway)
        outs = jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, outs, jnp.zeros_like(outs)),
            stage_axis)
        return outs

    # stage axis shards the layer dim of every stacked leaf
    param_spec = jax.tree.map(lambda _: P(stage_axis), stacked_params)
    return jax.shard_map(
        staged, mesh=mesh,
        in_specs=(param_spec, P(*( [None] * x.ndim ))),
        out_specs=P(*([None] * x.ndim)),
    )(stacked_params, x)

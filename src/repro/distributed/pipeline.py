"""GPipe-style pipeline parallelism with collective_permute (TPU-idiomatic).

Each device along the "pipe" mesh axis owns one stage's parameters; the
schedule runs n_micro + n_stages - 1 ticks; activations hop stage->stage
with ppermute so compute and the (tiny) boundary transfer overlap under
XLA's latency-hiding scheduler.  This is the optional PP mode for depth
scaling beyond what DP x TP covers; the production dry-run meshes use
DP x TP (+EP), so PP is exercised by its own test/bench on a host mesh.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn: Callable, mesh, axis: str, stage_params,
                   x_micro: jax.Array) -> jax.Array:
    """Run x_micro [n_micro, mb, ...] through n_stages pipeline stages.

    stage_fn(params_slice, x [mb, ...]) -> [mb, ...]
    stage_params: pytree with leading dim n_stages (sharded over `axis`).
    Returns [n_micro, mb, ...] outputs of the final stage.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    total = n_micro + n_stages - 1
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def local(params_local, x_local):
        # params_local: leading dim 1 (this stage); x_local [n_micro, mb,...]
        params_here = jax.tree.map(lambda w: w[0], params_local)
        stage_id = jax.lax.axis_index(axis)
        mb_shape = x_local.shape[1:]
        buf = jnp.zeros(mb_shape, x_local.dtype)       # activation in flight
        outs = jnp.zeros_like(x_local)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any left)
            idx = jnp.minimum(t, n_micro - 1)
            fresh = x_local[idx]
            buf = jnp.where(stage_id == 0,
                            jnp.where(t < n_micro, fresh, buf), buf)
            y = stage_fn(params_here, buf)
            # last stage emits microbatch t - (n_stages - 1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            emit = jnp.logical_and(stage_id == n_stages - 1,
                                   t >= n_stages - 1)
            outs = jnp.where(emit,
                             outs.at[out_idx].set(y), outs)
            buf = jax.lax.ppermute(y, axis, fwd_perm)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, total, tick, (buf, outs))
        # only the last stage holds real outputs; broadcast them
        outs = jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    p_specs = jax.tree.map(lambda _: P(axis), stage_params)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(p_specs, P()), out_specs=P(),
        check_vma=False)(stage_params, x_micro)

"""GPipe-style pipeline parallelism over a mesh axis (default: 'pod').

Layers are split into ``n_stages`` contiguous stages; the stacked stage
parameters are sharded over the pipeline axis, microbatches stream through
with ``lax.ppermute`` boundary transfers (the collective_permute schedule a
TPU pod runs between pods), and the classic GPipe bubble of (P-1) ticks
shows up explicitly in the tick loop.

This is the optional PP mode of DESIGN.md §6: the default multi-pod layout
uses the pod axis for data parallelism, but the launcher exposes
``--pipeline`` and tests exercise this executor on small CPU meshes against
the sequential reference (exact equality).

Scope: homogeneous block stacks (one scan body), which covers every dense
assigned arch; hybrid patterns pipeline at period granularity.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["gpipe"]


def gpipe(block_fn: Callable, mesh: Mesh, axis: str = "pod"):
    """Build a pipelined layer-stack applier.

    ``block_fn(params_one_layer, x) -> x`` applies one layer.
    Returns ``apply(stacked_params, x_micro)`` where

      stacked_params : leaves (L, ...) with L = n_stages * layers_per_stage,
                       sharded P(axis, ...) (stage-major layer order)
      x_micro        : (n_micro, mb, ...) microbatched activations,
                       replicated over ``axis``

    and the result matches the sequential application of all L layers to
    every microbatch (GPipe schedule, (n_stages - 1) bubble ticks).
    """
    n_stages = mesh.shape[axis]

    def apply(stacked_params, x_micro):
        n_micro = x_micro.shape[0]

        def stage_body(local_params, x_all):
            # local_params: (L/P, ...) this stage's layers
            # x_all: (n_micro, mb, ...) — every stage sees the microbatches;
            # only stage 0 uses them as true inputs.
            stage = lax.axis_index(axis)

            def run_stage(x):
                def one(h, lp):
                    return block_fn(lp, h), None
                h, _ = lax.scan(one, x, local_params)
                return h

            ticks = n_micro + n_stages - 1
            buf = jnp.zeros_like(x_all[0])          # inter-stage register
            outs = jnp.zeros_like(x_all)

            def tick(carry, t):
                buf, outs = carry
                mb_in = t - stage                    # microbatch index here
                x_in = jnp.where(
                    (mb_in >= 0) & (mb_in < n_micro),
                    lax.dynamic_index_in_dim(
                        x_all, jnp.clip(mb_in, 0, n_micro - 1), 0,
                        keepdims=False),
                    jnp.zeros_like(buf))
                h_in = jnp.where(stage == 0, x_in, buf)
                h_out = run_stage(h_in)
                # last stage writes its finished microbatch
                outs = lax.cond(
                    (stage == n_stages - 1) & (mb_in >= 0) & (mb_in < n_micro),
                    lambda o: lax.dynamic_update_index_in_dim(
                        o, h_out, jnp.clip(mb_in, 0, n_micro - 1), 0),
                    lambda o: o, outs)
                # forward transfer to the next stage
                buf = lax.ppermute(
                    h_out, axis,
                    [(i, (i + 1) % n_stages) for i in range(n_stages)])
                return (buf, outs), None

            (buf, outs), _ = lax.scan(tick, (buf, outs),
                                      jnp.arange(ticks))
            # every stage but the last holds zeros in outs: psum replicates
            # the finished microbatches to all stages
            return lax.psum(outs, axis)

        in_specs = (jax.tree.map(lambda _: P(axis), stacked_params),
                    P(*([None] * x_micro.ndim)))
        out_specs = P(*([None] * x_micro.ndim))
        return jax.shard_map(stage_body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(
            stacked_params, x_micro)

    return apply

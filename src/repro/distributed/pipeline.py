"""Pipeline parallelism (GPipe schedule) over a ``stage`` mesh axis.

DP/TP/SP/EP are wired throughout the framework; this module adds the PP
axis for depth-dominant deployments (very deep models or meshes whose
slow links make TP collectives per layer uneconomical — e.g. using the
cross-pod DCI as the pipeline hop so only (B/M, S, d) activations cross
pods once per stage instead of per-layer collectives).

Mechanics (classic GPipe, expressed with shard_map + ppermute):

* the stacked per-layer params (L, ...) shard over ``stage``: each of the
  S stages owns L/S contiguous layers;
* the batch splits into M microbatches; at clock tick t, stage s runs
  microbatch (t - s) if 0 <= t - s < M, then passes its activation to
  stage s+1 via ``jax.lax.ppermute``;
* the last stage's outputs are collected microbatch by microbatch; the
  pipeline drains after M + S - 1 ticks.  Bubble fraction is the usual
  (S-1)/(M+S-1).

Each device executes the SAME program (ticks where a stage has no work
process garbage that is never read — static shapes, no divergence), which
is exactly how production JAX pipelines (praxis/MaxText) express GPipe.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(mesh: Mesh, stage_axis: str, n_microbatches: int,
                   stage_fn: Callable, params, x: jax.Array) -> jax.Array:
    """Run ``y = stage_fn(stage_params, x)`` through all S stages.

    params: pytree whose leaves are (L, ...) stacked per-layer arrays,
            sharded P(stage_axis, ...) — each device sees (L/S, ...);
    stage_fn(local_params, x) -> x applies ONE STAGE's layers;
    x: (B, ...) global batch, replicated across ``stage``.
    Returns y: (B, ...) (value produced by the final stage, replicated).
    """
    S = mesh.shape[stage_axis]
    M = n_microbatches
    B = x.shape[0]
    assert B % M == 0, (B, M)
    mb = B // M

    pspecs = jax.tree.map(lambda _: P(stage_axis), params)

    def body(p_loc, x_rep):
        sid = jax.lax.axis_index(stage_axis)
        perm = [(i, (i + 1) % S) for i in range(S)]
        mbs = x_rep.reshape(M, mb, *x_rep.shape[1:])
        outs = jnp.zeros_like(mbs)
        carry = jnp.zeros_like(mbs[0])

        for t in range(M + S - 1):
            # stage 0 injects microbatch t from the replicated input
            inject = mbs[jnp.minimum(t, M - 1)]
            x_in = jnp.where(sid == 0, inject, carry)
            y = stage_fn(p_loc, x_in)
            # the last stage stores microbatch (t - (S-1)) when valid
            m_out = t - (S - 1)
            store = (sid == S - 1) & (0 <= m_out) & (m_out < M)
            idx = jnp.clip(m_out, 0, M - 1)
            outs = jnp.where(store,
                             outs.at[idx].set(y),
                             outs)
            # pass activations down the pipe (last->first wraps; the
            # wrapped value is never read by stage 0, which injects)
            carry = jax.lax.ppermute(y, stage_axis, perm)

        # the final stage holds the real outputs; broadcast to all stages
        # via psum of a masked copy (replicated output spec)
        outs = jnp.where(sid == S - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, stage_axis)
        return outs.reshape(B, *x_rep.shape[1:])

    return jax.shard_map(body, mesh=mesh, in_specs=(pspecs, P()),
                         out_specs=P(), check_vma=False)(params, x)


def stack_mlp_params(key, n_layers: int, d: int, dtype=jnp.float32):
    """Demo/test model: L × (dense + relu) with residual."""
    ks = jax.random.split(key, n_layers)
    w = jnp.stack([jax.random.normal(k, (d, d), dtype) * (0.5 / d ** 0.5)
                   for k in ks])
    b = jnp.zeros((n_layers, d), dtype)
    return {"w": w, "b": b}


def mlp_stage_fn(p_loc, x):
    """Apply this stage's L/S layers sequentially (scan keeps HLO flat)."""
    def layer(h, wb):
        w, b = wb
        return h + jax.nn.relu(h @ w + b), None

    y, _ = jax.lax.scan(layer, x, (p_loc["w"], p_loc["b"]))
    return y


def mlp_reference(params, x):
    def layer(h, wb):
        w, b = wb
        return h + jax.nn.relu(h @ w + b), None

    y, _ = jax.lax.scan(layer, x, (params["w"], params["b"]))
    return y

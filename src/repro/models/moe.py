"""Mixture-of-Experts FFN with top-k token-choice routing.

Dispatch is the capacity-bounded scatter formulation (GShard semantics,
static shapes, no (T, E, C) one-hot cube), GROUPED for distribution: tokens
are reshaped (G, T/G, d) where G = the number of data shards, so ranking /
capacity / scatter are all *local to a group* — no cross-device cumsum, no
global-token buffer.  Per group, tokens are ranked within their expert via
a cumulative-sum position, scattered into a (G, E, C, d) buffer, processed
by batched expert GEMMs, and combined back weighted by their gate.
Overflowing tokens are dropped (classic Switch behavior; the aux loss
pushes the router toward balance).

Sharding strategy (DESIGN.md §5): when n_experts %% tp == 0 the E dim of
the dispatch buffer shards over ``model`` (expert parallelism) while G
shards over ``data`` — each (data, model) device owns its group's tokens
for its experts, and the only communication is the output all-reduce over
``model`` that TP already pays.  Otherwise (granite: 40 experts on a
16-way axis) the expert FFN hidden dim shards over ``model`` (tensor
parallelism inside experts).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import Dtype, dense


def init_moe(key, d_model: int, d_ff: int, n_experts: int, dtype=Dtype) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d_model)
    return {
        "router": jax.random.normal(k1, (d_model, n_experts), jnp.float32) * s,
        "w_gate": jax.random.normal(k2, (n_experts, d_model, d_ff), dtype) * s,
        "w_up": jax.random.normal(k3, (n_experts, d_model, d_ff), dtype) * s,
        "w_down": jax.random.normal(k4, (n_experts, d_ff, d_model), dtype)
        * (1.0 / math.sqrt(d_ff)),
    }


def _constrain(x: jax.Array, spec) -> jax.Array:
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*spec))


def _local_dispatch_ffn(p_loc: dict, x_loc: jax.Array, *, n_experts: int,
                        top_k: int, capacity_factor: float,
                        e_base, e_local: int, dp_axes_t, tp_axis
                        ) -> tuple[jax.Array, jax.Array]:
    """Per-device MoE body (runs inside shard_map).

    ``x_loc`` (Tl, d) is this data-shard's tokens (replicated over the
    model axis); ``p_loc`` holds this device's expert slice.  Each device
    dispatches ONLY to its ``e_local`` experts [e_base, e_base+e_local)
    — a purely local scatter — computes the expert GEMMs, weights the
    outputs, and the caller psums partial outputs over the model axis.
    Capacity is per (data-shard, expert): C = cf·k·Tl/E.
    """
    Tl, d = x_loc.shape
    E = n_experts                     # dispatch id space (may be padded)
    E_route = p_loc["router"].shape[-1]  # real experts the router scores
    C = max(1, int(capacity_factor * top_k * Tl / E_route))

    logits = jnp.dot(x_loc.astype(jnp.float32), p_loc["router"])
    gates = jax.nn.softmax(logits, axis=-1)                       # (Tl, Er)
    top_vals, top_idx = jax.lax.top_k(gates, top_k)               # (Tl, k)
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)

    # aux loss over GLOBAL tokens: psum the local sums over the data axes
    me_l = jnp.sum(gates, axis=0)
    ce_l = jnp.sum(jax.nn.one_hot(top_idx[:, 0], E_route), axis=0)
    cnt = jnp.asarray(Tl, jnp.float32)
    if dp_axes_t:
        me_l = jax.lax.psum(me_l, dp_axes_t)
        ce_l = jax.lax.psum(ce_l, dp_axes_t)
        cnt = jax.lax.psum(cnt, dp_axes_t)
    aux = E_route * jnp.sum((me_l / cnt) * (ce_l / cnt))

    # rank each (token, slot) within its (global) expert queue — local
    flat_e = top_idx.reshape(-1)                                  # (Tk,)
    flat_g = top_vals.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)           # (Tk, E)
    pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
    keep = pos < C

    # route only to this device's experts; everything else -> overflow row
    rel_e = flat_e - e_base
    mine = keep & (rel_e >= 0) & (rel_e < e_local)
    rel_e_c = jnp.where(mine, rel_e, 0)
    slot = jnp.where(mine, pos, C)

    tok = jnp.repeat(jnp.arange(Tl), top_k)
    buf = jnp.zeros((e_local, C + 1, d), x_loc.dtype)
    buf = buf.at[rel_e_c, slot].add(x_loc[tok])                   # local!
    xin = buf[:, :C, :]                                           # (El, C, d)

    cpu_safe = jax.default_backend() == "cpu"
    cast = (lambda a: a.astype(jnp.float32)) if cpu_safe else (lambda a: a)
    g = jnp.einsum("ecd,edf->ecf", cast(xin), cast(p_loc["w_gate"]),
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", cast(xin), cast(p_loc["w_up"]),
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x_loc.dtype)
    y = jnp.einsum("ecf,efd->ecd", cast(h), cast(p_loc["w_down"]),
                   preferred_element_type=jnp.float32).astype(x_loc.dtype)

    y_pad = jnp.concatenate([y, jnp.zeros((e_local, 1, d), y.dtype)],
                            axis=1)
    picked = y_pad[rel_e_c, slot]                                 # (Tk, d)
    picked = picked * (flat_g[:, None] * mine[:, None]).astype(picked.dtype)
    out_partial = jnp.sum(picked.reshape(Tl, top_k, d), axis=1)
    # combine expert shards: the ONE collective the MoE layer pays
    out = jax.lax.psum(out_partial, tp_axis)
    return out, aux


def moe_ffn_sharded(p: dict, x: jax.Array, *, n_experts: int, top_k: int,
                    capacity_factor: float, mesh, dp_spec, tp_axis,
                    ep_pad: bool = False) -> tuple[jax.Array, jax.Array]:
    """shard_map MoE: explicit local dispatch + one psum.  GSPMD cannot
    partition the batched scatter/gather of token dispatch (it all-gathers
    a (G, T·k/G, d) buffer — 32 GiB/device at phi3.5-moe's train shape),
    so the dispatch is written per-device instead (DESIGN.md §5).

    Expert placement: E %% tp == 0 -> expert parallelism (each model shard
    owns E/tp experts); otherwise every shard holds all experts with the
    FFN hidden dim sharded (TP inside experts) and the psum reduces the
    partial down-projections.  ``ep_pad`` (§Perf, granite) instead PADS the
    expert dim up to a multiple of tp and uses expert parallelism: +20%
    weight memory for dummy experts that never receive tokens, in exchange
    for whole-d_ff expert GEMMs and a tp×-smaller dispatch buffer.
    """
    E = n_experts
    tp = mesh.shape[tp_axis]
    if ep_pad and E % tp != 0:
        E_pad = -(-E // tp) * tp
        pad = E_pad - E

        def pad_e(w):
            return jnp.concatenate(
                [w, jnp.zeros((pad,) + w.shape[1:], w.dtype)], axis=0)

        p = {"router": p["router"],
             "w_gate": pad_e(p["w_gate"]),
             "w_up": pad_e(p["w_up"]),
             "w_down": pad_e(p["w_down"])}
        # router still scores only the E real experts; dispatch uses the
        # padded id space so each shard owns E_pad/tp whole experts.
        E = E_pad
    ep = E % tp == 0
    dp_axes_t = dp_spec if isinstance(dp_spec, tuple) else (
        (dp_spec,) if dp_spec else ())
    # tiny token counts (single-lane decode) cannot shard over data:
    # replicate the tokens instead — every data shard runs the same
    # dispatch, the tp psum still combines expert shards correctly.
    dp_total = 1
    for a in dp_axes_t:
        dp_total *= mesh.shape[a]
    if x.shape[0] % max(dp_total, 1) != 0:
        dp_spec = None
        dp_axes_t = ()

    if ep:
        pspecs = {"router": P(None, None),
                  "w_gate": P(tp_axis, None, None),
                  "w_up": P(tp_axis, None, None),
                  "w_down": P(tp_axis, None, None)}
        e_local = E // tp
    else:
        pspecs = {"router": P(None, None),
                  "w_gate": P(None, None, tp_axis),
                  "w_up": P(None, None, tp_axis),
                  "w_down": P(None, tp_axis, None)}
        e_local = E

    xspec = P(dp_spec, None)

    def body(p_loc, x_loc):
        e_base = (jax.lax.axis_index(tp_axis) * e_local) if ep else 0
        return _local_dispatch_ffn(
            p_loc, x_loc, n_experts=E, top_k=top_k,
            capacity_factor=capacity_factor, e_base=e_base,
            e_local=e_local, dp_axes_t=dp_axes_t, tp_axis=tp_axis)

    out, aux = jax.shard_map(
        body, mesh=mesh, in_specs=(pspecs, xspec),
        out_specs=(xspec, P()), check_vma=False)(p, x)
    return out, aux


def moe_ffn(p: dict, x: jax.Array, *, n_experts: int, top_k: int,
            capacity_factor: float = 1.25, num_groups: int = 1,
            dp_spec=None, tp_axis=None, mesh=None, ep_pad: bool = False
            ) -> tuple[jax.Array, jax.Array]:
    """x (T, d) -> (out (T, d), aux_loss scalar).  T = flattened tokens.

    With ``mesh`` + ``tp_axis`` set, dispatch runs through the shard_map
    path (explicit local scatter, one psum).  Otherwise (CPU tests) the
    grouped pjit-free path below runs; ``num_groups`` G must divide T
    (local capacity C = cf·k·T/(G·E)).
    """
    if mesh is not None and tp_axis is not None:
        return moe_ffn_sharded(p, x, n_experts=n_experts, top_k=top_k,
                               capacity_factor=capacity_factor, mesh=mesh,
                               dp_spec=dp_spec, tp_axis=tp_axis,
                               ep_pad=ep_pad)
    T, d = x.shape
    E = n_experts
    G = num_groups if num_groups > 0 and T % num_groups == 0 else 1
    Tg = T // G
    C = max(1, int(capacity_factor * top_k * Tg / E))

    ep = tp_axis is not None and (E % 16 == 0)  # expert-parallel eligible
    xg = x.reshape(G, Tg, d)
    if tp_axis is not None:
        xg = _constrain(xg, (dp_spec, None, None))

    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32), p["router"])
    gates = jax.nn.softmax(logits, axis=-1)                  # (G, Tg, E)
    top_vals, top_idx = jax.lax.top_k(gates, top_k)          # (G, Tg, k)
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)

    # load-balancing aux loss (Switch): E * sum_e f_e * P_e, global means
    me = jnp.mean(gates, axis=(0, 1))                        # (E,)
    ce = jnp.mean(jax.nn.one_hot(top_idx[..., 0], E), axis=(0, 1))
    aux = E * jnp.sum(me * ce)

    # per-group expert queues: rank each (token, slot) within its expert
    flat_e = top_idx.reshape(G, Tg * top_k)                  # (G, Tk)
    flat_g = top_vals.reshape(G, Tg * top_k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)      # (G, Tk, E)
    pos_in_e = jnp.cumsum(onehot, axis=1) * onehot           # rank+1, local
    pos = jnp.sum(pos_in_e, axis=-1) - 1                     # (G, Tk)
    keep = pos < C
    slot = jnp.where(keep, pos, C)                           # drop -> C

    # scatter tokens into (G, E, C+1, d); row C is the overflow bin.
    # Every (G, Tk, d) intermediate is pinned to the data axis — without
    # the constraints the partitioner replicates the gather/scatter pair
    # (a 32 GiB/device temp at phi3.5-moe's train shape).
    espec = (tp_axis if ep else None) if tp_axis is not None else None
    tok = jnp.repeat(jnp.arange(Tg), top_k)                  # (Tk,)
    src = xg[:, tok, :]                                      # (G, Tk, d)
    gidx = jnp.arange(G)[:, None]
    if tp_axis is not None:
        src = _constrain(src, (dp_spec, None, None))
    buf = jnp.zeros((G, E, C + 1, d), x.dtype)
    if tp_axis is not None:
        buf = _constrain(buf, (dp_spec, espec, None, None))
    buf = buf.at[gidx, flat_e, slot].add(src)
    if tp_axis is not None:
        buf = _constrain(buf, (dp_spec, espec, None, None))
    xin = buf[:, :, :C, :]                                   # (G, E, C, d)
    if tp_axis is not None:
        xin = _constrain(xin, (dp_spec, espec, None, None))

    # XLA:CPU's DotThunk cannot execute this batched bf16×bf16->f32 dot
    # (TPU MXU does it natively).  On the CPU test path (no mesh wiring)
    # upcast the operands — numerically equivalent, f32 accumulate either
    # way; the dry-run always sets tp_axis so its HLO stays bf16.
    cpu_safe = tp_axis is None and jax.default_backend() == "cpu"
    cast = (lambda a: a.astype(jnp.float32)) if cpu_safe else (lambda a: a)
    g = jnp.einsum("gecd,edf->gecf", cast(xin), cast(p["w_gate"]),
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("gecd,edf->gecf", cast(xin), cast(p["w_up"]),
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    y = jnp.einsum("gecf,efd->gecd", cast(h), cast(p["w_down"]),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if tp_axis is not None:
        y = _constrain(y, (dp_spec, espec, None, None))

    # gather back: token t sums gate * y[g, e, slot] over its kept slots
    y_pad = jnp.concatenate([y, jnp.zeros((G, E, 1, d), y.dtype)], axis=2)
    if tp_axis is not None:
        y_pad = _constrain(y_pad, (dp_spec, espec, None, None))
    picked = y_pad[gidx, flat_e, slot]                       # (G, Tk, d)
    if tp_axis is not None:
        picked = _constrain(picked, (dp_spec, None, None))
    picked = picked * flat_g[..., None].astype(picked.dtype) * \
        keep[..., None].astype(picked.dtype)
    out = jnp.sum(picked.reshape(G, Tg, top_k, d), axis=2)   # (G, Tg, d)
    if tp_axis is not None:
        out = _constrain(out, (dp_spec, None, None))
    return out.reshape(T, d), aux

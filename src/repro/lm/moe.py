"""Mixture-of-Experts channel mixing: reference + pod-scale dispatch.

Four interchangeable implementations (MoEConfig.impl; "auto" picks by mesh):

* ``ref``     — dense all-experts einsum, gates zeroed outside top-k.  Exact
                (no capacity drops); O(E) FLOPs — tests / single device only.
                The correctness oracle for the distributed paths.
* ``ep_psum`` — experts sharded over 'model'.  Tokens enter replicated over
                'model' (GSPMD all-gathers the sequence shards at the
                shard_map boundary); every rank computes its own experts'
                contribution for all tokens; psum combines.  Simple, robust;
                collective volume = AG(x) + AR(y).  The BASELINE at scale.
* ``ep_a2a``  — tokens stay fully sharded; each rank routes its own tokens,
                all_to_all sends capacity buffers to expert owners and back.
                Collective volume ~ 2 * k * capacity_factor * routed tokens —
                the beyond-paper optimization (EXPERIMENTS.md §Perf).
* ``tp``      — for num_experts < model-axis size (grok-1: 8e over 16):
                expert d_ff sharded over 'model' (Megatron row/col parallel),
                local capacity dispatch, psum_scatter combine.

All distributed paths use capacity-based dispatch (GShard-style token
dropping at ``capacity_factor``); tests verify ep/tp == ref exactly when
capacity is generous and within-tolerance under realistic factors.

Weights arrive FSDP-sharded (expert dim over 'model', d over the data axes —
parallel/sharding.py) and are all-gathered over the data axes on use inside
the shard_map body; XLA reuses the gather across the three expert matrices'
consumers, and its transpose is the reduce-scatter of expert grads (ZeRO-3
semantics for the 1T-param architectures).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.lm.config import ArchConfig, MoEConfig

__all__ = ["moe_ffn", "router_aux_loss", "pick_impl", "dp_axes"]


def dp_axes(mesh: Optional[Mesh]) -> Tuple[str, ...]:
    if mesh is None:
        return ()
    return tuple(n for n in mesh.axis_names if n in ("pod", "data"))


def pick_impl(cfg: ArchConfig, mesh: Optional[Mesh], decode: bool) -> str:
    m = cfg.moe
    assert m is not None
    if m.impl != "auto":
        return m.impl
    if mesh is None or "model" not in mesh.axis_names or mesh.shape["model"] == 1:
        return "ref"
    if m.num_experts % mesh.shape["model"] != 0:
        return "tp"
    # a2a needs the sequence axis shardable over 'model'; decode has S == 1
    return "ep_psum" if decode else "ep_a2a"


def _act(cfg: ArchConfig, g, u):
    if cfg.act == "swiglu":
        return jax.nn.silu(g) * u
    return jax.nn.gelu(g) * u


def _router(x, wr, m: MoEConfig):
    """x (n, d) -> top-k (gates (n,k) f32 renormalized, idx (n,k) i32, probs)."""
    logits = (x.astype(jnp.float32) @ wr.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = lax.top_k(probs, m.top_k)
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-9)
    return gates, idx, probs


def router_aux_loss(probs: jax.Array, idx: jax.Array, num_experts: int) -> jax.Array:
    """Switch-style load-balancing loss: E * <f_e * p_e>."""
    me = probs.mean(axis=tuple(range(probs.ndim - 1)))          # <p_e>
    onehot = jax.nn.one_hot(idx, num_experts, dtype=jnp.float32)
    fe = onehot.sum(-2).mean(axis=tuple(range(probs.ndim - 1)))  # fraction routed
    fe = fe / jnp.maximum(fe.sum(), 1e-9)
    return num_experts * jnp.sum(me * fe)


# ---------------------------------------------------------------------------
# Capacity dispatch helpers (per-rank local, static shapes).
# ---------------------------------------------------------------------------


def _dispatch(x2, idx, gates, e_lo: int, e_hi: int, cap: int):
    """Scatter tokens into per-expert capacity buffers.

    x2 (n, d); idx/gates (n, k).  Experts [e_lo, e_hi) are handled here.
    Returns buf (E_loc, cap, d), and (slot_e, slot_c, keep, flat_t, flat_g)
    needed for the combine gather.
    """
    n, k = idx.shape
    E_loc = e_hi - e_lo
    flat_e = idx.reshape(-1) - e_lo                       # (n*k,)
    flat_t = jnp.repeat(jnp.arange(n), k)
    flat_g = gates.reshape(-1)
    valid = (flat_e >= 0) & (flat_e < E_loc)
    sort_key = jnp.where(valid, flat_e, E_loc)
    order = jnp.argsort(sort_key)                         # stable
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    sv = valid[order]
    starts = jnp.searchsorted(jnp.where(sv, se, E_loc), jnp.arange(E_loc))
    pos = jnp.arange(n * k) - starts[jnp.clip(se, 0, E_loc - 1)]
    keep = sv & (pos < cap)
    be = jnp.where(keep, se, 0)
    bc = jnp.where(keep, pos, cap)                        # cap -> dropped
    buf = jnp.zeros((E_loc, cap + 1, x2.shape[1]), x2.dtype)
    buf = buf.at[be, bc].add(x2[st] * keep[:, None].astype(x2.dtype))
    return buf[:, :cap], (be, bc, keep, st, sg)


def _combine(y_buf, meta, n: int):
    """Gather expert outputs back to token order, weighted by gates."""
    be, bc, keep, st, sg = meta
    y_buf = jnp.pad(y_buf, ((0, 0), (0, 1), (0, 0)))      # slot 'cap' = zeros
    vals = y_buf[be, bc] * (sg * keep)[:, None].astype(y_buf.dtype)
    out = jnp.zeros((n, y_buf.shape[-1]), y_buf.dtype)
    return out.at[st].add(vals)


def _expert_ffn(buf, wg, wu, wd, cfg: ArchConfig):
    """(E, cap, d) x (E, d, f) -> (E, cap, d)."""
    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    return jnp.einsum("ecf,efd->ecd", _act(cfg, g, u), wd)


def _gathered_weights(wg, wu, wd, axes: Tuple[str, ...], down_axis: int = 1):
    """All-gather FSDP-sharded expert weights over the data axes on use.

    ep modes shard dim 1 of all three (d for gate/up, f for down); tp mode
    shards d, which is dim 2 of w_down (``down_axis=2``)."""
    if not axes:
        return wg, wu, wd
    ag = lambda w, ax: lax.all_gather(w, axes, axis=ax, tiled=True)
    return ag(wg, 1), ag(wu, 1), ag(wd, down_axis)


def _replicated_aux(aux, mesh: Mesh):
    return lax.pmean(aux, tuple(mesh.axis_names))


# ---------------------------------------------------------------------------
# Implementations.
# ---------------------------------------------------------------------------


def _moe_ref(x, p, cfg: ArchConfig):
    """Dense reference: every expert on every token (tests only)."""
    m = cfg.moe
    B, S, d = x.shape
    x2 = x.reshape(-1, d)
    gates, idx, probs = _router(x2, p["router"], m)
    h = jnp.einsum("nd,edf->nef", x2, p["w_gate"])
    u = jnp.einsum("nd,edf->nef", x2, p["w_up"])
    y_all = jnp.einsum("nef,efd->ned", _act(cfg, h, u), p["w_down"])
    dense_gates = jnp.zeros((x2.shape[0], m.num_experts), jnp.float32)
    dense_gates = dense_gates.at[jnp.arange(x2.shape[0])[:, None], idx].add(gates)
    y = jnp.einsum("ned,ne->nd", y_all.astype(jnp.float32), dense_gates)
    aux = router_aux_loss(probs, idx, m.num_experts)
    return y.reshape(B, S, d).astype(x.dtype), aux


def _moe_ep_psum(x, p, cfg: ArchConfig, mesh: Mesh):
    """Experts over 'model'; tokens replicated over 'model' inside."""
    m = cfg.moe
    ep = mesh.shape["model"]
    E_loc = m.num_experts // ep
    dp = dp_axes(mesh)
    B, S, d = x.shape
    n_loc = (B // int(np.prod([mesh.shape[a] for a in dp]))) * S
    cap = max(1, math.ceil(n_loc * m.top_k / m.num_experts * m.capacity_factor))

    def body(x_loc, wr, wg, wu, wd):
        rank = lax.axis_index("model")
        bl, sl, _ = x_loc.shape
        x2 = x_loc.reshape(-1, d)
        gates, idx, probs = _router(x2, wr, m)
        wg, wu, wd = _gathered_weights(wg, wu, wd, dp)
        # local expert ids are global ids offset by rank*E_loc
        buf, meta = _dispatch(x2, idx - rank * E_loc, gates, 0, E_loc, cap)
        y_buf = _expert_ffn(buf, wg, wu, wd, cfg)
        y = _combine(y_buf, meta, x2.shape[0]).astype(x.dtype)
        y = lax.psum(y, "model")
        aux = router_aux_loss(probs, idx, m.num_experts)
        return y.reshape(bl, sl, d), _replicated_aux(aux, mesh)

    in_specs = (P(dp, None, None), P(None, None),
                P("model", dp, None), P("model", dp, None), P("model", dp, None))
    out_specs = (P(dp, None, None), P())
    y, aux = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)(
        x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y, aux


def _moe_ep_a2a(x, p, cfg: ArchConfig, mesh: Mesh):
    """Tokens fully sharded (seq over 'model'); all_to_all expert dispatch."""
    m = cfg.moe
    ep = mesh.shape["model"]
    E_loc = m.num_experts // ep
    dp = dp_axes(mesh)
    B, S, d = x.shape
    n_dp = int(np.prod([mesh.shape[a] for a in dp]))
    n_loc = (B // n_dp) * (S // ep)
    cap = max(1, math.ceil(n_loc * m.top_k / m.num_experts * m.capacity_factor))

    def body(x_loc, wr, wg, wu, wd):
        bl, sl, _ = x_loc.shape
        x2 = x_loc.reshape(-1, d)
        gates, idx, probs = _router(x2, wr, m)
        wg, wu, wd = _gathered_weights(wg, wu, wd, dp)
        # capacity buffers for ALL experts, grouped by owner rank
        buf, meta = _dispatch(x2, idx, gates, 0, m.num_experts, cap)
        buf = buf.reshape(ep, E_loc * cap, d)
        recv = lax.all_to_all(buf, "model", split_axis=0, concat_axis=0,
                              tiled=True)                  # (ep, E_loc*cap, d)
        recv = recv.reshape(ep, E_loc, cap, d).transpose(1, 0, 2, 3)
        recv = recv.reshape(E_loc, ep * cap, d)            # my experts, all srcs
        y_buf = _expert_ffn(recv, wg, wu, wd, cfg)
        y_buf = y_buf.reshape(E_loc, ep, cap, d).transpose(1, 0, 2, 3)
        y_buf = y_buf.reshape(ep, E_loc * cap, d)
        back = lax.all_to_all(y_buf, "model", split_axis=0, concat_axis=0,
                              tiled=True)
        back = back.reshape(m.num_experts, cap, d)
        y = _combine(back, meta, x2.shape[0]).astype(x.dtype)
        aux = router_aux_loss(probs, idx, m.num_experts)
        return y.reshape(bl, sl, d), _replicated_aux(aux, mesh)

    in_specs = (P(dp, "model", None), P(None, None),
                P("model", dp, None), P("model", dp, None), P("model", dp, None))
    out_specs = (P(dp, "model", None), P())
    y, aux = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)(
        x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y, aux


def _moe_tp(x, p, cfg: ArchConfig, mesh: Mesh):
    """num_experts < model axis: d_ff tensor-parallel, local dispatch."""
    m = cfg.moe
    dp = dp_axes(mesh)
    B, S, d = x.shape
    n_dp = int(np.prod([mesh.shape[a] for a in dp]))
    n_loc = (B // n_dp) * S
    cap = max(1, math.ceil(n_loc * m.top_k / m.num_experts * m.capacity_factor))

    def body(x_loc, wr, wg, wu, wd):
        bl, sl, _ = x_loc.shape
        x2 = x_loc.reshape(-1, d)
        gates, idx, probs = _router(x2, wr, m)
        wg, wu, wd = _gathered_weights(wg, wu, wd, dp, down_axis=2)
        buf, meta = _dispatch(x2, idx, gates, 0, m.num_experts, cap)
        y_buf = _expert_ffn(buf, wg, wu, wd, cfg)          # f is local shard
        y = _combine(y_buf, meta, x2.shape[0]).astype(x.dtype)
        y = lax.psum(y, "model")                           # row-parallel sum
        aux = router_aux_loss(probs, idx, m.num_experts)
        return y.reshape(bl, sl, d), _replicated_aux(aux, mesh)

    in_specs = (P(dp, None, None), P(None, None),
                P(None, dp, "model"), P(None, dp, "model"), P(None, "model", dp))
    out_specs = (P(dp, None, None), P())
    y, aux = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)(
        x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y, aux


def moe_ffn(x: jax.Array, p: dict, cfg: ArchConfig,
            mesh: Optional[Mesh] = None, *, decode: bool = False):
    """Routed experts (+ shared experts handled by the caller).

    Returns (y, aux_loss)."""
    impl = pick_impl(cfg, mesh, decode)
    if impl == "ref":
        return _moe_ref(x, p, cfg)
    if impl == "ep_psum":
        return _moe_ep_psum(x, p, cfg, mesh)
    if impl == "ep_a2a":
        return _moe_ep_a2a(x, p, cfg, mesh)
    if impl == "tp":
        return _moe_tp(x, p, cfg, mesh)
    raise ValueError(impl)

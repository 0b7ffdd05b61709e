"""Mixture-of-Experts FFN with two interchangeable distribution strategies.

``moe_impl = "gather"`` (pjit baseline, paper-era standard):
  top-k routing -> sort token-slots by expert -> capacity-bounded
  scatter into an (E, C, D) per-expert buffer -> batched expert matmuls
  -> scatter-add combine.  Pure pjit: XLA SPMD inserts the (expensive)
  cross-shard gathers/reduces.  Compiles everywhere; its collective cost
  is the §Perf baseline.

``moe_impl = "alltoall"`` (shard_map optimized path):
  tokens are sharded over (dp axes x model); each shard routes its own
  tokens and exchanges expert buckets with explicit ``jax.lax.all_to_all``
  over the model axis (true expert parallelism); each device computes only
  its local expert slots over tokens from every peer.

``moe_replicas > 1`` stores physical copies of each expert
(params-level; round-robin routing by token parity) so EP stays uniform
when n_experts < model-axis size (grok: 8 experts x 2 replicas on a
16-wide axis).  Replicas start identical and diverge under training —
an intentional capacity/load-balance variant.

Both paths drop overflow tokens (capacity factor), add the standard
load-balance auxiliary loss, and weight top-k combine by softmax gates.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.layers import mlp, sub

__all__ = ["moe_ffn"]


def _top_k_gates(logits: jax.Array, k: int):
    """softmax-renormalized top-k gates. logits [T, E] -> (gates [T,k], idx [T,k])."""
    gates, idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(gates, axis=-1)
    return gates, idx


def _aux_loss(logits: jax.Array, idx: jax.Array, n_experts: int) -> jax.Array:
    """Switch-style load-balance loss: E * <fraction routed> . <router prob>."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [T, E]
    me = jnp.mean(probs, axis=0)
    one_hot = jax.nn.one_hot(idx[:, 0], n_experts, dtype=jnp.float32)
    ce = jnp.mean(one_hot, axis=0)
    return n_experts * jnp.sum(me * ce)


def _phys_idx(idx: jax.Array, replicas: int):
    """Map logical expert ids -> physical slots (round-robin by token)."""
    if replicas == 1:
        return idx
    T, k = idx.shape
    rep = (jnp.arange(T)[:, None] + jnp.arange(k)[None, :]) % replicas
    return idx * replicas + rep


def _dispatch_indices(idx: jax.Array, T: int, k: int, E: int, C: int):
    """Routing bookkeeping shared by both impls.

    Returns (slot_token [T*k], slot_expert [T*k], rank_in_expert [T*k],
             keep [T*k]) with slots sorted by expert.
    """
    slot_expert = idx.reshape(-1)                       # [T*k]
    order = jnp.argsort(slot_expert, stable=True)       # slots grouped by expert
    slot_expert_s = slot_expert[order]
    slot_token_s = (jnp.arange(T * k) // k)[order]
    first = jnp.searchsorted(slot_expert_s, jnp.arange(E), side="left")
    rank = jnp.arange(T * k) - first[slot_expert_s]
    keep = rank < C
    return slot_token_s, slot_expert_s, rank, keep


def _expert_mlp(cfg: ModelConfig, xe: jax.Array, w_gate, w_up, w_down):
    """xe [E, C, D] through per-expert gated MLP."""
    dt = cfg.compute_dtype
    g = jnp.einsum("ecd,edf->ecf", xe, w_gate.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xe, w_up.astype(dt))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w_down.astype(dt))


def _route_and_bucket(cfg: ModelConfig, x2d, router, E_phys: int, C: int):
    """Shared per-(global or local)-view routing: returns xe, combine info."""
    dt = cfg.compute_dtype
    T, D = x2d.shape
    k = cfg.top_k
    logits = x2d @ router.astype(dt)
    gates, idx = _top_k_gates(logits, k)
    aux = _aux_loss(logits, idx, cfg.n_experts)
    idx_phys = _phys_idx(idx, cfg.moe_replicas)
    tok, exp, rank, keep = _dispatch_indices(idx_phys, T, k, E_phys, C)
    dest = exp * C + jnp.minimum(rank, C - 1)
    xe = jnp.zeros((E_phys * C, D), dt)
    xe = xe.at[dest].add(jnp.where(keep[:, None], x2d[tok], 0), mode="drop")
    gate_of_slot = gates.reshape(-1)[jnp.argsort(idx_phys.reshape(-1),
                                                 stable=True)]
    return xe, (tok, dest, keep, gate_of_slot), aux


def _combine(x2d_shape, dt, ye_flat, tok, dest, keep, gate_of_slot):
    y = jnp.zeros(x2d_shape, dt)
    return y.at[tok].add(
        jnp.where(keep[:, None], ye_flat[dest] * gate_of_slot[:, None], 0),
        mode="drop")


def _moe_gather(params: dict, cfg: ModelConfig, x2d: jax.Array):
    """pjit sort-gather-scatter formulation over the global token view."""
    T = x2d.shape[0]
    E_phys = cfg.n_experts * cfg.moe_replicas
    C = max(1, int(cfg.capacity_factor * T * cfg.top_k / E_phys))
    xe, (tok, dest, keep, gate), aux = _route_and_bucket(
        cfg, x2d, params["router"], E_phys, C)
    ye = _expert_mlp(cfg, xe.reshape(E_phys, C, -1),
                     params["w_gate"], params["w_up"], params["w_down"])
    y = _combine(x2d.shape, x2d.dtype, ye.reshape(E_phys * C, -1),
                 tok, dest, keep, gate)
    return y, aux


def _moe_alltoall(params: dict, cfg: ModelConfig, x2d: jax.Array,
                  mesh, dp_axes, ep_axis: str):
    """shard_map expert-parallel path with explicit all_to_all.

    Tokens are sharded over dp_axes + (ep_axis,): every device routes only
    its own token shard (no redundant routing across the model axis), then
    all_to_all over ep_axis moves expert buckets to their owners.
    """
    ep = mesh.shape[ep_axis]
    E_phys = cfg.n_experts * cfg.moe_replicas
    assert E_phys % ep == 0, (E_phys, ep, "pick moe_replicas so ep | E_phys")
    E_loc = E_phys // ep
    dt = cfg.compute_dtype

    def local(x_loc, router, w_gate, w_up, w_down):
        T_loc, D = x_loc.shape
        C_loc = max(1, int(cfg.capacity_factor * T_loc * cfg.top_k / E_phys))
        xe, (tok, dest, keep, gate), aux = _route_and_bucket(
            cfg, x_loc, router, E_phys, C_loc)
        # [ep, E_loc*C_loc, D] -> each device receives its experts' buckets
        # from every peer: [ep(peers)*E_loc*C_loc, D]
        xe = xe.reshape(ep, E_loc * C_loc, D)
        xe = jax.lax.all_to_all(xe, ep_axis, split_axis=0, concat_axis=0)
        xe = (xe.reshape(ep, E_loc, C_loc, D).transpose(1, 0, 2, 3)
                .reshape(E_loc, ep * C_loc, D))
        ye = _expert_mlp(cfg, xe, w_gate, w_up, w_down)
        ye = (ye.reshape(E_loc, ep, C_loc, D).transpose(1, 0, 2, 3)
                .reshape(ep, E_loc * C_loc, D))
        ye = jax.lax.all_to_all(ye, ep_axis, split_axis=0, concat_axis=0)
        y = _combine(x_loc.shape, x_loc.dtype, ye.reshape(E_phys * C_loc, D),
                     tok, dest, keep, gate)
        return y, aux[None]

    token_axes = tuple(dp_axes) + (ep_axis,)
    dp_spec = P(token_axes, None)
    y, aux = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(dp_spec, P(None, None), P(ep_axis, None, None),
                  P(ep_axis, None, None), P(ep_axis, None, None)),
        out_specs=(dp_spec, P(token_axes)),
        check_vma=False,
    )(x2d, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])
    return y, jnp.mean(aux)


def moe_ffn(params: dict, cfg: ModelConfig, x: jax.Array, *,
            mesh=None, dp_axes=None, ep_axis: str = "model"):
    """MoE FFN over [B, S, D]. Returns (y, aux_loss). Adds shared experts."""
    B, S, D = x.shape
    x2d = x.reshape(B * S, D)

    use_a2a = (cfg.moe_impl == "alltoall" and mesh is not None
               and ep_axis in mesh.shape
               and (cfg.n_experts * cfg.moe_replicas) % mesh.shape[ep_axis] == 0
               and (B * S) % (mesh.shape[ep_axis] *
                              max(1, __import__("math").prod(
                                  mesh.shape[a] for a in (dp_axes or ())))) == 0)
    if use_a2a:
        y2d, aux = _moe_alltoall(params, cfg, x2d, mesh, dp_axes or (), ep_axis)
    else:
        y2d, aux = _moe_gather(params, cfg, x2d)

    y = y2d.reshape(B, S, D)
    if cfg.n_shared_experts:
        y = y + mlp(sub(params, "shared"), cfg, x)
    return y, aux

"""Expert parallelism — distributed mixture-of-experts over a mesh axis.

The reference's ``MixtureTable`` (nn/MixtureTable.scala:221) is a
single-device soft mixture; distributed EP (experts sharded across chips,
tokens routed with all-to-all over ICI) is absent (SURVEY.md §2.9).  This
module provides both pieces TPU-first:

- ``top1_gating``: softmax router with capacity-bounded top-1 dispatch
  (tokens over capacity are dropped, combine weights renormalized);
- ``moe_apply``: shard_map'd expert layer — each rank holds ``experts/P``
  expert MLPs; dispatched tokens travel rank->rank with ``lax.all_to_all``
  (the EP all-to-all), experts run batched on the MXU, results return with
  the inverse all-to-all and are combined by gate weight.

Dense-dispatch formulation (one-hot matmuls) keeps shapes static for XLA.
"""
from __future__ import annotations

from functools import partial

import jax

from jax import shard_map
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def expert_capacity(n_tokens: int, n_experts: int,
                    capacity_factor: float) -> int:
    """Per-expert token capacity — ONE formula shared by every MoE front
    door (moe_apply, moe_apply_sharded_tokens, nn.MoE), so the same
    capacity_factor drops the same tokens everywhere."""
    return max(int(capacity_factor * n_tokens / n_experts), 1)


def top1_gating(logits, n_experts: int, capacity: int):
    """logits: (T, E). Returns (dispatch (T, E, C) one-hot, combine
    (T, E, C) weights): token t goes to expert e at slot c."""
    gates = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(gates, axis=-1)             # (T,)
    gate_val = jnp.max(gates, axis=-1)                  # (T,)
    onehot = jax.nn.one_hot(expert_idx, n_experts)      # (T, E)
    # position of each token within its expert's queue
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot   # (T, E)
    in_cap = (pos < capacity) & (onehot > 0)
    slot = jnp.asarray(pos, jnp.int32)
    dispatch = (jax.nn.one_hot(slot, capacity) *
                in_cap[..., None].astype(jnp.float32))  # (T, E, C)
    combine = dispatch * gate_val[:, None, None]
    return dispatch, combine


def moe_apply(router_w, expert_w1, expert_b1, expert_w2, expert_b2, x,
              mesh: Mesh, axis: str = "expert", capacity_factor: float = 1.25):
    """Distributed top-1 MoE FFN.

    x: (T, D) tokens (replicated across the expert axis for routing; the
       data axis, if any, composes outside).
    expert_w1: (E, D, H), expert_b1: (E, H), expert_w2: (E, H, D),
    expert_b2: (E, D) — sharded over ``axis`` on dim 0.
    Returns (T, D).
    """
    n_expert = expert_w1.shape[0]
    n_rank = mesh.shape[axis]
    assert n_expert % n_rank == 0
    e_local = n_expert // n_rank
    t = x.shape[0]
    capacity = expert_capacity(t, n_expert, capacity_factor)

    def ranked(router_w, w1, b1, w2, b2, x):
        logits = x @ router_w                           # (T, E)
        dispatch, combine = top1_gating(logits, n_expert, capacity)
        # gather expert inputs: (E, C, D); every rank computes the full
        # dispatch (router replicated) then keeps its local experts
        expert_in = jnp.einsum("td,tec->ecd", x, dispatch)
        # reshape to (n_rank, e_local, C, D) and all-to-all is unnecessary
        # here because x is replicated across the axis — each rank slices
        # its experts directly (the all-to-all formulation matters when
        # tokens are data-sharded; see moe_apply_sharded_tokens)
        rank = lax.axis_index(axis)
        local_in = lax.dynamic_slice_in_dim(expert_in, rank * e_local,
                                            e_local, axis=0)  # (e_local, C, D)
        h = jax.nn.relu(jnp.einsum("ecd,edh->ech", local_in, w1) + b1[:, None])
        local_out = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None]  # (e_local, C, D)
        # scatter back: all experts' outputs = all_gather over the axis
        all_out = lax.all_gather(local_out, axis, axis=0, tiled=True)  # (E, C, D)
        return jnp.einsum("ecd,tec->td", all_out, combine)

    pspec_e = P(axis)
    f = shard_map(
        ranked, mesh=mesh,
        in_specs=(P(), pspec_e, pspec_e, pspec_e, pspec_e, P()),
        out_specs=P(), check_vma=False)  # replication holds post-all_gather
    return f(router_w, expert_w1, expert_b1, expert_w2, expert_b2, x)


def moe_apply_sharded_tokens(router_w, expert_w1, expert_b1, expert_w2,
                             expert_b2, x, mesh: Mesh,
                             data_axis: str = "data",
                             expert_axis: str = "expert",
                             capacity_factor: float = 1.25):
    """MoE with tokens sharded over ``data_axis`` AND experts over
    ``expert_axis``: the full EP pattern — local routing, then
    ``all_to_all`` over the expert axis carries each rank's dispatched
    tokens to the expert owners and back."""
    n_expert = expert_w1.shape[0]
    n_rank = mesh.shape[expert_axis]
    e_local = n_expert // n_rank

    def ranked(router_w, w1, b1, w2, b2, x_local):
        t_local = x_local.shape[0]
        capacity = expert_capacity(t_local, n_expert, capacity_factor)
        logits = x_local @ router_w
        dispatch, combine = top1_gating(logits, n_expert, capacity)
        expert_in = jnp.einsum("td,tec->ecd", x_local, dispatch)  # (E, C, D)
        # (n_rank, e_local, C, D) --all_to_all--> each rank receives the
        # chunks destined for ITS experts from every peer:
        # result (n_rank_src, e_local, C, D)
        grouped = expert_in.reshape(n_rank, e_local, capacity, -1)
        received = lax.all_to_all(grouped, expert_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        recv = received.reshape(n_rank * e_local, capacity, -1)  # src-major
        h = jax.nn.relu(jnp.einsum("scd,edh->sch",
                                   recv.reshape(n_rank, e_local, capacity, -1)
                                   .transpose(1, 0, 2, 3)
                                   .reshape(e_local, n_rank * capacity, -1),
                                   w1) + b1[:, None])
        out = jnp.einsum("sch,ehd->scd", h, w2) + b2[:, None]
        # undo: (e_local, n_rank*C, D) -> (n_rank, e_local, C, D) -> a2a back
        back = (out.reshape(e_local, n_rank, capacity, -1)
                .transpose(1, 0, 2, 3))
        returned = lax.all_to_all(back, expert_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        expert_out = returned.reshape(n_expert, capacity, -1)
        return jnp.einsum("ecd,tec->td", expert_out, combine)

    pspec_e = P(expert_axis)
    f = shard_map(
        ranked, mesh=mesh,
        in_specs=(P(), pspec_e, pspec_e, pspec_e, pspec_e, P(data_axis)),
        out_specs=P(data_axis), check_vma=False)
    return f(router_w, expert_w1, expert_b1, expert_w2, expert_b2, x)

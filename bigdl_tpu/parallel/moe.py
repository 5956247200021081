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

import numpy as np
import jax

from jax import shard_map
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from bigdl_tpu.nn.containers import kept


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


# ---------------------------------------------------------------------------
# dropless top-k routing over the experts one chip holds
# ---------------------------------------------------------------------------

def sigmoid_topk_routing(x, router_w, bias, top_k: int, route_norm: bool,
                         route_scale: float, norm_eps: float = 1e-20):
    """x: (T, D) -> (idx (T, k) int32 expert ids, weights (T, k) float32).
    Scores are sigmoid(x W) in true float32 (the product is D x E: free);
    the experts are the top k of score + bias, the bias entering the
    choice only; the weights are the chosen scores, divided by their sum +
    ``norm_eps`` where ``route_norm`` (afmoe and deepseek_v3 write 1e-20,
    lfm2_moe 1e-6), times ``route_scale``.

    The choice is made once: a ``Recompute`` around the caller keeps
    ``idx`` and its recomputation runs no top-k.  A chosen score is picked
    out of its token's E by compares (``take_along_axis``'s result bit for
    bit: one term of each sum is not zero): on the chip a gather of T x k
    scalars costs ~8 ns a scalar, forward, and a scalar scatter backward,
    where the compares fuse into a reduction."""
    logits = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(scores + lax.stop_gradient(bias), top_k)
    idx = kept(idx, "route_idx")
    chosen = idx[:, :, None] == jnp.arange(scores.shape[-1])
    weights = jnp.sum(jnp.where(chosen, scores[:, None, :], 0.0), axis=-1)
    if route_norm:
        # XLA joins a sum of sums into one sum over (k, E), which adds a
        # token's k scores in another order than a sum over k does and
        # moves the last digit: the barrier keeps the two sums apart
        weights = lax.optimization_barrier(weights)
        weights = weights / (weights.sum(axis=-1, keepdims=True) + norm_eps)
    return idx, weights * route_scale


def sort_assignments(idx, experts_held):
    """The (token, choice) assignments grouped by the expert held here.
    idx: (T, k) global expert ids; ``experts_held``: the distinct ids of
    the experts held, in local order.  Returns (order, sizes): ``order``
    (T*k,) lists the flat assignment indices (token * k + choice), held
    experts first in local order, the absent ones' last; ``sizes``
    (n_held,) counts each held expert's.  An id's local index is found by
    compares against the held ids, not looked up (a scalar gather, as
    above)."""
    n_held = len(experts_held)
    is_held = idx.reshape(-1, 1) == jnp.asarray(experts_held, jnp.int32)
    # the local index of a held expert, ``n_held`` for one that lives
    # elsewhere
    local = jnp.min(jnp.where(is_held, jnp.arange(n_held, dtype=jnp.int32),
                              n_held), axis=-1)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    sizes = jnp.sum(is_held, axis=0, dtype=jnp.int32)
    return order, sizes


def _chunk_rows(order, sizes, start, n, top_k):
    """The sorted assignments [start, start + n): (rows, their tokens,
    which of them are held here, how many fall to each held expert)."""
    ends = jnp.cumsum(sizes)
    clip = lambda a: jnp.clip(a - start, 0, n)
    rows = lax.dynamic_slice(order, (start,), (n,))
    valid = start + jnp.arange(n) < ends[-1]
    return rows, rows // top_k, valid, clip(ends) - clip(ends - sizes)


def _ceil_div(a, b):
    return (a + b - 1) // b


def pass_rows(chunk, n_steps):
    """The row counts a chunk's pass may run over: the chunk in ``n_steps``
    equal steps, the chunk itself the last."""
    return tuple(sorted({_ceil_div(chunk * j, n_steps)
                         for j in range(1, n_steps + 1)}))


def _step_of(held, steps):
    """Which of ``steps`` is the smallest that is not under ``held``."""
    return jnp.sum(held > jnp.asarray(steps[:-1], jnp.int32))


def _walk_chunks(sizes, chunk, n_steps, run, carry):
    """``run(n, c, carry)`` for every chunk ``c`` that holds an assignment:
    the full ones over all their ``n = chunk`` rows, then the last one over
    the smallest ``n`` of ``pass_rows`` that holds its assignments.  One
    loop for each ``n``, the largest first, each of a length the routing
    decides (all but one or two of them run no pass)."""
    steps = pass_rows(chunk, n_steps)
    held = jnp.sum(sizes)
    full, last = held // chunk, held % chunk
    for i, n in reversed(list(enumerate(steps))):
        takes_last = (last > 0) & (_step_of(last, steps) == i)
        first = 0 if n == chunk else full
        carry = lax.fori_loop(first, full + takes_last, partial(run, n),
                              carry)
    return carry


def rows_moved(sizes, chunk, n_steps):
    """The rows ``grouped_experts``' passes run over, each time it walks
    its chunks: a full chunk's all, the last chunk's up to the smallest of
    ``pass_rows`` that holds its assignments."""
    steps = pass_rows(chunk, n_steps)
    held = jnp.sum(sizes)
    last = held % chunk
    return held - last + jnp.where(
        last > 0, jnp.asarray(steps)[_step_of(last, steps)], 0)


def _experts_of_rows(xs, w_gate, w_up, w_down, chunk_sizes, valid):
    """Rows that lie expert by expert through their experts' SwiGLU: three
    grouped products.  xs: (n, D) and the weights in the compute dtype;
    float32 (n, D), zero in the rows past the groups."""
    def ragged(a, w):
        # a grouped product says nothing of the rows past its groups, in
        # its result or in its operand's gradient: both sides are masked,
        # so that neither pass reads what the kernel left there
        a = jnp.where(valid[:, None], a, jnp.zeros((), a.dtype))
        out = lax.ragged_dot(a, w, chunk_sizes,
                             preferred_element_type=jnp.float32)
        return jnp.where(valid[:, None], out, 0.0)

    with jax.named_scope("MoeExperts"):
        hidden = jax.nn.silu(ragged(xs, w_gate)) * ragged(xs, w_up)
        return ragged(hidden.astype(xs.dtype), w_down)


def _float0(a):
    return np.zeros(a.shape, jax.dtypes.float0)


@partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def grouped_experts(x, w_gate, w_up, w_down, weights, order, sizes, chunk,
                    n_steps, top_k, cast):
    """sum over the assignments held here of weight * Expert(x_token), with
    no capacity: the sorted assignments are walked ``chunk`` rows at a
    time for as many chunks as hold one (a loop whose length the routing
    decides), so the memory is one chunk's and no imbalance drops a token.
    A chunk gathers its tokens' rows, runs them through their experts and
    adds the weighted result to its tokens' rows of the output, in place.
    The grouped products' kernels take the time of the rows held; all
    else in a pass (the gathers, the masks, the SwiGLU, the scatter-adds)
    costs by the row it is given, held or padding, and a second pass pays
    the scatter-adds' and the weight-gradient sums' fixed parts again.  So
    the caller sizes the chunk to hold a usual step's assignments at once,
    and the last chunk's pass runs over its first rows only: the smallest
    of ``n_steps`` equal steps of the chunk (``pass_rows``) that holds its
    assignments.

    x: (T, D); w_gate, w_up: (n_held, D, H); w_down: (n_held, H, D);
    weights: (T, k); order, sizes: ``sort_assignments``'; ``order`` is
    padded to a multiple of ``chunk``."""
    return _grouped_fwd(x, w_gate, w_up, w_down, weights, order, sizes,
                        chunk, n_steps, top_k, cast)


# A chunk's pass over its first n rows, forward and backward.  Each is
# jitted on its own so that a model's expert layers, which pass the same
# shapes, are traced and lowered once for each n, not once a layer and an n
# (and again in every ``optimize()`` call, which builds its step anew).

@partial(jax.jit, static_argnums=(0, 1, 2))
def _pass_fwd(n, chunk, top_k, c, out, order, sizes, xc, flat, wg, wu, wd):
    rows, tok, valid, chunk_sizes = _chunk_rows(order, sizes, c * chunk, n,
                                                top_k)
    y = _experts_of_rows(xc[tok], wg, wu, wd, chunk_sizes, valid)
    w = jnp.where(valid, flat[rows], 0.0)
    return out.at[tok].add(y * w[:, None])


@partial(jax.jit, static_argnums=(0, 1, 2))
def _pass_bwd(n, chunk, top_k, c, grads, dout, order, sizes, xc, flat, wg,
              wu, wd):
    dx, dwg, dwu, dwd, dflat = grads
    f32 = lambda a: a.astype(jnp.float32)
    rows, tok, valid, chunk_sizes = _chunk_rows(order, sizes, c * chunk, n,
                                                top_k)
    y, vjp = jax.vjp(lambda *a: _experts_of_rows(*a, chunk_sizes, valid),
                     xc[tok], wg, wu, wd)
    w = jnp.where(valid, flat[rows], 0.0)
    dyw = dout[tok]
    dxs, g, u, d = vjp(dyw * w[:, None])
    # the rows past the groups are padding that names some assignment not
    # held: they add zeros
    dw_rows = jnp.where(valid, jnp.sum(dyw * y, axis=-1), 0.0)
    return (dx.at[tok].add(f32(dxs)), dwg + f32(g), dwu + f32(u),
            dwd + f32(d), dflat.at[rows].add(dw_rows))


def _grouped_fwd(x, w_gate, w_up, w_down, weights, order, sizes, chunk,
                 n_steps, top_k, cast):
    xc, wg, wu, wd = cast(x), cast(w_gate), cast(w_up), cast(w_down)
    flat = weights.reshape(-1)
    return _walk_chunks(
        sizes, chunk, n_steps,
        lambda n, c, out: _pass_fwd(n, chunk, top_k, c, out, order, sizes,
                                    xc, flat, wg, wu, wd),
        jnp.zeros(x.shape, jnp.float32))


def _grouped_vjp_fwd(x, w_gate, w_up, w_down, weights, order, sizes, chunk,
                     n_steps, top_k, cast):
    out = _grouped_fwd(x, w_gate, w_up, w_down, weights, order, sizes,
                       chunk, n_steps, top_k, cast)
    # a whole routed pass to make, one row a token to hold: a Recompute
    # around the layer keeps the sum, and its recomputation runs no chunk
    out = kept(out, "experts_out")
    return out, (x, w_gate, w_up, w_down, weights, order, sizes)


def _grouped_vjp_bwd(chunk, n_steps, top_k, cast, res, dout):
    """Chunk by chunk again, each over its held rows: a chunk's rows go
    through their experts once more (nothing of the forward pass is kept
    but its inputs), the gradient of the output's rows comes back through
    them, and every sum is kept in float32 and added to in place."""
    x, w_gate, w_up, w_down, weights, order, sizes = res
    xc, wg, wu, wd = cast(x), cast(w_gate), cast(w_up), cast(w_down)
    flat = weights.reshape(-1)
    zeros = lambda a: jnp.zeros(a.shape, jnp.float32)
    dx, dwg, dwu, dwd, dflat = _walk_chunks(
        sizes, chunk, n_steps,
        lambda n, c, grads: _pass_bwd(n, chunk, top_k, c, grads, dout, order,
                                      sizes, xc, flat, wg, wu, wd),
        (zeros(x), zeros(w_gate), zeros(w_up), zeros(w_down), zeros(flat)))
    return (dx, dwg, dwu, dwd, dflat.reshape(weights.shape),
            _float0(order), _float0(sizes))


grouped_experts.defvjp(_grouped_vjp_fwd, _grouped_vjp_bwd)

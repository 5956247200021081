"""Pipeline parallelism — GPipe-style microbatch pipelining over a mesh axis.

Absent in the reference (SURVEY.md §2.9: PP = NO); first-class here because
pipeline schedules are a core TPU scaling strategy when a model exceeds one
chip's HBM.

Design (the shard_map ring formulation):
- the repeated-block model is expressed as ONE stage function applied P
  times (scan-over-layers), with each pipeline rank holding its stage's
  parameters (stacked pytree sharded on the ``pipe`` axis, leading dim P);
- microbatches stream through ranks with ``ppermute`` hops: at tick t,
  rank r computes its stage on the activation it received at t-1 and
  forwards the result around the ring — the classic GPipe fill/steady/drain
  schedule, total ticks = n_micro + P - 1;
- everything is one compiled region: XLA overlaps the ppermute hop with
  the next microbatch's compute.

``pipeline_apply`` returns the final-stage outputs for all microbatches in
order.  Differentiable end-to-end (ppermute has a transpose rule), so the
same function trains under ``jax.grad``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map

from bigdl_tpu.parallel.collectives import pvary
from jax.sharding import Mesh, PartitionSpec as P


def _merge_state_over(state, data_axis):
    """Replica-merge per-stage carried state: float leaves (BN running
    stats) average, non-float leaves take the max (rank-identical by
    construction).  Shared by both schedules."""
    return jax.tree_util.tree_map(
        lambda s: lax.pmean(s, data_axis)
        if jnp.issubdtype(s.dtype, jnp.floating)
        else lax.pmax(s, data_axis), state)


def pipeline_apply(stage_fn, stage_params, x_micro, mesh: Mesh,
                   axis: str = "pipe", remat: bool = False,
                   stage_state=None, data_axis: str = None):
    """Run a P-stage pipeline over microbatches.

    stage_fn(params_slice, x) -> y          (one stage's computation;
                                             activation shapes preserved)
    stage_params: pytree with leading dim P (stage-stacked), will be
                  sharded over ``axis``.
    x_micro: (M, micro_batch, ...) microbatched input (replicated).
    Returns (M, micro_batch, ...) outputs of the last stage.

    ``stage_state`` (optional): a stage-stacked pytree of per-stage carried
    state (e.g. BatchNorm running stats), sharded over ``axis`` like the
    params.  When given, the stage function takes the extended signature
    ``stage_fn(params_slice, state_slice, x, micro_idx) -> (y, new_state)``
    — ``micro_idx`` is the (traced) global microbatch index, for deriving
    per-microbatch RNG keys — state updates apply only on valid (non-fill/
    drain) ticks, sequentially per microbatch (the reference's per-clone
    running-stat updates on sub-batches, BatchNormalization.scala under
    _subModelNumber), and the return value becomes
    ``(outputs, new_stage_state)``.

    ``data_axis`` composes with data parallelism: x_micro is sharded
    over it on the per-microbatch batch dim and the outputs come back
    likewise sharded; float state pmeans across replicas.

    ``remat=True`` wraps the stage in ``jax.checkpoint``: only the
    pipeline-boundary activations (the scan carry, one microbatch
    activation per tick) stay live for the backward; each stage's
    *internal* activations are recomputed.  Measured on the 8-device CPU
    mesh (tests/test_pipeline_moe.py::test_pipeline_remat_memory):
    compiled temp memory for a 4-stage x 3-layer-MLP pipeline drops 2.4x.
    GPipe liveness caveat: even with remat, boundary activations for all
    in-flight microbatches are saved per tick — O(n_micro + P - 1) per
    stage.  ``pipeline_train_1f1b`` below implements the 1F1B schedule,
    which bounds that at ~2(P-1)+1 independent of n_micro;
    docs/distributed.md records both cost models.
    """
    n_stage = mesh.shape[axis]
    stateful = stage_state is not None
    if stateful:
        fn = stage_fn
    else:
        # legacy stateless signature; dummy state rides along untouched
        fn = lambda p, s, x, m: (stage_fn(p, x), s)
        stage_state = jnp.zeros((n_stage, 1), jnp.float32)
    if remat:
        fn = jax.checkpoint(fn)
    vary_axes = (axis,) if data_axis is None else (axis, data_axis)

    def ranked(params, st, x_all):
        # inside shard_map: params has leading dim 1 (my stage), x_all is
        # the full microbatch stack (replicated over the pipe axis)
        my_params = jax.tree_util.tree_map(lambda v: v[0], params)
        my_state = jax.tree_util.tree_map(lambda v: v[0], st)
        if data_axis is not None:
            my_state = jax.tree_util.tree_map(
                lambda v: pvary(v, (data_axis,)), my_state)
        rank = lax.axis_index(axis)
        n_micro = x_all.shape[0]
        n_ticks = n_micro + n_stage - 1
        fwd = [(i, (i + 1) % n_stage) for i in range(n_stage)]

        micro_shape = x_all.shape[1:]
        # pvary: scan carries must be device-varying over the pipe axis
        buf = pvary(jnp.zeros(micro_shape, x_all.dtype), vary_axes)
        outs = pvary(jnp.zeros((n_micro,) + micro_shape, x_all.dtype),
                     vary_axes)

        def tick(carry, t):
            buf, outs, my_state = carry
            # rank r processes the microbatch rank 0 injected at t - r
            m = t - rank
            valid = (m >= 0) & (m < n_micro)
            # rank 0 injects microbatch t (when available)
            inject = x_all[jnp.clip(t, 0, n_micro - 1)]
            cur = jnp.where(rank == 0,
                            jnp.where(t < n_micro, inject, jnp.zeros_like(inject)),
                            buf)
            y, ns = fn(my_params, my_state, cur, m)
            # state advances only on valid ticks (fill/drain run on zeros)
            my_state = jax.tree_util.tree_map(
                lambda old, new: jnp.where(valid, new, old), my_state, ns)
            # last rank emits microbatch (t - (P-1)) at tick t
            out_idx = t - (n_stage - 1)
            emit = (rank == n_stage - 1) & (out_idx >= 0)
            upd = lax.dynamic_update_index_in_dim(
                outs, y, jnp.maximum(out_idx, 0), 0)
            outs = jnp.where(emit, upd, outs)
            buf = lax.ppermute(y, axis, fwd)
            return (buf, outs, my_state), None

        (buf, outs, my_state), _ = lax.scan(
            tick, (buf, outs, my_state), jnp.arange(n_ticks))
        # every rank holds `outs`, but only the last rank's is real;
        # broadcast it (max works since others are zero-initialized only if
        # last rank wrote) — use psum of masked value for correctness
        mask = (rank == n_stage - 1).astype(outs.dtype)
        outs = lax.psum(outs * mask, axis)
        if data_axis is not None:
            my_state = _merge_state_over(my_state, data_axis)
        return outs, jax.tree_util.tree_map(lambda v: v[None], my_state)

    pspec = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    sspec = jax.tree_util.tree_map(lambda _: P(axis), stage_state)
    xspec = P(None, data_axis) if data_axis is not None else P()
    f = shard_map(ranked, mesh=mesh,
                      in_specs=(pspec, sspec, xspec),
                      out_specs=(xspec, sspec))
    outs, new_state = f(stage_params, stage_state, x_micro)
    return (outs, new_state) if stateful else outs


def stack_stage_params(per_stage_params):
    """[stage0_tree, stage1_tree, ...] -> one tree with leading dim P."""
    return jax.tree_util.tree_map(lambda *vs: jnp.stack(vs), *per_stage_params)


def pipeline_train_1f1b(stage_fn, loss_fn, stage_params, x_micro, t_micro,
                        mesh: Mesh, axis: str = "pipe",
                        shard_inputs: bool = False, stage_state=None,
                        data_axis: str = None):
    """1F1B pipeline schedule: forward and backward interleaved so each
    stage keeps at most ~2*(P-1)+1 in-flight microbatch activations —
    independent of the microbatch count — where GPipe's autodiff keeps
    n_micro + P - 1 per stage (pipeline_apply docstring).

    Schedule (combined tick k, stage r, P = n_stage):
      - forward of microbatch  mf = k - r
      - backward of microbatch mb = k - (2*(P-1) - r)
    so the last stage backwards a microbatch the same tick it forwards
    it (loss cotangent computed in place), and stage r's backward runs
    one tick before stage r-1's — the activation gradient rides the
    reverse ring.  Total ticks = n_micro + 2*(P-1).

    Residuals: only each stage's INPUT activation per in-flight
    microbatch is buffered (circular buffer, depth 2*P); the stage is
    recomputed inside ``jax.vjp`` at backward time — the same
    fwd+recompute+bwd = 3 stage evaluations per microbatch per stage
    that GPipe-with-remat costs, but with the bounded buffer.

    stage_fn(params_slice, x) -> y   (activation shapes preserved)
    loss_fn(y_last, target) -> scalar (per microbatch; mean over
    microbatches is applied here)
    Returns (mean_loss, grads) with grads shaped like ``stage_params``
    (leading dim P, stage-sharded like the input).

    Operand memory: by default x_micro / t_micro are REPLICATED onto
    every rank (in_specs P()), so per-device input+target memory is
    O(n_micro) even though live activations are bounded.
    ``shard_inputs=True`` shards both over the pipe axis instead
    (n_micro must divide by P): each rank stores n_micro/P microbatches
    and the owner delivers the tick's microbatch with ONE masked psum
    (same for the target on the backward side) — O(n_micro/P) operand
    memory for two extra microbatch-sized collectives per tick.

    ``data_axis`` (optional): composes the pipeline with data
    parallelism over a second mesh axis — each data-parallel replica
    group runs the SAME 1F1B schedule on its microbatch shard (x/t
    sharded over ``data_axis`` on the per-microbatch batch dim), and
    gradients / loss / float state pmean across replicas before
    returning, exactly the plain-DP contract.  Incompatible with
    ``shard_inputs`` (one sharding per operand dim).

    ``stage_state`` (optional): stage-stacked carried state (BN running
    stats), sharded over ``axis``; switches the stage function to the
    extended signature ``stage_fn(params_slice, state_slice, x, micro_idx)
    -> (y, new_state)`` and the return value to ``(loss, grads,
    new_stage_state)``.  Contract: a stage's TRAINING-mode output must not
    depend on the carried state (true of BatchNorm, which normalizes by
    batch statistics in training — running stats are eval-only), because
    the backward-time recompute runs against a later state than the
    forward half; stochastic layers must key off ``micro_idx`` so the
    recompute draws the same mask.  State advances once per valid forward
    tick — per-microbatch sequential EMA, the reference's per-clone
    sub-batch updates (BatchNormalization.scala under _subModelNumber).
    """
    n_stage = mesh.shape[axis]
    stateful = stage_state is not None
    if stateful:
        fn = stage_fn
    else:
        fn = lambda p, s, x, m: (stage_fn(p, x), s)
        stage_state = jnp.zeros((n_stage, 1), jnp.float32)
    n_micro = x_micro.shape[0]
    depth = 2 * n_stage  # circular residual buffer, >= max in-flight + 1
    if shard_inputs and data_axis is not None:
        raise ValueError("shard_inputs and data_axis are mutually "
                         "exclusive (one sharding per operand dim)")
    if shard_inputs and n_micro % n_stage:
        raise ValueError(f"shard_inputs requires n_micro ({n_micro}) "
                         f"divisible by the pipe axis ({n_stage})")
    per = n_micro // n_stage if shard_inputs else n_micro
    vary_axes = (axis,) if data_axis is None else (axis, data_axis)
    dscale = mesh.shape[data_axis] if data_axis is not None else 1

    def ranked(params, st, x_all, t_all):
        my_params = jax.tree_util.tree_map(lambda v: v[0], params)
        my_state0 = jax.tree_util.tree_map(lambda v: v[0], st)
        if data_axis is not None:
            # state updates derive from the data-sharded x, so the carry
            # must start data-varying
            my_state0 = jax.tree_util.tree_map(
                lambda v: pvary(v, (data_axis,)), my_state0)
        rank = lax.axis_index(axis)

        def fetch(arr, m):
            # microbatch m of a possibly pipe-sharded (per, mb, ...)
            # array.  m MUST be a global (rank-independent) index: with
            # shard_inputs the owning rank contributes its slice and the
            # psum delivers it everywhere — a rank-dependent m would make
            # each rank contribute for a DIFFERENT microbatch and the sum
            # would be garbage.
            if not shard_inputs:
                return arr[jnp.clip(m, 0, n_micro - 1)]
            local = arr[jnp.clip(m - rank * per, 0, per - 1)]
            mine = (m // per == rank) & (m >= 0) & (m < n_micro)
            return lax.psum(local * mine.astype(local.dtype), axis)
        n_ticks = n_micro + 2 * (n_stage - 1)
        fwd_ring = [(i, (i + 1) % n_stage) for i in range(n_stage)]
        bwd_ring = [(i, (i - 1) % n_stage) for i in range(n_stage)]

        micro_shape = x_all.shape[1:]
        zeros_micro = jnp.zeros(micro_shape, x_all.dtype)
        buf_fwd = pvary(zeros_micro, vary_axes)        # fwd ring carry
        buf_bwd = pvary(zeros_micro, vary_axes)        # bwd ring carry
        resid = pvary(jnp.zeros((depth,) + micro_shape, x_all.dtype),
                      vary_axes)                       # saved stage inputs
        # my_params are already device-varying (stage-sharded), so zeros
        # derived from them are too — no pvary needed (pcast would reject)
        # grad_acc stays data-INVARIANT: inside shard_map, jax.vjp w.r.t.
        # the data-replicated my_params already psums each cotangent over
        # the data axis (vma-aware AD), so the per-tick gp arrives as the
        # cross-replica SUM — the 1/dscale in the loss closure turns that
        # into the mean, and no explicit grad collective is needed
        grad_acc = jax.tree_util.tree_map(jnp.zeros_like, my_params)
        loss_acc = pvary(jnp.zeros((), jnp.float32), vary_axes)

        def tick(carry, k):
            buf_fwd, buf_bwd, resid, grad_acc, loss_acc, my_state = carry

            # ---------------- forward half ----------------
            mf = k - rank
            f_valid = (mf >= 0) & (mf < n_micro)
            # global index: rank 0 is the only consumer and its mf == k
            inject = fetch(x_all, k)
            cur = jnp.where(rank == 0, inject, buf_fwd)
            y, ns = fn(my_params, my_state, cur, mf)
            my_state = jax.tree_util.tree_map(
                lambda old, new: jnp.where(f_valid, new, old), my_state, ns)
            resid = lax.dynamic_update_index_in_dim(
                resid, jnp.where(f_valid, cur, zeros_micro),
                jnp.maximum(mf, 0) % depth, 0)
            buf_fwd_next = lax.ppermute(
                jnp.where(f_valid, y, jnp.zeros_like(y)), axis, fwd_ring)

            # ---------------- backward half ----------------
            mb = k - (2 * (n_stage - 1) - rank)
            b_valid = (mb >= 0) & (mb < n_micro)
            x_saved = resid[jnp.maximum(mb, 0) % depth]
            # global index: the last rank is the only consumer of the
            # target and its mb == k - (P-1)
            tgt = fetch(t_all, k - (n_stage - 1))
            is_last = rank == n_stage - 1

            # ONE stage vjp per tick: recompute the stage forward, then
            # pick the cotangent — the loss gradient (last stage; from a
            # cheap vjp of loss_fn alone on the recomputed y) or the
            # incoming activation gradient off the reverse ring.  Static
            # structure on every rank/tick, 3 stage evals per microbatch
            # total (fwd half + recompute + bwd) as documented.  The
            # carried state is a non-diff constant here (see the stateful
            # contract in the docstring).
            y_re, stage_vjp = jax.vjp(
                lambda p, xx: fn(p, my_state, xx, mb)[0],
                my_params, x_saved)
            loss_val, loss_vjp = jax.vjp(
                lambda yy: loss_fn(yy, tgt) / (n_micro * dscale), y_re)
            one = pvary(jnp.ones((), loss_val.dtype), vary_axes)
            (dy,) = loss_vjp(one)
            cot = jnp.where(is_last, dy, buf_bwd)
            gp, gx = stage_vjp(cot)

            # jnp.where masking (NOT multiply-by-mask): a vjp evaluated
            # on the zeroed residual of a fill/drain tick may be
            # non-finite, and NaN * 0 would poison the accumulator
            grad_acc = jax.tree_util.tree_map(
                lambda acc, g: acc + jnp.where(b_valid, g,
                                               jnp.zeros_like(g)),
                grad_acc, gp)
            loss_acc = loss_acc + jnp.where(
                is_last & b_valid, loss_val.astype(jnp.float32), 0.0)
            buf_bwd_next = lax.ppermute(
                jnp.where(b_valid, gx, jnp.zeros_like(gx)), axis, bwd_ring)

            return (buf_fwd_next, buf_bwd_next, resid, grad_acc,
                    loss_acc, my_state), None

        carry = (buf_fwd, buf_bwd, resid, grad_acc, loss_acc, my_state0)
        carry, _ = lax.scan(tick, carry, jnp.arange(n_ticks))
        _, _, _, grad_acc, loss_acc, my_state = carry
        loss = lax.psum(loss_acc, axis)  # only last rank contributed
        if data_axis is not None:
            # loss_acc already carries the 1/dscale factor: psum over the
            # replicas completes the global mean
            loss = lax.psum(loss, data_axis)
            my_state = _merge_state_over(my_state, data_axis)
        grads = jax.tree_util.tree_map(lambda g: g[None], grad_acc)
        return loss, grads, jax.tree_util.tree_map(lambda v: v[None],
                                                   my_state)

    pspec = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    sspec = jax.tree_util.tree_map(lambda _: P(axis), stage_state)
    if shard_inputs:
        xspec = P(axis)
    elif data_axis is not None:
        xspec = P(None, data_axis)   # (M, mb, ...): shard the batch dim
    else:
        xspec = P()
    f = shard_map(ranked, mesh=mesh,
                      in_specs=(pspec, sspec, xspec, xspec),
                      out_specs=(P(), pspec, sspec))
    loss, grads, new_state = f(stage_params, stage_state, x_micro, t_micro)
    return (loss, grads, new_state) if stateful else (loss, grads)

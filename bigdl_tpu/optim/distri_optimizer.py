"""DistriOptimizer — synchronous data-parallel training over a device mesh
(ref optim/DistriOptimizer.scala, call stack SURVEY.md §3.1).

Mapping from the reference, piece by piece:

- Spark partition per node + model replica     -> mesh axis ``data``; the
  (initThreadModels :344-410)                     model is written once, XLA
                                                  replicates per device
- AllReduceParameter reduce-scatter/all-gather -> XLA all-reduce over ICI,
  (putGradients/getWeights)                       emitted by jit from the
                                                  sharded-batch mean loss
- FP16 wire compression                        -> bf16 compute policy
  (FP16CompressedTensor)                          (on-chip cast, no wire)
- per-partition weight update                  -> optional ZeRO-1 optimizer
  (optimMethod.optimize on MY slice :232)         state sharding
- straggler dropping (invokeAndWait2 timeout)  -> gradient masking: an XLA
  (DistriOptimizer.scala:154-172, threshold        dispatch cannot be
  :245-278)                                        cancelled, so replicas
                                                  over the kth-largest
                                                  time threshold are
                                                  masked out of the NEXT
                                                  aggregation instead —
                                                  psum(w*g)/sum(w), the
                                                  reference's div-by-
                                                  finishedModelNum (see
                                                  optim/straggler.py)
- Metrics phase breakdown :114-118             -> step metrics below

Multi-host: each process feeds its local batch shard;
``jax.make_array_from_process_local_data`` assembles the global array
(the Spark-RDD locality role, ZippedPartitionsWithLocalityRDD).
"""
from __future__ import annotations

import logging
import os
import time

import jax

from jax import shard_map
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bigdl_tpu.nn.module import Context
from bigdl_tpu.obs import events as obs_events
from bigdl_tpu.optim.local_optimizer import (LocalOptimizer,
                                             _HostSyncWindow, _PendingStep,
                                             _finite_all,
                                             _model_fingerprint,
                                             _where_finite, validate)
from bigdl_tpu.optim.metrics import Metrics
from bigdl_tpu.parallel.mesh import data_parallel_mesh
from bigdl_tpu.utils.engine import Engine
from bigdl_tpu.utils.random import RNG
from bigdl_tpu.utils.table import T

logger = logging.getLogger("bigdl_tpu.optim")


def _put_host(arr, sharding):
    """Host array → device array under ``sharding``, multi-host-safe:
    every process holds the FULL host copy (replicated state, or a
    checkpoint/anchor restore) and contributes its addressable slices —
    the one placement primitive this jax supports for arbitrary
    cross-process shardings."""
    import jax as _jax
    arr = np.asarray(arr)
    return _jax.make_array_from_callback(arr.shape, sharding,
                                         lambda idx: arr[idx])


class DistriOptimizer(LocalOptimizer):
    def __init__(self, model, dataset, criterion, mesh=None,
                 drop_percentage: float = 0.0, tensor_parallel: bool = False,
                 zero1: bool = False, gradient_compression: str = None,
                 pipeline_stages: int = None, pipeline_schedule: str = "1f1b",
                 pipeline_microbatches: int = None,
                 expert_parallel: bool = False,
                 sequence_parallel: bool = False):
        """``tensor_parallel=True`` with a mesh containing a ``model`` axis
        shards eligible weights (and their optimizer state) over that axis
        via ``parallel.sharding.shard_params_rule`` — hybrid DP x TP with
        the same user API as pure DP.

        ``zero1=True`` shards optimizer state over the ``data`` axis
        (ZeRO-1) — the direct analogue of the reference's owner-partition
        update (each AllReduceParameter partition updates only its weight
        slice, DistriOptimizer.scala:232); XLA moves the state shards as
        needed and HBM per chip drops by ~|opt_state|*(1-1/N).

        ``gradient_compression="bf16"`` is the reference's FP16 wire codec
        (parameters/FP16CompressedTensor.scala: gradients truncated to 16
        bits before crossing the network): the step is built with
        ``shard_map`` so each device computes local grads, casts them to
        bf16, and the cross-device all-reduce moves bf16 — halving
        ICI/DCN gradient traffic — before the f32 update.

        ``pipeline_stages=P`` trains a ``Sequential`` model with pipeline
        parallelism over a ``pipe`` mesh axis — the model is stage-
        partitioned automatically (``parallel/pipeline_model.py``) and the
        batch streams through as ``pipeline_microbatches`` microbatches
        (default 2·P) under ``pipeline_schedule``: ``"1f1b"`` (bounded
        activation memory) or ``"gpipe"`` (optionally with
        ``set_gradient_checkpointing``).  Same front door as every other
        distribution mode (ref Optimizer.scala:151-186).  Stage sharding
        owns the whole mesh, so it composes with none of
        tensor_parallel/zero1/gradient_compression — and gradients never
        cross ranks under PP (each stage's grads stay home), so there is
        no wire to compress."""
        super().__init__(model, dataset, criterion)
        if gradient_compression not in (None, "bf16"):
            raise ValueError("gradient_compression must be None or 'bf16'")
        if pipeline_stages is not None:
            if tensor_parallel or zero1 or gradient_compression \
                    or expert_parallel or sequence_parallel:
                raise ValueError(
                    "pipeline_stages owns the mesh; it does not combine "
                    "with tensor_parallel/zero1/gradient_compression/"
                    "expert_parallel/sequence_parallel")
            if pipeline_schedule not in ("1f1b", "gpipe"):
                raise ValueError("pipeline_schedule must be '1f1b' or "
                                 "'gpipe'")
            if jax.process_count() > 1:
                # multi-host pipeline: stages span hosts over DCN.  Every
                # process must feed the IDENTICAL global batch (operands
                # ride replicated), so a per-process-sharded dataset
                # cannot drive it — fail at construction, not at
                # optimize() after the user's setup work
                from bigdl_tpu.optim.optimizer import is_distributed_dataset
                if is_distributed_dataset(dataset):
                    raise ValueError(
                        "multi-host pipeline_stages needs a replicated "
                        "(non-distributed) dataset: every process feeds "
                        "the identical global batch")
            if mesh is None:
                from bigdl_tpu.parallel.mesh import make_mesh
                devs = jax.devices()
                if len(devs) < pipeline_stages:
                    raise ValueError(
                        f"pipeline_stages={pipeline_stages} needs that "
                        f"many devices, have {len(devs)}")
                if jax.process_count() > 1 and len(devs) != pipeline_stages:
                    # devs[:P] would be a host-0-only mesh while every
                    # process must join the pipeline collectives — the
                    # multi-host spanning layout needs an explicit choice
                    raise ValueError(
                        f"multi-host pipeline with {len(devs)} global "
                        f"devices and pipeline_stages={pipeline_stages}: "
                        "pass an explicit mesh (e.g. make_mesh({'data': "
                        f"{len(devs) // pipeline_stages}, 'pipe': "
                        f"{pipeline_stages}}})) so every process holds "
                        "mesh devices")
                # default mesh: the first P devices as a pure pipe axis
                # (pass an explicit {'data': d, 'pipe': P} mesh to use
                # the rest for hybrid dp x pp)
                mesh = make_mesh({"pipe": pipeline_stages},
                                 devs[:pipeline_stages])
            if "pipe" not in mesh.axis_names or \
                    mesh.shape["pipe"] != pipeline_stages:
                raise ValueError(
                    f"mesh needs a 'pipe' axis of size {pipeline_stages}, "
                    f"got {dict(mesh.shape)}")
            if set(mesh.axis_names) - {"pipe", "data"}:
                raise ValueError(
                    "pipeline meshes support 'pipe' plus an optional "
                    f"'data' axis (hybrid dp x pp), got {mesh.axis_names}")
        elif expert_parallel:
            if tensor_parallel or zero1 or gradient_compression \
                    or sequence_parallel:
                raise ValueError(
                    "expert_parallel composes with data parallelism only "
                    "(mesh {'data': d, 'expert': e}); tensor_parallel/"
                    "zero1/gradient_compression/sequence_parallel assume "
                    "replicated or data-sharded params, not expert-"
                    "sharded ones")
            if mesh is None or "expert" not in mesh.axis_names:
                raise ValueError(
                    "expert_parallel needs a mesh with an 'expert' axis")
        elif sequence_parallel:
            if tensor_parallel or zero1 or gradient_compression:
                raise ValueError(
                    "sequence_parallel composes with data parallelism "
                    "only (mesh {'data': d, 'seq': s})")
            if mesh is None or "seq" not in mesh.axis_names \
                    or "data" not in mesh.axis_names:
                raise ValueError(
                    "sequence_parallel needs a mesh with 'data' and "
                    "'seq' axes (pure SP: use {'data': 1, 'seq': s})")
        elif gradient_compression and tensor_parallel:
            raise ValueError(
                "gradient_compression composes with DP and zero1, not "
                "tensor_parallel: TP grads are per-leaf sharded over the "
                "model axis, so there is no single flat gradient wire to "
                "compress (the reference has no TP at all)")
        self.gradient_compression = gradient_compression
        self._z1c_flat = None  # padded flat-param length (compressed ZeRO-1)
        self.pipeline_stages = pipeline_stages
        self.pipeline_schedule = pipeline_schedule
        self.pipeline_microbatches = pipeline_microbatches
        self._pipe_plan = None
        self.mesh = mesh if mesh is not None else data_parallel_mesh()
        self.tensor_parallel = tensor_parallel
        self.zero1 = zero1
        self.expert_parallel = expert_parallel
        self.sequence_parallel = sequence_parallel
        self._straggler = None
        if drop_percentage:
            # constructor shorthand: drop and cap at the same fraction
            # (the reference arms both through setDropMoudleProperty,
            # Optimizer.scala:116-124)
            self.set_drop_module_property(drop_percentage, drop_percentage)

    def set_drop_module_property(self, drop_percentage: float,
                                 max_drop_percentage: float,
                                 batch_size: int = 100,
                                 warmup_iteration: int = 200,
                                 time_source=None):
        """Arm straggler dropping (ref Optimizer.setDropMoudleProperty,
        Optimizer.scala:116-124; drop/threshold machinery
        DistriOptimizer.scala:154-172, :245-278).  Each data replica is
        one reference "task": replicas whose measured step time exceeded
        the kth-largest threshold are masked out of the gradient
        aggregation — ``psum(w*g)/sum(w)``, the reference's
        ``gradientPartition.div(finishedModelNum)`` — one dispatch after
        the measurement (an XLA collective cannot be cancelled mid-
        flight the way ``invokeAndWait2`` cancels a JVM task).
        ``time_source(local_wall) -> (n_tasks,) seconds`` overrides the
        per-process wall-clock default (tests inject synthetic
        schedules); see optim/straggler.py."""
        from bigdl_tpu.optim.straggler import StragglerPolicy
        if not drop_percentage:
            self._straggler = None
            return self
        if (self.pipeline_stages is not None or self.expert_parallel
                or self.sequence_parallel or self.tensor_parallel):
            raise ValueError(
                "straggler drop masks per-DATA-replica gradients; it "
                "composes with DP, zero1 and gradient_compression only "
                "(the reference's tasks are data-parallel model clones)")
        if "data" not in self.mesh.axis_names:
            raise ValueError("straggler drop needs a 'data' mesh axis")
        self._straggler = StragglerPolicy(
            n_tasks=self.mesh.shape["data"],
            drop_percentage=drop_percentage,
            max_drop_percentage=max_drop_percentage,
            compute_threshold_batch_size=batch_size,
            warmup_iteration=warmup_iteration,
            time_source=time_source)
        return self

    def _straggler_task_times(self, fetch_wall: float,
                              step_wall: float) -> np.ndarray:
        """Per-task (= per data-replica) seconds for this iteration.

        Multi-host: the signal is each process's HOST-SIDE wall (data
        fetch + preprocessing), assigned to the replicas that process
        owns.  The dispatch wall itself is useless here — the collective
        is bulk-synchronous, so every process's step ENDS at the same
        instant and a process that entered late (because its fetch was
        slow) measures a SHORTER dispatch than the healthy hosts; the
        fetch wall is the part of the iteration where a straggling host
        actually spends its excess time.  Single host: no skew is
        observable within one XLA dispatch, so every task reads the same
        total wall and dropping never engages."""
        pol = self._straggler
        if pol.time_source is not None:
            times = pol.task_times(fetch_wall + step_wall)
            if jax.process_count() > 1:
                # every process must hold IDENTICAL policy state or they
                # disagree on accept/reject and deadlock the collective:
                # merge the per-process views (any process seeing a task
                # slow counts)
                from jax.experimental import multihost_utils
                allv = np.asarray(multihost_utils.process_allgather(
                    np.asarray(times, np.float64)))
                times = allv.reshape(jax.process_count(), -1).max(axis=0)
            return times
        if jax.process_count() == 1:
            return pol.task_times(fetch_wall + step_wall)
        from jax.experimental import multihost_utils
        walls = np.asarray(multihost_utils.process_allgather(
            np.asarray(fetch_wall, np.float64))).reshape(-1)
        ax = list(self.mesh.axis_names).index("data")
        devs = np.moveaxis(self.mesh.devices, ax, 0).reshape(
            pol.n_tasks, -1)
        return np.array([walls[row[0].process_index] for row in devs],
                        np.float64)

    def _maybe_validate(self, params, net_state, state, force=False):
        # triggers first (every_epoch is stateful — probe exactly once),
        # THEN the pipeline unpack: validation consumes module-tree
        # pytrees, but unpacking the stage-stacked arrays is a full-model
        # host gather that must not run on every non-firing iteration
        if not force and (self.validation_trigger is None
                          or not self.validation_trigger(state)):
            return
        if self._pipe_plan is not None:
            params = self._pipe_plan.unpack_params(params)
            net_state = self._pipe_plan.unpack_state(net_state)
        super()._maybe_validate(params, net_state, state, force=True)

    def _maybe_checkpoint(self, params, net_state, opt_state, state,
                          force=False, neval_label=None):
        if not force and (self.checkpoint_trigger is None
                          or not self.checkpoint_trigger(state)):
            return
        if self._pipe_plan is not None:
            # unpack only when actually firing (full-model host gather),
            # and BEFORE the process gate: multi-host stage gathering is
            # a collective every process must join.  opt_state stays
            # stage-stacked — a resumed run re-packs the same partition,
            # so set_optim_state round-trips.
            params = self._pipe_plan.unpack_params(params)
            net_state = self._pipe_plan.unpack_state(net_state)
            # opt_state leaves are stage-stacked too: bring host copies
            # so process 0 can pickle them (a multi-host sharded array
            # is not picklable)
            opt_state = jax.tree_util.tree_map(
                self._pipe_plan._gather_stacked, opt_state)
            # params are replicated post-unpack, so exactly one process
            # writes — the reference gathers slices to the driver and
            # saves once (getModel + File.save, DistriOptimizer.scala:
            # 320-342); writing from every host would race on a shared
            # checkpoint path.
            if jax.process_index() != 0:
                return
        # non-pipeline: the base decides per snapshot — replicated state
        # writes from process 0 only; zero1 state sharded across
        # processes writes one shard file per process
        # (resilience/checkpoint.py, docs/resilience.md)
        super()._maybe_checkpoint(params, net_state, opt_state, state,
                                  force=True, neval_label=neval_label)

    def _preemption_pending(self) -> bool:
        """Multi-host preemption barrier: ANY process's SIGTERM stops all
        of them at the same iteration (one host exiting alone would
        strand the rest in a dead collective).  The merge is a tiny
        allgather per iteration, paid only while the handler is armed —
        install it on EVERY process (``Engine.install_preemption_handler``
        from the shared launcher path) or the collective deadlocks."""
        if jax.process_count() == 1:
            return Engine.preempted()
        if not Engine.preemption_armed():
            if Engine.preempted():
                from bigdl_tpu.utils.log import warn_every
                warn_every(
                    logger, "preempt-unarmed", 30.0,
                    "preemption requested but the handler is not armed: "
                    "a multi-host run only honors the notice when "
                    "Engine.install_preemption_handler() ran on EVERY "
                    "process (the stop flag must merge as a collective); "
                    "ignoring it")
            return False
        from jax.experimental import multihost_utils
        flags = self._guarded(lambda: np.asarray(
            multihost_utils.process_allgather(
                np.asarray(1.0 if Engine.preempted() else 0.0,
                           np.float32))))
        return bool(flags.max() > 0)

    def _expert_param_specs(self, params):
        """Path-aware sharding tree: the expert-stacked leaves of ``MoE``
        modules (w1/b1/w2/b2, leading dim = n_experts) shard dim 0 over
        the ``expert`` axis — the reference has no EP at all (SURVEY.md
        §2.9); the GSPMD partitioning of the MoE dispatch einsums is the
        all-to-all the hand-scheduled parallel/moe.moe_apply spells out.
        Router and every non-MoE param replicate."""
        from bigdl_tpu.nn.moe import MoE
        mesh = self.mesh
        rep = NamedSharding(mesh, P())
        exp = NamedSharding(mesh, P("expert"))
        esize = mesh.shape["expert"]

        def walk(mod, ptree):
            out = {"~": {}}
            is_moe = isinstance(mod, MoE)
            for k, v in ptree.get("~", {}).items():
                shard = (is_moe and k != "router"
                         and np.ndim(v) >= 1 and v.shape[0] % esize == 0)
                out["~"][k] = exp if shard else rep
            for name, child in mod._modules.items():
                out[name] = walk(child, ptree[name])
            return out

        return walk(self.model, params)

    def _mirror_opt_specs(self, opt_state, params, pspec, rep):
        """Optimizer-state subtrees that mirror the param tree (SGD
        velocity, Adagrad variance) inherit the param shardings; anything
        else (scalar counters) replicates."""
        ptd = jax.tree_util.tree_structure(params)
        if not isinstance(opt_state, dict):
            return jax.tree_util.tree_map(lambda _: rep, opt_state)
        out = {}
        for k, sub in opt_state.items():
            if jax.tree_util.tree_structure(sub) == ptd:
                out[k] = pspec
            else:
                out[k] = jax.tree_util.tree_map(lambda _: rep, sub)
        return out

    def _shardings(self, params, net_state, opt_state):
        mesh = self.mesh
        rep = NamedSharding(mesh, P())
        data = NamedSharding(mesh, P("data")
                             if "data" in mesh.axis_names else P())
        reps = lambda tree: jax.tree_util.tree_map(lambda _: rep, tree)
        if self.expert_parallel:
            pspec = self._expert_param_specs(params)
            ospec = self._mirror_opt_specs(opt_state, params, pspec, rep)
            return pspec, reps(net_state), ospec, data
        if self.tensor_parallel and "model" in mesh.axis_names:
            from bigdl_tpu.parallel.sharding import (shard_params_rule,
                                                     zero1_tp_rule)
            rule = shard_params_rule(mesh, "model")
            orule = zero1_tp_rule(mesh, "data", "model") if self.zero1 else rule
            return (jax.tree_util.tree_map(rule, params), reps(net_state),
                    jax.tree_util.tree_map(orule, opt_state), data)
        if self.zero1:
            from bigdl_tpu.parallel.sharding import zero1_rule
            zrule = zero1_rule(mesh, "data")
            return (reps(params), reps(net_state),
                    jax.tree_util.tree_map(zrule, opt_state), data)
        return reps(params), reps(net_state), reps(opt_state), data

    def _core_step(self, fold_axis=None, grad_transform=None,
                   state_merge=None, update_transform=None,
                   finite_merge=None, taps_merge=None):
        """The train step both builders share: loss_fn, value_and_grad,
        optimizer update.  ``fold_axis`` decorrelates the dropout key per
        replica; ``grad_transform``/``state_merge`` hook the compressed
        path's collectives in; ``update_transform`` replaces the plain
        ``method.update`` (the compressed-ZeRO-1 owner-partition path).
        ``finite_merge`` reconciles the non-finite-guard flag across
        replicas inside shard_map (local grads can be finite on one
        replica and not another; a divergent skip decision would fork the
        replicated params).  ``taps_merge`` does the same for the in-jit
        tap scalars (obs/taps.py): under shard_map they are computed from
        LOCAL gradients, so the shard_map builder pmean-merges them —
        divergent per-replica values behind a replicated out_spec would
        silently report one arbitrary replica."""
        from bigdl_tpu.obs import taps as obs_taps
        model, criterion, method = self.model, self.criterion, self.optim_method
        static_hyper = self._hyper(None)
        del static_hyper["lr"]
        has_scales = self._setup_lr_scales(static_hyper)
        taps_on = obs_taps.enabled(self._taps_enabled)
        # sequence-parallel trainers hand attention layers the mesh so
        # they route through the exact ring collective (nn/attention.py)
        seq_mesh = self.mesh if self.sequence_parallel else None

        def step(params, net_state, opt_state, x, y, lr, key, lr_scales):
            hyper = dict(static_hyper, lr=lr)
            if has_scales:
                hyper["lr_scales"] = lr_scales
            if fold_axis is not None:
                # independent dropout masks per replica (the reference's
                # thread-local RNG per model clone)
                key = jax.random.fold_in(key, jax.lax.axis_index(fold_axis))

            def loss_fn(p):
                out, ns = model.apply(p, x, net_state,
                                      Context(training=True, key=key,
                                              seq_mesh=seq_mesh))
                # in the plain jit path: mean over the GLOBAL batch — with x
                # sharded over "data" and params replicated, jax.grad makes
                # XLA emit the cross-ICI all-reduce; this line IS
                # AllReduceParameter
                return criterion.apply_loss(out, y), ns

            (loss, new_net_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            if grad_transform is not None:
                grads, loss = grad_transform(grads, loss)
            if state_merge is not None:
                new_net_state = state_merge(new_net_state)
            finite = _finite_all(loss, grads)
            if finite_merge is not None:
                finite = finite_merge(finite)
            if update_transform is not None:
                new_params, new_opt_state = update_transform(
                    grads, opt_state, params, hyper)
            else:
                new_params, new_opt_state = method.update(
                    grads, opt_state, params, hyper)
            new_params = _where_finite(finite, new_params, params)
            new_opt_state = _where_finite(finite, new_opt_state, opt_state)
            new_net_state = _where_finite(finite, new_net_state, net_state)
            taps = (obs_taps.compute(grads, params, new_params)
                    if taps_on else {})
            if taps and taps_merge is not None:
                taps = taps_merge(taps)
            return (new_params, new_net_state, new_opt_state, loss, finite,
                    taps)

        return step

    def _jit_step(self, step, ps, ns, os_, data_s, x_s=None,
                  x_chunk_s=None, extra_in=()):
        """Shared jit wiring: carried state is donated (buffers recycled in
        place); optimize() passes copies so the module's arrays survive.
        The trailing lr_scales argument rides replicated (prefix sharding
        broadcasts over its pytree) and is never donated.

        ``x_s``/``x_chunk_s`` override the INPUT sharding when it differs
        from the label sharding (sequence parallelism also shards dim T).

        With ``iters_per_dispatch > 1`` the step is wrapped in a
        lax.scan over stacked (n, B, ...) batches — same device-side
        training loop as LocalOptimizer (set_iterations_per_dispatch),
        batch sharded over "data" on dim 1."""
        from bigdl_tpu.serve import xcache
        fn_key = ("distri_step", _model_fingerprint(self.model),
                  type(self.optim_method).__name__)
        rep = NamedSharding(self.mesh, P())
        n = self.iters_per_dispatch
        if n <= 1:
            return xcache.tracked_jit(
                step, fn_key, key_argnums=(3, 4), mesh=self.mesh,
                in_shardings=(ps, ns, os_, x_s or data_s, data_s,
                              rep, rep, rep) + tuple(extra_in),
                out_shardings=(ps, ns, os_, rep, rep, rep),
                donate_argnums=(0, 1, 2),
            )

        if extra_in:
            raise ValueError("extra step operands are single-dispatch "
                             "only (no chunked-scan wiring for them)")
        chunk_data_s = NamedSharding(self.mesh, P(None, "data"))
        return xcache.tracked_jit(
            self._scan_chunk(step, n), fn_key + ("chunk%d" % n,),
            key_argnums=(3, 4), mesh=self.mesh,
            in_shardings=(ps, ns, os_, x_chunk_s or chunk_data_s,
                          chunk_data_s, rep, rep, rep),
            out_shardings=(ps, ns, os_, rep, rep, rep),
            donate_argnums=(0, 1, 2),
        )

    def _build_step_compressed(self):
        """shard_map step with bf16 gradient all-reduce (the FP16 wire codec
        role, ref FP16CompressedTensor.scala:29/parAdd :173-268: compress,
        ship, add).  Params stay replicated f32; only the gradient crossing
        the mesh is 16-bit.

        BatchNorm running stats are computed per shard and pmean-merged —
        the reference's replicas likewise each update their own running
        stats on their sub-batch (BatchNormalization.scala under
        _subModelNumber clones); the global-batch stats of the plain jit
        path are a (slightly tighter) superset of that behavior.

        ``zero1=True`` composes, reproducing the reference's single
        mechanism where the fp16 codec and the owner-partition update are
        one code path (AllReduceParameter.scala:162-235: compressed
        gradient slices land on their owner, which runs optimMethod on
        its slice and serves the updated weights back):

        - local grads ravel to ONE flat vector (the reference's flattened
          getParameters storage), padded to a multiple of the data-axis
          size;
        - ``psum_scatter`` in bf16 — each device receives only its owned
          slice of the summed gradient, and only bf16 bytes cross the
          mesh (vs pmean moving the full vector to every device);
        - the optimizer updates the owned slice with opt state that
          lives data-sharded (ZeRO-1: HBM per chip for optimizer state
          drops by 1/N);
        - ``all_gather`` redistributes the updated f32 slices (the
          reference's getWeights).
        """
        mesh = self.mesh
        method = self.optim_method
        # straggler drop rides this same shard_map path with a f32 wire
        # when compression is off: tasks = data replicas, and the masked
        # aggregation needs the per-replica gradients this builder has
        wire = jnp.bfloat16 if self.gradient_compression else jnp.float32
        masked = self._straggler is not None
        # (w, msum) for the current trace, pushed by the masked step
        # wrapper below so the hooks — whose (grads, loss) signature is
        # fixed by _core_step — can see the mask operand
        mask_cell = []

        def wmean(x, dtype):
            """Weighted replica mean computed in ``dtype`` — with w == 1
            this is exactly pmean(x.astype(dtype)): psum then divide."""
            w, msum = mask_cell[-1]
            return (jax.lax.psum((x * w).astype(dtype), "data")
                    / msum.astype(dtype))

        def loss_mean(grads, loss):
            if mask_cell:
                # the reference's lossSum / finishedModelNum (:226)
                return grads, wmean(loss, loss.dtype)
            return grads, jax.lax.pmean(loss, "data")

        def grad_transform(grads, loss):
            # compress -> all-reduce(mean) over the wire dtype -> f32;
            # masked: psum(w*g)/sum(w) — the reference's div-by-
            # finishedModelNum (DistriOptimizer.scala:231-234)
            if mask_cell:
                grads = jax.tree_util.tree_map(
                    lambda g: wmean(g, wire).astype(g.dtype), grads)
                return grads, wmean(loss, loss.dtype)
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g.astype(wire),
                                        "data").astype(g.dtype), grads)
            return grads, jax.lax.pmean(loss, "data")

        def state_merge(net_state):
            def merge(s):
                if not jnp.issubdtype(jnp.asarray(s).dtype, jnp.floating):
                    return s
                if mask_cell:
                    # dropped replicas' BN stats are excluded, like the
                    # reference's cancelled tasks never touching theirs
                    return wmean(s, s.dtype)
                return jax.lax.pmean(s, "data")
            return jax.tree_util.tree_map(merge, net_state)

        update_transform = None
        if self.zero1:
            from jax.flatten_util import ravel_pytree
            if self.state.get("learningRates", None) is not None:
                raise ValueError(
                    "state['learningRates'] (per-param lr scales) is not "
                    "supported with zero1 + gradient_compression: the "
                    "owner-partition update runs on a flat slice, not the "
                    "param tree")
            ndata = mesh.shape["data"]
            # concrete ravel builds the unravel closure; the flat copy is
            # transient (freed after this scope)
            flat0, unravel = ravel_pytree(self.model.params())
            total = int(flat0.size)
            pad = (-total) % ndata
            self._z1c_flat = total + pad
            slice_len = self._z1c_flat // ndata
            del flat0

            def update_transform(grads, opt_state, params, hyper):
                gflat, _ = ravel_pytree(grads)
                if mask_cell:
                    # masked replica contributes zeros; divide by the
                    # finished count instead of ndata
                    gflat = gflat * mask_cell[-1][0]
                gflat = jnp.pad(gflat, (0, pad)).astype(wire)
                gslice = jax.lax.psum_scatter(gflat, "data", tiled=True)
                gslice = gslice.astype(jnp.float32) / (
                    mask_cell[-1][1] if mask_cell else ndata)
                pflat, _ = ravel_pytree(params)
                pflat = jnp.pad(pflat, (0, pad))
                rank = jax.lax.axis_index("data")
                pslice = jax.lax.dynamic_slice_in_dim(
                    pflat, rank * slice_len, slice_len)
                new_pslice, new_opt = method.update(
                    gslice, opt_state, pslice, hyper)
                new_flat = jax.lax.all_gather(new_pslice, "data", tiled=True)
                return unravel(new_flat[:total]), new_opt

        core = self._core_step(
            fold_axis="data",
            grad_transform=loss_mean if self.zero1 else grad_transform,
            state_merge=state_merge, update_transform=update_transform,
            # non-finite guard: replicas see LOCAL grads here (the zero1
            # path aggregates inside update_transform), so one replica's
            # NaN must veto the update on every replica or the
            # where-select forks the replicated params
            finite_merge=lambda f: jax.lax.pmin(
                f.astype(jnp.int32), "data").astype(jnp.bool_),
            # tap scalars are per-replica inside shard_map: pmean to a
            # truly replicated value (grad_norm then reads as the
            # replica-mean of local-gradient norms — docs/observability.md)
            taps_merge=lambda t: {k: jax.lax.pmean(v, "data")
                                  for k, v in t.items()})
        if masked:
            # 9th operand: the (n_tasks,) 0/1 drop mask, replicated —
            # push (w_this_replica, finished_count) for the hooks above
            def step(params, ns, os_, x, y, lr, key, lr_scales, mask):
                w = mask[jax.lax.axis_index("data")]
                mask_cell.append((w, mask.sum()))
                try:
                    return core(params, ns, os_, x, y, lr, key, lr_scales)
                finally:
                    mask_cell.pop()
        else:
            step = core
        rep, data = P(), P("data")
        if self.zero1:
            # flat mirrors of the parameter vector shard over data; scalar
            # leaves (e.g. Adagrad's 0-d step counter, identical on every
            # rank) stay replicated — same guard as zero1_rule
            ospec = jax.tree_util.tree_map(
                self._z1c_leaf_spec, self._z1c_opt_shape())
        else:
            ospec = rep
        sharded = shard_map(
            step, mesh=mesh,
            in_specs=(rep, rep, ospec, data, data, rep, rep, rep)
            + ((rep,) if masked else ()),
            out_specs=(rep, rep, ospec, rep, rep, rep),
            check_vma=False,
        )
        params, net_state, opt_state = self._state_trees()
        rep_s = NamedSharding(mesh, rep)
        data_s = NamedSharding(mesh, data)
        reps = lambda tree: jax.tree_util.tree_map(lambda _: rep_s, tree)
        if self.zero1:
            opt_s = jax.tree_util.tree_map(
                lambda l: NamedSharding(mesh, self._z1c_leaf_spec(l)),
                self._z1c_opt_shape())
        else:
            opt_s = reps(opt_state)
        if masked:
            if self.iters_per_dispatch > 1:
                raise ValueError(
                    "straggler drop recomputes the mask every iteration "
                    "(ref DistriOptimizer.scala:154: the timeout applies "
                    "per invokeAndWait2 round); it does not combine with "
                    "set_iterations_per_dispatch > 1")
            return self._jit_step(sharded, reps(params), reps(net_state),
                                  opt_s, data_s, extra_in=(rep_s,))
        return self._jit_step(sharded, reps(params), reps(net_state),
                              opt_s, data_s)

    def _z1c_opt_shape(self):
        """Abstract optimizer-state tree for the flat compressed-ZeRO-1
        parameter vector."""
        return jax.eval_shape(
            self.optim_method.init_state,
            jax.ShapeDtypeStruct((self._z1c_flat,), jnp.float32))

    def _z1c_leaf_spec(self, leaf):
        ndata = self.mesh.shape["data"]
        if leaf.ndim >= 1 and leaf.shape[0] % ndata == 0:
            return P("data")
        return P()

    def _initial_opt_state(self, params):
        """Compressed ZeRO-1 keeps optimizer state as data-sharded slices
        of the flat parameter vector (the reference's per-partition
        optimMethod state, AllReduceParameter.scala:162-235) — init it
        flat; everything else defers to the base builder."""
        z1c = ((self.gradient_compression or self._straggler is not None)
               and self.zero1)
        if z1c and self._resume_opt_state is None:
            state = self.optim_method.init_state(
                jnp.zeros((self._z1c_flat,), jnp.float32))
            return jax.tree_util.tree_map(
                lambda v: jax.device_put(
                    v, NamedSharding(self.mesh, self._z1c_leaf_spec(v))),
                state)
        if z1c and self._resume_opt_state is not None:
            return self._adapt_z1c_state(self._resume_opt_state)
        if self.zero1 and self._resume_opt_state is not None:
            # world-size-agnostic restore: the snapshot holds the FULL
            # logical tree (load_latest_checkpoint reassembles shards);
            # partition it over THIS mesh's data axis — which may differ
            # from the saving run's (dp=4 checkpoint, dp=3 restore)
            from bigdl_tpu.parallel.sharding import zero1_rule
            rule = zero1_rule(self.mesh, "data")
            if self.tensor_parallel and "model" in self.mesh.axis_names:
                from bigdl_tpu.parallel.sharding import zero1_tp_rule
                rule = zero1_tp_rule(self.mesh, "data", "model")
            return jax.tree_util.tree_map(
                lambda v: _put_host(np.asarray(v), rule(np.asarray(v))),
                self._resume_opt_state)
        return super()._initial_opt_state(params)

    def _adapt_z1c_state(self, host_state):
        """Restore flat compressed-ZeRO-1 optimizer state saved at ANY
        world size: the stored flat mirrors carry the saving run's
        padding (flat param count rounded up to ITS data-axis size), so
        leaves are trimmed to the model's true flat length and re-padded
        for this mesh before sharding.  Scalar leaves (step counters)
        pass through."""
        from jax.flatten_util import ravel_pytree
        total = int(ravel_pytree(self.model.params())[0].size)

        def adapt(v):
            arr = np.asarray(v)
            if arr.ndim >= 1 and arr.shape[0] >= total:
                arr = np.pad(arr[:total],
                             [(0, self._z1c_flat - total)]
                             + [(0, 0)] * (arr.ndim - 1))
            return _put_host(
                arr, NamedSharding(self.mesh, self._z1c_leaf_spec(arr)))

        return jax.tree_util.tree_map(adapt, host_state)

    def _state_trees(self):
        # used only to derive sharding specs: opt_state as abstract
        # ShapeDtypeStructs (the rules read .ndim/.shape), so building the
        # step never materializes a second model-sized state tree in HBM
        params = self.model.params()
        net_state = self.model.state()
        opt_state = jax.eval_shape(self.optim_method.init_state, params)
        return params, net_state, opt_state

    def _build_step_pipeline(self):
        """Pipeline-parallel train step through the same Optimizer front
        door (ref Optimizer.scala:151-186): partition the Sequential model
        into P stages, stream the batch as M microbatches under the chosen
        schedule, update each stage's params with the stage-local grads.
        Params/opt-state/net-state live stage-sharded on the ``pipe`` axis
        — per-device model memory is O(|model|/P), the point of PP."""
        from bigdl_tpu.parallel.pipeline import (pipeline_apply,
                                                 pipeline_train_1f1b)
        from bigdl_tpu.parallel.pipeline_model import partition_sequential


        # Shape peek from the TRAIN stream (the eval pass may end with a
        # partial batch and its first batch can differ from the looped
        # train batch size), with the host RNG snapshotted/restored: the
        # peek's shuffle permutation and augmentation draws must not
        # advance the stream, or every later batch would shift and the
        # trajectory would silently diverge from an identical
        # non-pipeline run.  (A PreFetch stage in the pipeline draws from
        # per-thread derived streams this snapshot cannot cover.)
        rng_state = RNG.np_rng().get_state()
        peek = next(iter(self.dataset.data(train=True)))
        RNG.np_rng().set_state(rng_state)
        xb = np.asarray(peek.data)
        B = xb.shape[0]
        M = self.pipeline_microbatches or 2 * self.pipeline_stages
        if B % M:
            raise ValueError(
                f"batch size {B} is not divisible by "
                f"pipeline_microbatches={M}")
        data_axis = ("data" if "data" in self.mesh.axis_names
                     and self.mesh.shape["data"] > 1 else None)
        if data_axis and (B // M) % self.mesh.shape["data"]:
            raise ValueError(
                f"microbatch size {B // M} is not divisible by the data "
                f"axis ({self.mesh.shape['data']}) — hybrid dp x pp "
                "shards each microbatch across the data replicas")
        plan = partition_sequential(self.model, self.pipeline_stages,
                                    (B // M,) + xb.shape[1:], axis="pipe")
        self._pipe_plan = plan
        logger.info("pipeline partition (schedule=%s, %d microbatches):\n%s",
                    self.pipeline_schedule, M, plan.describe())

        criterion, method = self.criterion, self.optim_method
        static_hyper = self._hyper(None)
        del static_hyper["lr"]
        if self._setup_lr_scales(static_hyper):
            raise ValueError("state['learningRates'] (per-param lr scales) "
                             "is not supported with pipeline_stages")
        mesh, schedule, remat = self.mesh, self.pipeline_schedule, self.remat
        loss_fn = plan.make_loss_fn(criterion)
        from bigdl_tpu.obs import taps as obs_taps
        taps_on = obs_taps.enabled(self._taps_enabled)

        def step(stacked_p, stacked_s, opt_state, x, y, lr, key, lr_scales):
            hyper = dict(static_hyper, lr=lr)
            xf = plan.pack_input(x.reshape((M, plan.mb) + x.shape[1:]))
            tm = y.reshape((M, plan.mb) + y.shape[1:])
            stage_fn = plan.make_stage_fn(key, fold_axis=data_axis)
            if schedule == "1f1b":
                loss, grads, new_s = pipeline_train_1f1b(
                    stage_fn, loss_fn, stacked_p, xf, tm, mesh, "pipe",
                    stage_state=stacked_s, data_axis=data_axis)
            else:
                def gpipe_loss(p, s):
                    outs, ns = pipeline_apply(stage_fn, p, xf, mesh, "pipe",
                                              remat=remat, stage_state=s,
                                              data_axis=data_axis)
                    return jax.vmap(loss_fn)(outs, tm).mean(), ns

                (loss, new_s), grads = jax.value_and_grad(
                    gpipe_loss, has_aux=True)(stacked_p, stacked_s)
            finite = _finite_all(loss, grads)
            new_p, new_opt = method.update(grads, opt_state, stacked_p,
                                           hyper)
            new_p = _where_finite(finite, new_p, stacked_p)
            new_opt = _where_finite(finite, new_opt, opt_state)
            new_s = _where_finite(finite, new_s, stacked_s)
            # taps over the stage-stacked trees: norms cover every
            # stage's params/grads at once (the stacking is just layout)
            taps = (obs_taps.compute(grads, stacked_p, new_p)
                    if taps_on else {})
            return new_p, new_s, new_opt, loss, finite, taps

        pipe = NamedSharding(mesh, P("pipe"))
        rep = NamedSharding(mesh, P())
        # opt-state leaves mirror the (P, max) stacked params and shard
        # over "pipe"; scalar leaves (Adagrad's step counter) replicate
        opt_shape = jax.eval_shape(
            method.init_state,
            jax.ShapeDtypeStruct((plan.n_stages, plan.max_p), jnp.float32))
        opt_s = jax.tree_util.tree_map(
            lambda l: pipe if l.ndim >= 1
            and l.shape[0] % plan.n_stages == 0 else rep, opt_shape)
        n = self.iters_per_dispatch
        fn = step if n <= 1 else self._scan_chunk(step, n)
        from bigdl_tpu.serve import xcache
        return xcache.tracked_jit(
            fn, ("pipeline_step", _model_fingerprint(self.model),
                 type(method).__name__, plan.n_stages,
                 "chunk%d" % n if n > 1 else "single"),
            key_argnums=(3, 4), mesh=mesh,
            in_shardings=(pipe, pipe, opt_s, rep, rep, rep, rep, rep),
            out_shardings=(pipe, pipe, opt_s, rep, rep, rep),
            donate_argnums=(0, 1, 2),
        )

    def _build_step(self):
        if self.pipeline_stages is not None:
            return self._build_step_pipeline()
        if self.gradient_compression or self._straggler is not None:
            # straggler drop needs the per-replica gradients only the
            # shard_map builder sees; it rides that path with a f32 wire
            # when compression is off
            return self._build_step_compressed()
        step = self._core_step()
        params, net_state, opt_state = self._state_trees()
        ps, ns, os_, data_s = self._shardings(params, net_state, opt_state)
        x_s = x_chunk_s = None
        if self.sequence_parallel:
            x_s = NamedSharding(self.mesh, P("data", "seq"))
            x_chunk_s = NamedSharding(self.mesh, P(None, "data", "seq"))
        return self._jit_step(step, ps, ns, os_, data_s, x_s, x_chunk_s)

    def _device_put_batch(self, x, y, stacked: bool = False):
        """Assemble the global sharded batch from this process's local
        shard.  ``stacked=True``: (n, local_B, ...) chunk for the
        device-side loop — sharded over "data" on dim 1."""
        mesh = self.mesh
        if self.pipeline_stages is not None:
            # pipeline operands arrive replicated and the engine's
            # shard_map reshards them (pure pp: in_specs P(); hybrid:
            # P(None, "data") — so hybrid pays a d-times-larger host
            # transfer than strictly needed; acceptable at current batch
            # sizes, revisit with a reshaped device_put if it shows up)
            spec = P()
        elif "data" in mesh.axis_names:
            spec = P(None, "data") if stacked else P("data")
        else:
            spec = P()   # e.g. a pure-EP mesh: batch replicates
        xspec = spec
        if self.sequence_parallel and spec != P():
            # inputs additionally shard their time dim over "seq"
            t_dim = 2 if stacked else 1
            xa = np.asarray(x)
            if xa.ndim <= t_dim or xa.shape[t_dim] % mesh.shape["seq"]:
                raise ValueError(
                    f"sequence_parallel needs input dim {t_dim} (time) "
                    f"divisible by the seq axis ({mesh.shape['seq']}); "
                    f"got shape {xa.shape}")
            xspec = (P(None, "data", "seq") if stacked
                     else P("data", "seq"))
        xsh = NamedSharding(mesh, xspec)
        ysh = NamedSharding(mesh, spec)
        if jax.process_count() == 1:
            return (jax.device_put(jnp.asarray(x), xsh),
                    jax.device_put(jnp.asarray(y), ysh))
        return (jax.make_array_from_process_local_data(xsh, np.asarray(x)),
                jax.make_array_from_process_local_data(ysh, np.asarray(y)))

    def _global_records_factor(self) -> int:
        """Host-batch → global-record multiplier for the prefetch
        producer's epoch arithmetic: multi-host data-sharded batches
        assemble ``process_count`` local shards into one global array
        (``make_array_from_process_local_data``); pipeline operands ride
        replicated, so their global batch equals the local one."""
        if jax.process_count() == 1 or self.pipeline_stages is not None:
            return 1
        if "data" in self.mesh.axis_names:
            return jax.process_count()
        return 1

    # -- elastic recovery (resilience/elastic.py, docs/resilience.md) ------

    def _elastic_session(self):
        """Arm recover-in-place for this run, or return None (and train
        with the historical fail-fast contract).  Armed only when every
        parameter bit is redundant across the surviving processes — pure
        data-parallel layouts (plain DP, zero1, gradient compression):
        pipeline/tensor/expert/sequence parallelism shard params across
        processes, so a dead peer takes the only copy of its slice."""
        from bigdl_tpu.resilience import elastic
        if not elastic.enabled() or jax.process_count() == 1:
            return None
        rt = elastic.runtime()
        if not rt.armed:
            logger.warning(
                "BIGDL_ELASTIC=1 but the job was not brought up through "
                "the elastic runtime (Engine.init_distributed with the "
                "flag set, or resilience.elastic.initialize): recover-in-"
                "place disabled — the stock runtime's heartbeat defaults "
                "abort survivors before any re-form could run")
            return None
        mode = None
        if self.pipeline_stages is not None:
            mode = "pipeline_stages"
        elif self.tensor_parallel:
            mode = "tensor_parallel"
        elif self.expert_parallel:
            mode = "expert_parallel"
        elif self.sequence_parallel:
            mode = "sequence_parallel"
        elif self._straggler is not None:
            mode = "straggler dropping"
        if mode is not None:
            logger.warning(
                "BIGDL_ELASTIC=1 ignored: %s is keyed to the original "
                "process world (params or policy state are not redundant "
                "across survivors); this run keeps the fail-fast "
                "watchdog contract", mode)
            return None
        try:
            cadence = max(1, int(os.environ.get("BIGDL_ELASTIC_ANCHOR",
                                                "1")))
        except ValueError:
            cadence = 1
        return {"keeper": elastic.AnchorKeeper(), "gather": None,
                "cadence": cadence}

    def _elastic_gather_fn(self):
        """The anchor gather: one dispatch producing fresh REPLICATED
        copies of (params, net_state, opt_state) — zero1 shards all-
        gather back to full leaves, so every survivor holds a complete
        host snapshot after the background D2H (the redundancy recovery
        reshards from)."""
        rep = NamedSharding(self.mesh, P())
        copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)
        return jax.jit(lambda p, s, o: (copy(p), copy(s), copy(o)),
                       out_shardings=(rep, rep, rep))

    def _elastic_offer(self, params, net_state, opt_state, state, count):
        """Enqueue a consistent anchor snapshot (async: the collective
        dispatches here, the D2H lands on the keeper's thread)."""
        es = self._elastic
        if es["gather"] is None:
            es["gather"] = self._elastic_gather_fn()
        pipeline = self._train_pipeline
        snap = T()
        snap.update(state)
        payload = {"state": snap, "neval": int(state["neval"]),
                   "epoch": int(state["epoch"]), "count": int(count),
                   "rng": (pipeline.rng_snapshot() if pipeline is not None
                           else RNG.snapshot())}
        # abandonable: on sync-dispatch backends the gather collective
        # runs right here, and a dead peer must not wedge the loop
        trees = self._guarded(
            lambda: es["gather"](params, net_state, opt_state))
        es["keeper"].offer(trees, payload)

    def _guarded(self, fn):
        """Host-blocking work (window flush, validation, checkpoint,
        preemption merge) runs abandonably while elastic is armed: a
        collective with a dead peer hangs forever on this backend, and
        the loop must reach its recovery point instead."""
        if self._elastic is None:
            return fn()
        from bigdl_tpu.resilience import elastic
        return elastic.guarded_sync(fn)

    def _flush_window(self, state, monitor, reason):
        if self._elastic is not None:
            from bigdl_tpu.resilience import elastic
            if elastic.tripped() is not None:
                # the pending scalars ride collectives the dead peer will
                # never join; PARK them (freeing a doomed buffer blocks
                # in the PJRT destructor) — the anchor is the resume truth
                if self._window is not None and self._window.pending:
                    elastic.runtime().leaked.append(
                        list(self._window.pending))
                    self._window.pending.clear()
                if reason == "exception":
                    return
                elastic.check()
            return self._guarded(
                lambda: super(DistriOptimizer, self)._flush_window(
                    state, monitor, reason))
        return super()._flush_window(state, monitor, reason)

    def _elastic_recover(self, trip):
        """The recovery protocol between two ``_optimize_run`` attempts:
        quiesce (the unwind already abandoned in-flight work), re-form
        the fleet at the reduced world size, restore the training state
        from the newest complete host anchor, re-partition the dataset,
        and hand back to the loop — which rebuilds mesh-keyed
        executables through the (reset) xcache registry on re-entry.
        Raises ``ReformAbort`` when recovery is impossible; the caller
        falls back to the fail-fast exit."""
        from bigdl_tpu.resilience import elastic
        es = self._elastic
        started = time.monotonic() - (elastic.trip_age() or 0.0)
        obs_events.emit("recover", kind="quiesce",
                        step=int(self.state["neval"]),
                        stale=sorted(trip.stale))
        anchor = es["keeper"].latest()
        world_before = int(elastic.runtime().world or jax.process_count())
        elastic.reform(trip.stale)   # ReformAbort propagates to caller
        world_after = jax.process_count()
        self.mesh = data_parallel_mesh()
        Engine.init()                # refresh node/core counts
        # training state: the anchor is the last consistent step
        self.model.load_params(anchor.params)
        self.model.load_state(anchor.net_state)
        self._resume_opt_state = anchor.opt_state
        self.state.update(anchor.state)
        self.state["neval"] = anchor.neval
        self.state["epoch"] = anchor.epoch
        RNG.restore(anchor.rng)
        self._elastic_resume_count = anchor.count
        self._reshard_dataset()
        # executables, device copies and writer threads are keyed to the
        # abandoned runtime; drop them (xcache was reset in the reform)
        self._ckpt_copy_fn = None
        self._ckpt_writer = None
        self._lr_scales_arg = None
        # the old keeper's drain thread may be wedged on a doomed gather
        # (and its queue holds doomed buffers) — park it with the rest of
        # the old runtime and seed a fresh one from the anchor on host
        elastic.runtime().leaked.append((es["keeper"], es["gather"]))
        keeper = elastic.AnchorKeeper()
        keeper.capture_sync(
            (anchor.params, anchor.net_state, anchor.opt_state),
            {"state": anchor.state, "neval": anchor.neval,
             "epoch": anchor.epoch, "count": anchor.count,
             "rng": anchor.rng})
        es["keeper"] = keeper
        es["gather"] = None
        obs_events.emit("recover", kind="reshard", step=int(anchor.neval),
                        world_after=int(world_after))
        pause = time.monotonic() - started
        obs_events.emit("recover", kind="resume", step=int(anchor.neval),
                        world_before=int(world_before),
                        world_after=int(world_after),
                        pause_s=round(pause, 4))
        logger.warning(
            "elastic: resuming from in-memory anchor at neval=%d epoch=%d "
            "(world %d -> %d, recovery pause %.2fs, no checkpoint read)",
            anchor.neval, anchor.epoch, world_before, world_after, pause)

    def _reshard_dataset(self):
        """Walk the dataset chain and re-key every world-size-dependent
        stage to the LIVE topology: ShardedDataSet strided shards and
        ``SampleToBatch(global_batch_size=...)`` local batches.  A
        global batch that does not divide the re-formed world is a
        recovery failure HERE (uniform exit 43), not a raw unwind at
        the first post-recovery iteration."""
        from bigdl_tpu.resilience import elastic

        def check_batch(t):
            gbs = getattr(t, "global_batch_size", None)
            if gbs and gbs % jax.process_count():
                raise elastic.ReformAbort(
                    f"global batch {gbs} cannot be divided over the "
                    f"re-formed world of {jax.process_count()} "
                    "process(es)")
            for sub in getattr(t, "transformers", None) or ():
                check_batch(sub)

        for root in (self.dataset, self.validation_dataset):
            ds = root
            while ds is not None:
                if hasattr(ds, "reshard"):
                    ds.reshard()
                t = getattr(ds, "transformer", None)
                if t is not None:
                    check_batch(t)
                ds = getattr(ds, "base", None)

    def _elastic_fail(self, abort):
        """Recovery was impossible: honor the historical fail-fast
        contract — same crash bundle and exit code as the watchdog's
        default policy, so operators see ONE failure shape."""
        from bigdl_tpu.resilience import elastic
        from bigdl_tpu.resilience.watchdog import EXIT_CODE
        logger.error("elastic: recover-in-place impossible (%s) — "
                     "falling back to the fail-fast exit %d",
                     abort, EXIT_CODE)
        try:
            obs_events.emit("recover", kind="abort", reason=str(abort))
            from bigdl_tpu.obs import diagnostics
            import threading
            t = threading.Thread(
                target=lambda: diagnostics.dump_crash_bundle(
                    "elastic-abort", extra={"reason": str(abort)}),
                daemon=True, name="bigdl-elastic-postmortem")
            t.start()
            t.join(timeout=3.0)
        except Exception:
            logger.exception("elastic abort crash bundle failed")
        if elastic.runtime().orig_index == 0:
            # this process hosts the coordination service: linger so the
            # other survivors' exit-43 lands before the socket close
            # SIGABRTs them mid-unwind (the watchdog's grace, one knob)
            dog = elastic.runtime().watchdog
            time.sleep(dog.coordinator_grace if dog is not None else 2.0)
        os._exit(EXIT_CODE)

    def optimize(self):
        from bigdl_tpu.resilience import elastic
        self._elastic = self._elastic_session()
        self._elastic_resume_count = None
        if self._elastic is None:
            return self._optimize_run()
        try:
            while True:
                try:
                    return self._optimize_run()
                except Exception as err:
                    if isinstance(err, elastic.PeerLossRecovery):
                        trip = err
                    else:
                        # a dead peer surfaces as an immediate collective
                        # error (gloo TCP reset) well before the heartbeat
                        # timeout — park for the watchdog's verdict; only
                        # a confirmed peer death converts into recovery
                        logger.warning(
                            "elastic: training raised %s: %s — awaiting "
                            "the watchdog's verdict before treating it as "
                            "peer loss", type(err).__name__, err)
                        trip = elastic.await_trip()
                        if trip is None:
                            raise
                    # the unwound traceback's frames reference buffers
                    # whose defining computation involves the dead peer;
                    # FREEING such a buffer blocks forever in the PJRT
                    # destructor (awaiting the definition event) — park
                    # the whole traceback with the rest of the doomed
                    # runtime instead of letting it die here
                    elastic.runtime().leaked.append(err)
                try:
                    self._elastic_recover(trip)
                except Exception as abort:
                    # ANY recovery failure — quorum/timeout aborts or an
                    # unexpected error after the new world formed — takes
                    # the uniform fail-fast exit; a raw unwind here would
                    # strand the other survivors in the re-formed
                    # collectives with an arbitrary exit code
                    self._elastic_fail(abort)
        finally:
            self._elastic = None

    def _optimize_run(self):
        state = self.state
        state.get_or_update("epoch", 1)
        state.get_or_update("neval", 1)
        # see LocalOptimizer.optimize: a resumed state blob may carry the
        # previous run's preemption mark
        state["preempted"] = False

        step_fn = self._build_step()  # pipeline mode builds its plan here
        # ledger key for the windowed train_mfu gauge (pipeline-mode
        # steps carry no fn_key; the gauge just stays silent there)
        self._step_fn_key = getattr(step_fn, "fn_key", None)
        params = jax.tree_util.tree_map(jnp.copy, self.model.params())
        net_state = jax.tree_util.tree_map(jnp.copy, self.model.state())
        if self._pipe_plan is not None:
            pipe_s = NamedSharding(self.mesh, P("pipe"))
            params = jax.device_put(self._pipe_plan.pack_params(params),
                                    pipe_s)
            net_state = jax.device_put(self._pipe_plan.pack_state(net_state),
                                       pipe_s)
        opt_state = self._initial_opt_state(params)
        self._resume_opt_state = None   # consumed; never reuse a stale tree
        monitor = self._start_obs_run()

        count = 0
        if self._elastic is not None and self._elastic_resume_count:
            # post-recovery re-entry: continue the interrupted epoch's
            # record count from the anchor (docs/resilience.md: the epoch
            # TAIL re-reads from the re-sharded stream)
            count = int(self._elastic_resume_count)
        self._elastic_resume_count = None
        epoch_size = self.dataset.size()
        n_disp = self.iters_per_dispatch
        straggler = self._straggler
        # straggler drop re-times and accepts/rejects every iteration on
        # the host, so it keeps the per-step sync; _make_train_pipeline
        # already returns None for it
        pipeline = self._make_train_pipeline(n_disp, epoch_size)
        self._train_pipeline = pipeline
        data_iter = None if pipeline is not None \
            else self.dataset.data(train=True)
        self._window = _HostSyncWindow(
            1 if straggler is not None else self._sync_cadence())
        wall_start = time.perf_counter()

        if self._elastic is not None:
            from bigdl_tpu.resilience import elastic as elastic_mod
            # generation-0 anchor: a peer death before the first step's
            # snapshot must still find a complete resume point
            self._elastic_offer(params, net_state, opt_state, state, count)

        try:
            # the end trigger stays outside every span and outside the
            # ``loop`` counter (see LocalOptimizer.optimize)
            while not self.end_when(state):
                fetch_start = time.perf_counter()   # the iteration's top
                if self._elastic is not None:
                    elastic_mod.check()   # raises PeerLossRecovery on trip
                neval0 = int(state["neval"])
                epoch0 = int(state["epoch"])
                self._window.arm()
                dev = qdepth = None
                with self.spans.span("data-load"):
                    if pipeline is not None:
                        # the span measures the CONSUMER's wait only; the
                        # producer's transform wall rides data-load/fetch
                        item, waited = pipeline.get()
                        self._drain_pipeline_obs(pipeline, item, waited,
                                                 neval0)
                        qdepth = item.queue_depth
                        if item.device is not None:
                            dev = item.device
                    elif n_disp <= 1:
                        batch = next(data_iter)
                        xh = self._chaos_prestep(batch.data, neval0)
                        yh = batch.labels
                    else:
                        xh, yh = self._next_chunk(data_iter, n_disp)
                        xh = self._chaos_prestep(xh, neval0)
                if dev is None:
                    if pipeline is not None:
                        # chaos host mode: poison at CONSUME time, so
                        # every site stays keyed by the consuming step
                        xh = self._chaos_prestep(item.x, neval0)
                        yh = item.y
                    with self.spans.span("h2d"):
                        dev = self._device_put_batch(xh, yh,
                                                     stacked=n_disp > 1)
                x, y = dev
                global_b = (x.shape[0] * x.shape[1] if n_disp > 1
                            else x.shape[0])
                fetch_wall = time.perf_counter() - fetch_start

                drop_mask = None
                if straggler is not None:
                    drop_mask = straggler.mask()
                    if not straggler.accepts(drop_mask):
                        # iteration rejected: batch consumed, no update, no
                        # neval advance (ref DistriOptimizer.scala:224 guard)
                        straggler.reject(drop_mask)
                        self.spans.record(
                            "loop", time.perf_counter() - fetch_start)
                        continue

                # distributed: summary() adds the per-process breakdown,
                # the reference's "computing time for each node" accumulator
                it_start = time.perf_counter()
                with self.spans.span("dispatch"), \
                        self.metrics.timer("computing time average",
                                           distributed=True):
                    lr = self._current_lr()
                    key = RNG.next_key()
                    step_args = (params, net_state, opt_state, x, y,
                                 jnp.float32(lr), key, self._lr_scales_arg)
                    if straggler is not None:
                        (params, net_state, opt_state, loss, finite,
                         taps) = step_fn(*step_args, jnp.asarray(drop_mask))
                        # the device→host transfer blocks, so the timer
                        # (and the straggler's task clock) sees the real
                        # dispatch wall — the one mode that syncs per
                        # step.  The HOST array rides the window so the
                        # cadence-1 flush does not transfer a second time.
                        loss = np.asarray(loss)
                    elif self._elastic is not None:
                        # on backends that execute collectives on the
                        # dispatching thread (multi-process CPU), a step
                        # whose peer died would wedge the loop right here
                        # — run it abandonably
                        (params, net_state, opt_state, loss, finite,
                         taps) = self._guarded(lambda: step_fn(*step_args))
                    else:
                        (params, net_state, opt_state, loss, finite,
                         taps) = step_fn(*step_args)
                train_time = time.perf_counter() - it_start

                n_dropped = 0
                if straggler is not None:
                    with self.spans.span("aggregate"):
                        # the cross-process task-time merge (allgather)
                        straggler.record(self._straggler_task_times(
                            fetch_wall, time.perf_counter() - it_start),
                            drop_mask)
                    n_dropped = int(len(drop_mask) - drop_mask.sum())
                    if n_dropped:
                        # ref logger.debug("Dropped modules: " + ...) :248
                        logger.debug("Dropped modules: %d", n_dropped)
                        # only the finished tasks' records count toward the
                        # epoch (ref recordsNum += finishedThreads.size *
                        # stackSize, accumulateCount += recordsNum :236)
                        global_b = int(global_b * float(drop_mask.sum())
                                       / len(drop_mask))
                with self.spans.span("bookkeep"):
                    count += global_b
                    state["neval"] = neval0 + n_disp
                    state["evalCounter"] = \
                        state.get("evalCounter", 0) + n_disp
                    extra = {}
                    if n_dropped:
                        extra["straggler_dropped"] = n_dropped
                    if qdepth is not None:
                        extra["queue_depth"] = int(qdepth)
                    self._window.push(_PendingStep(
                        neval0, epoch0, count, loss, finite, taps, lr,
                        global_b, fetch_wall, train_time, extra))
                    rolled = count >= epoch_size
                    count, data_iter = self._advance_epochs(
                        state, count, epoch_size, n_disp, data_iter,
                        pipeline)
                if self._elastic is not None and \
                        neval0 % self._elastic["cadence"] == 0:
                    # consistent post-step snapshot (post-rollover: the
                    # epoch's shuffle draw is already in the RNG payload)
                    self._elastic_offer(params, net_state, opt_state,
                                        state, count)
                if self._window.due() or rolled:
                    self._flush_window(state, monitor,
                                       "epoch" if rolled else "cadence")
                with self.spans.span("bookkeep"):
                    ne_val = self._fired_within(self.validation_trigger,
                                                state, n_disp)
                    ne_ck = self._fired_within(self.checkpoint_trigger,
                                               state, n_disp)
                    preempt = self._preemption_pending()
                if preempt or ne_val is not None or ne_ck is not None:
                    self._flush_window(state, monitor,
                                       "preempt" if preempt else "trigger")
                if ne_val is not None:
                    self._guarded(lambda: self._maybe_validate(
                        params, net_state, state, force=True))
                if ne_ck is not None:
                    self._guarded(lambda: self._maybe_checkpoint(
                        params, net_state, opt_state, state, force=True,
                        neval_label=ne_ck))
                if preempt:
                    self._checkpoint_and_stop(params, net_state, opt_state,
                                              state)
                self.spans.record("loop", time.perf_counter() - fetch_start)
                if preempt:
                    break
            flush_start = time.perf_counter()
            self._flush_window(state, monitor, "run-end")
            self.spans.record("loop", time.perf_counter() - flush_start,
                              count=0)
        finally:
            try:
                # see LocalOptimizer.optimize: crash-adjacent steps must
                # reach the event stream before the pipeline tears down
                self._flush_window(state, monitor, "exception")
            except Exception as e:
                logger.warning("pending-step flush during unwind "
                               "failed: %s", e)
            if pipeline is not None:
                pipeline.close()
            self._train_pipeline = None
            if self._ckpt_writer is not None:
                if self._elastic is None:
                    self._flush_ckpt_writer("run end")
                elif elastic_mod.tripped() is None:
                    # a possibly-doomed unwind: bound the wait — if this
                    # turns into a recovery, _elastic_recover drops the
                    # writer (its thread may be wedged on dead arrays)
                    self._flush_ckpt_writer("elastic unwind", timeout=5.0)

        # gather (replicated -> host) and write back, ref getModel :475-499
        if self._pipe_plan is not None:
            params = self._pipe_plan.unpack_params(params)
            net_state = self._pipe_plan.unpack_state(net_state)
        self.model.load_params(jax.device_get(params))
        self.model.load_state(jax.device_get(net_state))
        # snapshot per-node metrics while every process is still here, so
        # post-training summary(per_node=True) from one process is safe
        # (also what makes the per-host span table below deadlock-free:
        # process 0 renders from the cache, no late collective)
        self.metrics.collect_per_node()
        self._end_obs_run(state, wall_start)
        if jax.process_index() == 0:
            logger.info("per-host phase breakdown (mean s/iter):\n%s",
                        self.spans.per_host_report())
        logger.info("Training finished in %.1fs", time.perf_counter() - wall_start)
        return self.model

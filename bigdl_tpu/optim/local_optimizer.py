"""LocalOptimizer — single-host training with one compiled step
(ref optim/LocalOptimizer.scala:40, call stack SURVEY.md §3.2).

The reference clones coreNumber model replicas on JVM threads and reduces
their gradients slice-wise; on TPU one ``jit``-compiled
forward+loss+grad+update over the full local batch saturates the chip, so
the replica machinery dissolves (SURVEY.md §2.9: intra-node splitting is a
JVM-thread artifact).  What is kept, capability-for-capability:

- iteration loop with epoch/neval state Table (keys match the reference for
  checkpoint parity),
- throughput + data-fetch vs train-time logging (LocalOptimizer.scala:151),
- Trigger-driven validation and checkpointing,
- OptimMethod with Table config (SGD schedules update the lr host-side;
  the scalar feeds the compiled step as an argument, so no retrace).
"""
from __future__ import annotations

import collections
import logging
import os
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.dataset import prefetch as prefetch_mod
from bigdl_tpu.nn.containers import kept_report
from bigdl_tpu.nn.module import Context
from bigdl_tpu.obs import events as obs_events
from bigdl_tpu.obs import taps as obs_taps
from bigdl_tpu.obs.spans import SpanTracker, render_timeline
from bigdl_tpu.optim.optim_method import SGD, OptimMethod, Default
from bigdl_tpu.optim import trigger as triggers
from bigdl_tpu.optim.metrics import Metrics
from bigdl_tpu.parallel.ring_attention import walk_report
from bigdl_tpu.utils.table import Table, T
from bigdl_tpu.utils import file as File
from bigdl_tpu.utils.engine import Engine
from bigdl_tpu.utils.log import warn_every
from bigdl_tpu.utils.random import RNG

logger = logging.getLogger("bigdl_tpu.optim")


class NonFiniteGradError(RuntimeError):
    """Training aborted: non-finite gradients for more consecutive steps
    than the abort threshold (``set_nonfinite_policy`` /
    ``BIGDL_NONFINITE_ABORT``) — the run has diverged and skipping can no
    longer save it."""


def _finite_all(loss, grads):
    """One scalar: loss AND every gradient leaf finite.  Computed inside
    the existing jit step (a handful of VPU reductions fused into the
    backward), so the happy path pays no extra dispatch."""
    finite = jnp.all(jnp.isfinite(loss))
    for g in jax.tree_util.tree_leaves(grads):
        finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(g)))
    return finite


def _where_finite(finite, new_tree, old_tree):
    """Skip-step select: keep the pre-step value on every leaf when the
    step produced non-finite gradients (the update, optimizer state and
    BN running stats are all poisoned by one NaN)."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(finite, n, o), new_tree, old_tree)


class _PendingStep:
    """One dispatched-but-not-yet-synced iteration: device scalars (loss,
    finite flag, tap dict) plus the host-side bookkeeping captured at
    dispatch time, held until the next cadence flush."""

    __slots__ = ("neval0", "epoch", "count", "loss", "finite", "taps",
                 "lr", "records", "fetch_t", "train_t", "extra")

    def __init__(self, neval0, epoch, count, loss, finite, taps, lr,
                 records, fetch_t, train_t, extra):
        self.neval0 = neval0
        self.epoch = epoch
        self.count = count
        self.loss = loss
        self.finite = finite
        self.taps = taps
        self.lr = lr
        self.records = records
        self.fetch_t = fetch_t
        self.train_t = train_t
        self.extra = extra


#: flushes whose only purpose is to bring the host's record up to date.
#: Every other reason (``trigger``, ``preempt``, ``run-end``, ``exception``)
#: needs the device's last word and drains.
_RECORD_FLUSHES = ("cadence", "epoch")


class _HostSyncWindow:
    """Cadence-gated device→host synchronization for the training loops
    (docs/observability.md "host pipeline").

    The serial loop ended every iteration in ``float(loss)`` — a
    blocking device→host copy that stops the host from dispatching the
    next step while the device finishes this one.  Instead the loop
    parks each step's device scalars
    here and materializes them in one blocking batch every ``cadence``
    iterations (the same elapsed-iterations gate, and therefore the same
    boundaries, as ``obs.taps.TapsMonitor``), at epoch/validation/
    checkpoint boundaries, on preemption, and at run end.  The in-jit
    skip-step guard (PR 1) keeps params safe between syncs.

    **A flush that only brings the record up to date leaves the newest
    step running.**  Waiting for the step dispatched a moment ago empties
    the device, and everything the loop does at a boundary (the log
    lines, the next batch, the key, the call) then runs with nothing on
    it.  So a ``cadence`` or ``epoch`` flush at a cadence above 1
    materializes every pending step but the last one pushed; that step
    stays the head of ``pending`` and goes out with the next flush.
    ``state["loss"]``, the non-finite ledger and the step events are then
    one step behind the dispatch (they already lag by up to a cadence
    between flushes).  Whatever needs the device's last word drains as
    before: ``trigger`` (validation, checkpoint), ``preempt``,
    ``run-end``, ``exception``, and every flush at cadence 1
    (``BIGDL_SYNC_EVERY_STEP``, straggler mode).  The rule is
    :meth:`flushable`, decided by the reason and the cadence alone.

    ``flush_steps``/``flush_reasons``/``flush_kept`` are the audit trail
    the sync-count test asserts on: the last step each flush materialized,
    why, and the step it left in flight (None: it drained).  Host syncs
    happen at flush boundaries, nowhere else.
    """

    def __init__(self, cadence: int):
        self.cadence = max(1, int(cadence))
        self.pending: list[_PendingStep] = []
        self._last_flush = 0
        self._t0 = None
        self.flush_steps = deque(maxlen=1024)
        self.flush_reasons = deque(maxlen=1024)
        self.flush_kept = deque(maxlen=1024)

    def arm(self):
        """Start the window wall clock — called at the top of the first
        iteration the window covers, so the flushed throughput spans
        fetch + dispatch + sync like the serial per-step number did."""
        if self._t0 is None:
            self._t0 = time.perf_counter()

    def push(self, entry: _PendingStep):
        self.arm()
        self.pending.append(entry)

    def in_flight(self) -> int:
        """Steps dispatched whose loss the device has not produced yet.
        Every dispatched and unflushed step is in ``pending`` and the
        device runs them in order, so the walk goes from the newest and
        stops at the first ready one: no wait, no transfer, and a cost
        that is the depth and not the cadence."""
        n = 0
        for entry in reversed(self.pending):
            if entry.loss.is_ready():
                break
            n += 1
        return n

    def dispatched_since_flush(self) -> bool:
        """Whether a step was pushed after the last flush: ``pending``
        alone does not say, its newest may be the step that flush kept."""
        return bool(self.pending) and not (
            self.flush_kept
            and self.pending[-1].neval0 == self.flush_kept[-1])

    def flushable(self, reason: str) -> int:
        """How many of the pending steps a flush for ``reason`` would
        materialize: all of them, or all but the newest (see the class)."""
        keep = int(self.cadence > 1 and reason in _RECORD_FLUSHES)
        return max(0, len(self.pending) - keep)

    def due(self) -> bool:
        """Same chunk-safe gate as ``TapsMonitor``, over the steps a
        cadence flush would materialize: at least ``cadence`` iterations
        have begun between the last flushed step and the newest of them.
        A flush so covers ``cadence`` steps, and a window that holds
        only the step the last flush kept is never due."""
        n = self.flushable("cadence")
        return n > 0 and \
            (self.pending[n - 1].neval0 - self._last_flush) >= self.cadence

    def flush(self, reason: str):
        """Materialize what :meth:`flushable` says (the only device→host
        block in the loop) and book the audit trail.  Returns (entries,
        losses, finites, window_wall), or None where there is nothing to
        materialize.  Where a step stays in flight the wall clock is
        armed again at once: that step is the next window's first, so
        each window's wall is that of the steps it materializes."""
        n = self.flushable(reason)
        if not n:
            return None
        entries, self.pending = self.pending[:n], self.pending[n:]
        losses = [np.asarray(e.loss) for e in entries]
        finites = [np.asarray(e.finite) for e in entries]
        now = time.perf_counter()
        wall = (now - self._t0) if self._t0 else 0.0
        self._t0 = now if self.pending else None
        self._last_flush = entries[-1].neval0
        self.flush_steps.append(entries[-1].neval0)
        self.flush_reasons.append(reason)
        self.flush_kept.append(
            self.pending[0].neval0 if self.pending else None)
        return entries, losses, finites, wall


class LocalOptimizer:
    def __init__(self, model, dataset, criterion):
        self.model = model
        self.dataset = dataset
        self.criterion = criterion
        self.optim_method: OptimMethod = SGD()
        self.state = T()
        self.end_when = triggers.max_epoch(10)
        self.validation_trigger = None
        self.validation_dataset = None
        self.validation_methods = None
        self.checkpoint_trigger = None
        self.checkpoint_path = None
        self.metrics = Metrics()
        self.remat = False
        self._resume_opt_state = None
        self.iters_per_dispatch = 1
        # non-finite-grad policy: skip the update (params/opt-state/BN
        # stats keep their pre-step values), count, abort after this many
        # CONSECUTIVE bad steps (0/None = never abort)
        self.nonfinite_abort = int(
            os.environ.get("BIGDL_NONFINITE_ABORT", "10"))
        self._nonfinite_skips = 0
        self._nonfinite_streak = 0
        # observability (docs/observability.md): in-jit taps (None =
        # BIGDL_OBS_TAPS / _CADENCE env defaults), phase spans, optional
        # TensorBoard sinks
        self._taps_enabled = None
        self._taps_cadence = None
        self._taps_monitor = None
        self._train_summary = None
        self._val_summary = None
        self.spans = SpanTracker(self.metrics)
        # async host pipeline (dataset/prefetch.py): live runner + the
        # cadence window, both set up per optimize() run
        self._train_pipeline = None
        self._window = None
        # async/sharded checkpointing (resilience/checkpoint.py): lazy
        # writer thread + the non-donated device-copy jit it feeds from
        self._ckpt_writer = None
        self._ckpt_copy_fn = None
        # elastic recovery session (resilience/elastic.py) — armed by the
        # DistriOptimizer loop when BIGDL_ELASTIC=1 on a multi-process run
        self._elastic = None

    def set_taps(self, enabled: bool | None = None,
                 cadence: int | None = None):
        """Override the in-jit tap gating for this run (None defers to
        ``BIGDL_OBS_TAPS`` / ``BIGDL_OBS_TAPS_CADENCE``).  Takes effect
        at the next ``optimize()`` — the taps are part of the compiled
        step."""
        self._taps_enabled = enabled
        self._taps_cadence = cadence
        return self

    def set_train_summary(self, summary):
        """TensorBoard training-curve sink (``obs.TrainSummary``):
        Loss/LearningRate/Throughput per iteration, tap scalars at the
        taps cadence.  Multi-host: attach on process 0 only (the
        reference's driver-side TrainSummary)."""
        self._train_summary = summary
        return self

    def set_val_summary(self, summary):
        """TensorBoard validation sink (``obs.ValidationSummary``): one
        scalar per validation method at each validation trigger."""
        self._val_summary = summary
        return self

    def set_nonfinite_policy(self, abort_after: int | None = 10):
        """Abort training (NonFiniteGradError) after ``abort_after``
        consecutive skipped steps; 0/None keeps skipping forever.  The
        detection itself is always on — it folds into the jit step for
        free (ref has no equivalent: a NaN there poisons the
        AllReduceParameter weights silently)."""
        self.nonfinite_abort = int(abort_after or 0)
        return self

    def set_gradient_checkpointing(self, enabled: bool = True):
        """Rematerialize the forward inside backward (``jax.checkpoint``):
        trades FLOPs for HBM — the TPU-native replacement for the
        reference's shared-buffer memory tricks (SpatialShareConvolution,
        ResNet.shareGradInput)."""
        self.remat = enabled
        return self

    # -- builder config (ref Optimizer.scala:66-124) ----------------------
    def set_state(self, state: Table):
        self.state.update(state)
        return self

    def set_optim_method(self, method: OptimMethod):
        self.optim_method = method
        return self

    def set_iterations_per_dispatch(self, n: int):
        """Device-side training loop: ONE dispatch runs ``n`` train steps
        via ``lax.scan``, each consuming a DISTINCT minibatch from a
        stacked host transfer.  Where a step's device work is shorter
        than one host dispatch, this recovers the device-limited rate.
        Semantics:
        triggers/validation/checkpoint/lr updates happen at dispatch
        (n-step) granularity, and ``state['loss']`` is the chunk's last
        step.  Batches inside a chunk must share one shape (the standard
        looped training iterators guarantee this)."""
        self.iters_per_dispatch = max(1, int(n))
        return self

    def set_optim_state(self, opt_state):
        """Restore the optimizer's internal state (momentum velocity
        etc.) from a ``state.N`` snapshot's ``opt_state`` entry — without
        this a momentum run resumes with zeroed velocity and diverges
        from the uninterrupted trajectory (ref: state Table + internal
        buffers both persist through Optimizer.saveState,
        OptimMethod.scala clearHistory/state)."""
        self._resume_opt_state = opt_state
        return self

    def set_end_when(self, end_when):
        self.end_when = end_when
        return self

    def _initial_opt_state(self, params):
        """Fresh optimizer state, or the restored snapshot from
        set_optim_state.  The snapshot is COPIED: the donating jit step
        would otherwise delete the caller's buffers after one dispatch
        (same guard as the params/net_state copies in optimize())."""
        if self._resume_opt_state is not None:
            return jax.tree_util.tree_map(lambda v: jnp.array(v),
                                          self._resume_opt_state)
        return self.optim_method.init_state(params)

    def set_validation(self, trigger, dataset, methods):
        self.validation_trigger = trigger
        self.validation_dataset = dataset
        self.validation_methods = methods
        return self

    def set_checkpoint(self, path, trigger):
        self.checkpoint_path = path
        self.checkpoint_trigger = trigger
        return self

    # -- hyper extraction --------------------------------------------------
    def _hyper(self, lr):
        s = self.state
        return {
            "lr": lr,
            "weight_decay": float(s.get("weightDecay", 0.0)),
            "momentum": float(s.get("momentum", 0.0)),
            "dampening": float(s.get("dampening", s.get("momentum", 0.0))),
            "nesterov": bool(s.get("nesterov", False)),
            "lr_decay": float(s.get("learningRateDecay", 0.0)),
            # per-param lr multipliers shaped like model.params()
            # (ref SGD.scala "learningRates"); baked into the trace
            "lr_scales": s.get("learningRates", None),
        }

    def _current_lr(self):
        schedule = self.state.get("learningRateSchedule", Default())
        schedule.update_hyper_parameter(self.state, self.state)
        return -self.state.get("currentLearningRate", -self.state.get("learningRate", 1e-3))

    def _setup_lr_scales(self, static_hyper):
        """Per-param lr multipliers flow in as a jit ARGUMENT (not a baked
        constant, which would duplicate a model-sized tree in the
        executable); a scalar dummy stands in when unused."""
        has_scales = static_hyper.pop("lr_scales", None) is not None
        if has_scales:
            if not isinstance(self.optim_method, SGD):
                raise ValueError(
                    "state['learningRates'] (per-param lr scales) is only "
                    f"supported by SGD, not {type(self.optim_method).__name__}"
                    " — it would be silently ignored")
            self._lr_scales_arg = jax.tree_util.tree_map(
                jnp.asarray, self.state["learningRates"])
        else:
            self._lr_scales_arg = jnp.zeros(())
        return has_scales

    def _build_step(self):
        model, criterion, method = self.model, self.criterion, self.optim_method
        # non-lr hypers are fixed for the run: bake them in as trace-time
        # constants (nesterov/momentum branches resolve at compile time);
        # only the scheduled lr flows in as a traced scalar.
        static_hyper = self._hyper(None)
        del static_hyper["lr"]
        has_scales = self._setup_lr_scales(static_hyper)

        remat = self.remat
        taps_on = obs_taps.enabled(self._taps_enabled)

        # XLA names the module after this function, and the name is part
        # of the persistent compile cache's key while op metadata (the
        # scopes below, the stack frames) is not: an executable cached by
        # a commit whose step carried other scopes is served with THAT
        # commit's names.  The step got its scopes under this name.
        def train_step(params, net_state, opt_state, x, y, lr, key,
                       lr_scales):
            hyper = dict(static_hyper, lr=lr)
            if has_scales:
                hyper["lr_scales"] = lr_scales

            def loss_fn(p):
                apply = model.apply
                if remat:
                    apply = jax.checkpoint(
                        lambda p_, x_: model.apply(
                            p_, x_, net_state, Context(training=True, key=key)))
                    out, ns = apply(p, x)
                else:
                    out, ns = apply(p, x, net_state, Context(training=True, key=key))
                with jax.named_scope(type(criterion).__name__):
                    return criterion.apply_loss(out, y), ns

            # runs when the step is traced: what the model's Recomputes
            # kept for the backward pass, and how its attention cores'
            # backward walks, go into the log, once a trace
            with kept_report() as report, walk_report() as cores:
                (loss, new_net_state), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            if report["layers"]:
                obs_events.emit("recompute", **report)
            if cores:
                obs_events.emit("attention_walk", cores=cores)
            # the scopes name the update's and the taps' operations in a
            # profile (metadata only, as the module scopes of nn/containers)
            with jax.named_scope("optim-update"):
                finite = _finite_all(loss, grads)
                new_params, new_opt_state = method.update(
                    grads, opt_state, params, hyper)
                new_params = _where_finite(finite, new_params, params)
                new_opt_state = _where_finite(finite, new_opt_state,
                                              opt_state)
                new_net_state = _where_finite(finite, new_net_state,
                                              net_state)
            # in-jit taps: extra outputs of the SAME dispatch, post-skip-
            # select so update_ratio reads 0 on a skipped step
            with jax.named_scope("obs-taps"):
                taps = (dict(obs_taps.compute(grads, params, new_params),
                             **obs_taps.module_counters(new_net_state))
                        if taps_on else {})
            return (new_params, new_net_state, new_opt_state, loss, finite,
                    taps)

        # donate the carried state: the old params/opt-state buffers are
        # dead after each step, so XLA reuses them instead of allocating a
        # second copy of the model per step (lr_scales is reused each call
        # and must NOT be donated).  Dispatches register in the shared
        # executable cache (serve/xcache.py) keyed on the batch operands
        # only, so train rides the same compile accounting as eval/serve.
        from bigdl_tpu.serve import xcache
        fn_key = ("train_step", _model_fingerprint(self.model),
                  type(self.optim_method).__name__)
        n = self.iters_per_dispatch
        if n <= 1:
            return xcache.tracked_jit(train_step, fn_key,
                                      key_argnums=(3, 4),
                                      donate_argnums=(0, 1, 2))
        return xcache.tracked_jit(self._scan_chunk(train_step, n),
                                  fn_key + ("chunk%d" % n,),
                                  key_argnums=(3, 4),
                                  donate_argnums=(0, 1, 2))

    @staticmethod
    def _scan_chunk(step, n):
        """Wrap a per-step train fn in the device-side n-step loop
        (shared by Local and Distri builders)."""
        from jax import lax

        def chunk(params, net_state, opt_state, xs, ys, lr, key, lr_scales):
            keys = jax.random.split(key, n)

            def body(carry, xyk):
                p, ns, o = carry
                x, y, k = xyk
                p, ns, o, loss, finite, taps = step(p, ns, o, x, y, lr, k,
                                                    lr_scales)
                return (p, ns, o), (loss, finite, taps)

            ((params, net_state, opt_state),
             (losses, finites, taps)) = lax.scan(
                body, (params, net_state, opt_state), (xs, ys, keys))
            # taps leaves arrive stacked (n,); the host monitor reports
            # the chunk's last step, matching state['loss']
            return params, net_state, opt_state, losses, finites, taps

        return chunk

    @staticmethod
    def _next_chunk(data_iter, n):
        """Draw n uniform-shape batches and stack them host-side (each
        batch converted once — see ``prefetch.stack_chunk``)."""
        return prefetch_mod.stack_chunk([next(data_iter) for _ in range(n)])

    def _device_put_batch(self, x, y, stacked: bool = False):
        """Host batch → device arrays.  The Distri override shards over
        the mesh; the prefetch transfer thread calls this off the main
        thread to overlap H2D with compute."""
        del stacked
        return jnp.asarray(x), jnp.asarray(y)

    def _global_records_factor(self) -> int:
        """Host-batch → global-record multiplier for the producer's epoch
        arithmetic (multi-host data sharding overrides this)."""
        return 1

    def _sync_cadence(self) -> int:
        """Iterations between host materializations of loss/finite — the
        taps cadence (``BIGDL_OBS_TAPS_CADENCE`` / ``set_taps``), or 1
        under the ``BIGDL_SYNC_EVERY_STEP`` escape hatch."""
        if prefetch_mod.sync_every_step():
            return 1
        return obs_taps.cadence(self._taps_cadence)

    def _make_train_pipeline(self, n_disp: int, epoch_size: int):
        """The background input pipeline for this run, or None (prefetch
        disabled, or a mode that needs per-iteration host feedback).
        With a FaultInjector installed the runner stays host-side so
        ``_chaos_prestep`` keys every site by the CONSUMING step and H2D
        happens after poisoning — ``BIGDL_FAULTS`` drills are unchanged."""
        from bigdl_tpu.resilience import faults
        if not prefetch_mod.enabled():
            return None
        if getattr(self, "_straggler", None) is not None:
            # straggler drop accepts/rejects and re-times every iteration
            # on the host; producing ahead would decouple its clock
            return None
        to_device = None
        if faults.get() is None:
            stacked = n_disp > 1
            to_device = lambda xh, yh: self._device_put_batch(
                xh, yh, stacked=stacked)
        return prefetch_mod.PipelineRunner(
            self.dataset, train=True, chunk=n_disp, epoch_size=epoch_size,
            to_device=to_device,
            records_scale=self._global_records_factor())

    def _drain_pipeline_obs(self, pipeline, item, waited, neval0):
        """Book the background threads' telemetry onto the main-thread
        spans/events: the producer's draws with their per-stage self
        times and the transfer thread's ``h2d/prefetch``, and a
        prefetch_stall event when the queue failed to hide the fetch.
        The transfers are credited to the top-level ``h2d`` phase as
        well, which the per-host table shows."""
        drained = pipeline.take_spans()
        for path, (sec, n) in drained.items():
            self.spans.record(path, sec, count=n)
        if prefetch_mod.H2D in drained:
            self.spans.record("h2d", *drained[prefetch_mod.H2D])
        if waited > 0.01 and item.seq >= pipeline.depth:
            obs_events.emit("prefetch_stall", step=int(neval0),
                            seconds=round(waited, 6),
                            queue_depth=int(item.queue_depth))

    def _flush_window(self, state, monitor, reason: str):
        """Materialize the pending window: one blocking device→host sync
        (the ``host-wait`` span), then (the ``flush`` span) the per-step
        host work the serial loop did eagerly — loss logging, the
        non-finite ledger, step events and TensorBoard scalars.

        What is materialized is the window's to say
        (``_HostSyncWindow.flushable``): a ``cadence`` or ``epoch`` flush
        leaves the newest step in flight (counter ``flush/kept``) and the
        host's record one step behind the dispatch; every other reason
        drains.  A call with nothing to materialize books nothing.  The
        taps monitor is fed from the flushed entries only, so it never
        waits for the step still running.  An abort raised by the ledger
        is deferred until every pending step's events are out, the kept
        step's too (a second flush, booked as ``exception``)."""
        w = self._window
        if w is None:
            return
        abort = None
        for why in (reason, "exception"):
            if not w.flushable(why):
                break
            with self.spans.span("host-wait"):
                flushed = w.flush(why)
            with self.spans.span("flush"):
                abort = self._book_flushed(state, monitor, why, flushed,
                                           abort)
            if abort is None:
                break
        if abort is not None:
            raise abort

    def _book_flushed(self, state, monitor, reason, flushed, abort):
        """The host's record of the steps a flush materialized, oldest
        first.  Returns the ledger's abort (``abort`` where one is
        already on its way: the ledger is not asked again)."""
        entries, losses, finites, wall = flushed
        self._flushes[reason] += 1
        if self._window.flush_kept[-1] is not None:
            self._flushes_kept[reason] += 1
            self.spans.record("flush/kept", 0.0)
        records = sum(e.records for e in entries)
        rate = records / max(wall, 1e-9)
        self._note_window_utilization(entries, wall)
        epoch_size = self.dataset.size()
        for e, lv, fv in zip(entries, losses, finites):
            loss_f = float(lv.reshape(-1)[-1])
            state["loss"] = loss_f
            logger.info(
                "Epoch %d %d/%d loss %.6f lr %.5g throughput %.1f "
                "records/s (fetch %.4fs dispatch %.4fs, synced %s)",
                e.epoch, e.count, epoch_size, loss_f, e.lr, rate,
                e.fetch_t, e.train_t, reason)
            if abort is None:
                try:
                    self._note_finite(fv, state)
                except NonFiniteGradError as exc:
                    abort = exc  # emit the remaining step events first
            self._emit_step_event(e.neval0, loss_f, e.lr, rate,
                                  monitor.push(e.neval0, e.taps),
                                  **e.extra)
        return abort

    def _note_window_utilization(self, entries, wall):
        """Windowed ``train_mfu`` + ``train_step_wall_seconds`` gauges,
        published at flush boundaries ONLY (the host-sync cadence — the
        warm path never pays this): ledger flops for the compiled step
        x iterations in the window / (window wall x datasheet peak).
        The flops come from the compile-time capture of THIS loop's
        tracked-jit step (``obs/ledger.py``; a scanned chunk's scan
        body is counted once by XLA, so the chunk entry is already the
        per-iteration count), which is the same number ``bench.py``
        resolves — live MFU and bench MFU cannot silently diverge.
        Best-effort: absent ledger/flops just skips the gauge."""
        fn_key = getattr(self, "_step_fn_key", None)
        if fn_key is None or wall <= 0 or not entries:
            return
        try:
            from bigdl_tpu.obs import ledger as obs_ledger
            from bigdl_tpu.obs import metrics as obs_metrics
            iters = len(entries) * max(
                1, int(getattr(self, "iters_per_dispatch", 1)))
            label = ("distri" if type(self).__name__.startswith("Distri")
                     else "local")
            reg = obs_metrics.get()
            reg.gauge("train_step_wall_seconds",
                      "windowed mean train-step wall (fetch + dispatch "
                      "+ sync)", agg="max",
                      optimizer=label).set(wall / iters)
            flops = obs_ledger.get().flops_for(fn_key)
            peak = obs_ledger.device_peak_flops()   # None on the CPU
            if flops and peak:
                mfu = flops * iters / (wall * peak)
                reg.gauge("train_mfu",
                          "windowed model flops utilization of the "
                          "training loop (ledger flops x step rate / "
                          "datasheet peak)", agg="max",
                          optimizer=label).set(mfu)
        except Exception as e:  # pragma: no cover - obs mid-teardown
            logger.warning("train utilization gauge failed: %s", e)

    # -- main loop (ref LocalOptimizer.optimize :77) ----------------------
    def optimize(self):
        """Train until the end trigger fires and return the model, which
        then holds the trained parameters.

        A model whose parameters take more than ``_TAKEOVER_SHARE`` of the
        device's memory is not copied but **taken over**: the compiled step
        donates the model's own arrays, so during the run the model holds
        deleted arrays and must not be read (checkpoints and validation
        are made from the loop's state, not from the model).  The loop
        hands its latest parameters back when it ends, by an exception too:
        the model then holds the last step's result, and its initial
        weights are gone.  Smaller models are copied first and keep their
        initial weights until the run ends cleanly."""
        state = self.state
        state.get_or_update("epoch", 1)
        state.get_or_update("neval", 1)
        # a resumed state blob may carry the previous run's preemption
        # mark; this run hasn't been preempted (yet)
        state["preempted"] = False

        # copy the model's arrays: the jit step donates its carried state,
        # and donating the module's own buffers would leave the user's model
        # holding deleted arrays mid-training.  A model that fills a large
        # share of the device cannot be held twice: the loop takes its
        # arrays over and hands the trained ones back when it ends, however
        # it ends (the ``finally`` below)
        taken_over = _fills_device(self.model.params())
        params = self.model.params() if taken_over else \
            jax.tree_util.tree_map(jnp.copy, self.model.params())
        net_state = jax.tree_util.tree_map(jnp.copy, self.model.state())
        opt_state = self._initial_opt_state(params)
        step_fn = self._build_step()
        # the ledger key the MFU gauge resolves flops through (the
        # tracked-jit wrapper captured cost at its compiling dispatch)
        self._step_fn_key = getattr(step_fn, "fn_key", None)
        monitor = self._start_obs_run()

        count = 0
        epoch_size = self.dataset.size()
        n_disp = self.iters_per_dispatch
        pipeline = self._make_train_pipeline(n_disp, epoch_size)
        self._train_pipeline = pipeline
        data_iter = None if pipeline is not None \
            else self.dataset.data(train=True)
        self._window = _HostSyncWindow(self._sync_cadence())
        wall_start = time.perf_counter()

        try:
            # the user's end trigger stays the loop's condition, outside
            # every span and outside the ``loop`` counter: a caller may
            # read the span totals from inside it (the benchmark opens its
            # window there), and a span open around that read would book
            # its whole wall after the read
            while not self.end_when(state):
                neval0 = int(state["neval"])
                epoch0 = int(state["epoch"])
                # the iteration's top: its spans carry the step from here
                fetch_start = self.spans.begin_step(neval0)
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
                fetch_time = time.perf_counter() - fetch_start

                train_start = time.perf_counter()
                with self.spans.span("dispatch"):
                    # the rate and the key (two small device programs and
                    # a host-to-device copy a step) apart from the call,
                    # which may block in the runtime
                    with self.spans.span("prepare"):
                        lr = self._current_lr()
                        key = RNG.next_key()
                        lr_dev = jnp.float32(lr)
                    in_flight = self._note_in_flight()
                    with self.spans.span("call"):
                        params, net_state, opt_state, loss, finite, taps = \
                            step_fn(params, net_state, opt_state, x, y,
                                    lr_dev, key, self._lr_scales_arg)
                train_time = time.perf_counter() - train_start

                with self.spans.span("bookkeep"):
                    b = (x.shape[0] * x.shape[1] if n_disp > 1
                         else x.shape[0])
                    count += b
                    state["neval"] = neval0 + n_disp
                    state["evalCounter"] = \
                        state.get("evalCounter", 0) + n_disp
                    extra = {"in_flight": in_flight}
                    if qdepth is not None:
                        extra["queue_depth"] = int(qdepth)
                    # loss/finite/taps stay ON DEVICE; the window
                    # materializes them at the next cadence/boundary flush
                    # (no per-step device→host sync — the tentpole of this
                    # layer)
                    self._window.push(_PendingStep(
                        neval0, epoch0, count, loss, finite, taps, lr, b,
                        fetch_time, train_time, extra))
                    rolled = count >= epoch_size
                    count, data_iter = self._advance_epochs(
                        state, count, epoch_size, n_disp, data_iter,
                        pipeline)
                if self._window.due() or rolled:
                    self._flush_window(state, monitor,
                                       "epoch" if rolled else "cadence")
                # trigger predicates are host-only (no device sync); a
                # firing one forces its own flush below so validation/
                # checkpoint always see materialized loss + finite ledger
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
                    self._maybe_validate(params, net_state, state,
                                         force=True)
                if ne_ck is not None:
                    self._maybe_checkpoint(params, net_state, opt_state,
                                           state, force=True,
                                           neval_label=ne_ck)
                if preempt:
                    self._checkpoint_and_stop(params, net_state, opt_state,
                                              state)
                self.spans.end_step()
                if preempt:
                    break
            # the closing flush belongs to the loop's wall, as its spans
            # belong to the loop's thread (count 0: no iteration)
            flush_start = time.perf_counter()
            self._flush_window(state, monitor, "run-end")
            self.spans.record("loop", time.perf_counter() - flush_start,
                              count=0)
        finally:
            try:
                # best-effort: an exception between cadence boundaries
                # (fault, dispatch error, watchdog exit) must not lose
                # the already-dispatched steps' events + finite ledger —
                # the postmortem needs the steps NEAREST the crash.  A
                # no-op on clean exit (run-end already flushed); never
                # masks the propagating exception.
                self._flush_window(state, monitor, "exception")
            except Exception as e:
                logger.warning("pending-step flush during unwind "
                               "failed: %s", e)
            if pipeline is not None:
                pipeline.close()
            self._train_pipeline = None
            # leaving optimize() with snapshots still in flight would
            # let the process exit before they are durable
            self._flush_ckpt_writer("run end")
            if taken_over:
                if any(leaf.is_deleted()
                       for leaf in jax.tree_util.tree_leaves(params)):
                    logger.error("the step that failed had consumed the "
                                 "model's parameters: the model is left "
                                 "without them")
                self.model.load_params(params)

        self.model.load_params(params)
        self.model.load_state(net_state)
        self._end_obs_run(state, wall_start)
        logger.info("Training finished in %.1fs", time.perf_counter() - wall_start)
        return self.model

    # -- resilience hooks (docs/resilience.md) ----------------------------
    def _chaos_prestep(self, x_host, neval: int):
        """FaultInjector sites threaded through the train loop: NaN/Inf
        batch poisoning (drives the non-finite guard end-to-end through
        the real backward), slow-worker delay, induced process death.
        Returns the (possibly poisoned) host batch; a no-op None-check
        when chaos is off."""
        from bigdl_tpu.resilience import faults
        inj = faults.get()
        if inj is None:
            return x_host
        spec = inj.fires("slow_worker", step=neval)
        if spec is not None:
            time.sleep(spec.delay)
        if inj.fires("proc_kill", step=neval) is not None:
            logger.error("FaultInjector: induced process death at "
                         "iteration %d", neval)
            os._exit(1)
        poison = None
        if inj.fires("nan_grad", step=neval) is not None:
            poison = np.nan
        elif inj.fires("inf_grad", step=neval) is not None:
            poison = np.inf
        if poison is not None:
            x_host = np.array(x_host, dtype=np.float32, copy=True)
            x_host.reshape(-1)[0] = poison
        return x_host

    def _note_finite(self, finite, state):
        """Host-side accounting for the jit-folded finite flag(s): count
        skipped steps, track the consecutive streak, abort past the
        threshold.  ``finite`` is a scalar (or (n,) per-chunk array —
        the streak then continues across dispatch boundaries)."""
        flags = np.atleast_1d(np.asarray(finite)).astype(bool)
        n_bad = int((~flags).sum())
        if n_bad == 0:
            self._nonfinite_streak = 0
            return
        self._nonfinite_skips += n_bad
        # longest consecutive bad run, seeded with the streak carried in
        # from earlier dispatches — a >=threshold run INSIDE one chunk
        # must abort even if the chunk's last step recovered
        streak = self._nonfinite_streak
        worst = streak
        for f in flags:
            streak = 0 if f else streak + 1
            worst = max(worst, streak)
        self._nonfinite_streak = streak
        state["nonFiniteSkips"] = self._nonfinite_skips
        warn_every(
            logger, "nonfinite", 5.0,
            "non-finite gradients at iteration %d: update skipped, "
            "params/optimizer state kept (%d skipped total, %d "
            "consecutive, abort threshold %s)",
            int(state["neval"]), self._nonfinite_skips,
            worst, self.nonfinite_abort or "off")
        if self.nonfinite_abort and worst >= self.nonfinite_abort:
            # postmortem before the raise: the abort event + crash bundle
            # are what explains this death from the run directory alone
            from bigdl_tpu.obs import diagnostics
            obs_events.emit("abort", step=int(state["neval"]),
                            reason="nonfinite",
                            skips=int(self._nonfinite_skips),
                            streak=int(worst))
            diagnostics.dump_crash_bundle(
                "nonfinite-abort",
                extra={"neval": int(state["neval"]), "streak": int(worst),
                       "skips": int(self._nonfinite_skips),
                       "threshold": int(self.nonfinite_abort)})
            raise NonFiniteGradError(
                f"{worst} consecutive non-finite-gradient "
                f"steps (threshold {self.nonfinite_abort}, iteration "
                f"{int(state['neval'])}): loss has diverged — lower the "
                "learning rate or resume from an earlier checkpoint")

    def _preemption_pending(self) -> bool:
        """SIGTERM arrived (``Engine.install_preemption_handler``)?  The
        distributed loop overrides this with an any-process merge so every
        host agrees to stop at the same iteration."""
        return Engine.preempted()

    def _checkpoint_and_stop(self, params, net_state, opt_state, state):
        """Preemption epilogue: force one final checkpoint (when a
        checkpoint path is configured) and mark the state so callers can
        tell a preempted run from a completed one — flag first, so it
        rides the snapshot payload."""
        state["preempted"] = True
        obs_events.emit("preempt", step=int(state["neval"]),
                        signal_at=Engine.preempted_at())
        if self.checkpoint_path:
            self._maybe_checkpoint(params, net_state, opt_state, state,
                                   force=True)
            # the eviction deadline is real: the final snapshot must
            # be on disk before the exit, async mode or not
            self._flush_ckpt_writer("preemption checkpoint-and-stop")
        # the exit is clean, but the bundle records WHERE the notice
        # landed (docs/observability.md: preemption postmortems)
        from bigdl_tpu.obs import diagnostics
        diagnostics.dump_crash_bundle(
            "preemption", extra={"neval": int(state["neval"]),
                                 "signal_at": Engine.preempted_at()})
        # the notice has been honored; a LATER optimize() in this process
        # (restart after resume) must not stop on the stale flag — a new
        # SIGTERM sets it again
        Engine.clear_preemption()
        logger.warning(
            "preemption: checkpointed at iteration %d, leaving the "
            "training loop (resume with load_latest_checkpoint)",
            int(state["neval"]))

    def _advance_epochs(self, state, count, epoch_size, n_disp, data_iter,
                        pipeline=None):
        """Epoch rollover shared by both optimizers' loops.  Single-step
        keeps the historical semantics (leftover count resets — it came
        from the discarded iterator); a chunk can span several epochs of
        a small dataset, so it rolls the epoch counter through.  With a
        prefetch pipeline the PRODUCER already performed the shuffle and
        iterator rebuild at the same point of the draw stream
        (``PipelineRunner._advance_epoch``); only the counters move here."""
        if n_disp <= 1:
            if count >= epoch_size:
                state["epoch"] = state["epoch"] + 1
                count = 0
                if pipeline is None:
                    self.dataset.shuffle()
                    data_iter = self.dataset.data(train=True)
                self.spans.emit_phase_events(obs_events.get(),
                                             int(state["neval"]))
            return count, data_iter
        rolled = count >= epoch_size
        while count >= epoch_size:
            state["epoch"] = state["epoch"] + 1
            count -= epoch_size
            if pipeline is None:
                self.dataset.shuffle()
                data_iter = self.dataset.data(train=True)
        if rolled:
            self.spans.emit_phase_events(obs_events.get(),
                                         int(state["neval"]))
        return count, data_iter

    @staticmethod
    def _fired_within(trig, state, n):
        """The first neval in this dispatch's (neval-n, neval] interval
        at which ``trig`` would have fired, or None — periodic triggers
        (several_iteration(k)) must not be skipped because neval jumps by
        n per dispatch, and the probe keeps trigger evaluation host-only
        so a non-firing iteration costs no device sync.  Probes a shallow
        state copy per intermediate iteration (triggers are cheap
        predicates); the caller then invokes the action with force=True
        (stateful triggers like every_epoch must be probed exactly
        once)."""
        if trig is None:
            return None
        neval = state["neval"]
        for ne in range(neval - n + 1, neval + 1):
            probe = T()
            probe.update(state)
            probe["neval"] = ne
            if trig(probe):
                return ne
        return None

    # -- observability plumbing (docs/observability.md) -------------------
    def _obs_flags(self) -> dict:
        """The run-configuration snapshot stamped into the run_start
        event — enough to tell two runs apart in a pile of JSONL."""
        flags = {"optimizer": type(self).__name__,
                 "taps": obs_taps.enabled(self._taps_enabled),
                 "taps_cadence": obs_taps.cadence(self._taps_cadence),
                 "iters_per_dispatch": self.iters_per_dispatch,
                 "nonfinite_abort": self.nonfinite_abort,
                 "prefetch": prefetch_mod.enabled(),
                 "prefetch_depth": prefetch_mod.depth(),
                 "sync_cadence": self._sync_cadence(),
                 "optim_method": type(self.optim_method).__name__}
        mesh = getattr(self, "mesh", None)
        if mesh is not None:
            flags["mesh"] = {k: int(v) for k, v in dict(mesh.shape).items()}
        return flags

    def _note_in_flight(self) -> int:
        """How far ahead the host is, read where the work happens: the
        steps the device still holds just before the next one is handed
        to it, booked on the span tree as two counters in the manner of
        ``loop``.  ``dispatch/in-flight`` sums the counts, one booking a
        dispatch; ``dispatch/device-empty`` books each dispatch that
        found none, from which until the launch lands the device has no
        work, and the run keeps by what such a dispatch followed: the
        flush it is the first dispatch after, by its reason (one that
        drained the device, or one whose kept step ended before the host
        came back), the call's ``start``, or ``none`` (steps were
        dispatched since the last flush and the device ran out of
        them)."""
        w = self._window
        n = w.in_flight()
        self.spans.record("dispatch/in-flight", n)
        self._in_flight_hist[n] += 1
        if n == 0:
            self.spans.record("dispatch/device-empty", 0.0)
            if w.dispatched_since_flush():
                reason = "none"
            else:
                reason = w.flush_reasons[-1] if w.flush_reasons else "start"
            self._empty_after[reason] += 1
        return n

    def _start_obs_run(self):
        """Fresh taps monitor + run_start event at each optimize(); the
        step timeline of this call starts at the ring's present end."""
        self._taps_monitor = obs_taps.TapsMonitor(self._taps_cadence,
                                                  self._taps_enabled)
        self._timeline_mark = self.spans.appended
        self._in_flight_hist = collections.Counter()
        self._empty_after = collections.Counter()
        self._flushes = collections.Counter()
        self._flushes_kept = collections.Counter()
        try:
            # BIGDL_OBS_HBM_SAMPLE=<s>: cadence HBM sampler for the
            # run (process-wide, started once; obs/ledger.py)
            from bigdl_tpu.obs import ledger as obs_ledger
            obs_ledger.maybe_start_sampler_from_env()
        except Exception:   # pragma: no cover - obs layer unavailable
            pass
        obs_events.emit("run_start", flags=self._obs_flags())
        return self._taps_monitor

    def _end_obs_run(self, state, wall_start):
        """Flush the tap tail (short runs still log one sample), emit
        the cumulative phase breakdown, this call's step timeline (what
        an untraced slow window leaves behind) and the run_end event."""
        ev = obs_events.get()
        tail = self._taps_monitor.flush() if self._taps_monitor else None
        timeline = self.spans.step_timeline(self._timeline_mark)
        if timeline is not None:
            timeline.update(
                steps=sum(self._in_flight_hist.values()),
                in_flight={str(n): c for n, c
                           in sorted(self._in_flight_hist.items())},
                device_empty=dict(self._empty_after),
                flushes={reason: {"count": n,
                                  "kept": self._flushes_kept[reason]}
                         for reason, n in self._flushes.items()})
            logger.info(render_timeline(timeline))
            if ev is not None:
                ev.emit("step_timeline", **timeline)
        if ev is not None:
            self.spans.emit_phase_events(ev, int(state["neval"]))
            fields = {"steps": int(state["neval"]) - 1,
                      "wall": time.perf_counter() - wall_start}
            if tail:
                fields["taps"] = tail
            ev.emit("run_end", **fields)

    def _emit_step_event(self, neval, loss, lr, throughput, tap_vals,
                         **extra):
        """One structured step event + TensorBoard scalars.  ``tap_vals``
        is the monitor's cadence-gated dict (None off-boundary)."""
        ev = obs_events.get()
        if ev is None and self._train_summary is None:
            return
        fields = dict(step=int(neval), loss=float(loss), lr=float(lr),
                      throughput=float(throughput))
        if tap_vals:
            fields["taps"] = tap_vals
        if self._nonfinite_skips:
            fields["skips"] = int(self._nonfinite_skips)
        fields.update(extra)
        if ev is not None:
            ev.emit("step", **fields)
        ts = self._train_summary
        if ts is not None:
            ts.add_scalar("Loss", loss, neval)
            ts.add_scalar("LearningRate", lr, neval)
            ts.add_scalar("Throughput", throughput, neval)
            if tap_vals:
                for k, v in tap_vals.items():
                    ts.add_scalar("Taps/" + k, v, neval)

    # -- validation (ref LocalOptimizer.scala:196-242) --------------------
    def _maybe_validate(self, params, net_state, state, force=False):
        if not force and (self.validation_trigger is None
                          or not self.validation_trigger(state)):
            return
        pipeline = self._train_pipeline
        with self.spans.span("validate"):
            if pipeline is not None:
                # hold the producer before its next draw: validation may
                # iterate the same backing store an epoch shuffle mutates
                # (the wait for a draw in flight is validation's cost)
                pipeline.pause()
            try:
                results = validate(self.model, params, net_state,
                                   self.validation_dataset,
                                   self.validation_methods)
            finally:
                if pipeline is not None:
                    pipeline.resume()
        for method, result in results:
            logger.info("%s is %s", method, result)
            val = result.result()[0]
            state[str(method)] = val
            obs_events.emit("validation", step=int(state["neval"]),
                            method=str(method), value=float(val))
            if self._val_summary is not None:
                self._val_summary.add_scalar(str(method), val,
                                             int(state["neval"]))

    def _maybe_checkpoint(self, params, net_state, opt_state, state,
                          force=False, neval_label=None):
        if not force and (self.checkpoint_trigger is None
                          or not self.checkpoint_trigger(state)):
            return
        neval = state["neval"] if neval_label is None else neval_label
        from bigdl_tpu.resilience import checkpoint as ckpt_mod
        # the classic (synchronous, whole-tree) path cannot express
        # optimizer state sharded ACROSS processes — those leaves are not
        # addressable from one writer — so zero1 multi-host snapshots ride
        # the sharded writer even with the async flag off
        sharded = jax.process_count() > 1 and any(
            ckpt_mod.is_cross_process_sharded(l)
            for l in jax.tree_util.tree_leaves(opt_state))
        if ckpt_mod.async_enabled() or sharded:
            with self.spans.span("checkpoint"):
                self._emit_checkpoint(params, net_state, opt_state, state,
                                      neval,
                                      asynchronous=ckpt_mod.async_enabled())
            return
        if jax.process_count() > 1 and jax.process_index() != 0:
            # replicated state, shared checkpoint dir: exactly one writer
            # (the reference's driver-side getModel + File.save)
            return
        with self.spans.span("checkpoint"):
            # load host copies: loading the live pytree would leave the
            # module referencing buffers the next (donating) step deletes
            self.model.load_params(jax.device_get(params))
            self.model.load_state(jax.device_get(net_state))
            File.save_module(self.model,
                             f"{self.checkpoint_path}/model.{neval}")
            # "neval": the file label (= the nominal firing iteration under
            # the device-side loop, which may be < state['neval']); kept in
            # the payload so resume tooling can detect the chunked case.
            # "rng": host-stream snapshot so a resume can replay the
            # uninterrupted run's shuffle/augmentation draws
            # (load_latest_checkpoint(restore_rng=True)).  With the
            # prefetch pipeline the stream has advanced past the batches
            # merely PREFETCHED; the runner's snapshot is pinned to the
            # last CONSUMED batch so the resumed trajectory matches.
            pipeline = self._train_pipeline
            rng_snap = (pipeline.rng_snapshot() if pipeline is not None
                        else RNG.snapshot())
            File.save({"state": state, "opt_state": opt_state,
                       "neval": neval, "rng": rng_snap},
                      f"{self.checkpoint_path}/state.{neval}")
            keep = ckpt_mod.keep_count()
            if keep:
                from bigdl_tpu.optim.optimizer import prune_checkpoints
                prune_checkpoints(self.checkpoint_path, keep,
                                  just_written=neval)
        obs_events.emit("checkpoint", step=int(neval),
                        path=f"{self.checkpoint_path}/model.{neval}")

    def _flush_ckpt_writer(self, context: str, timeout: float = 120.0):
        """Drain the async checkpoint writer, LOUDLY: a flush that times
        out at a preemption/run-end epilogue means the newest snapshot
        may be missing at resume — that must be in the log, not silently
        indistinguishable from success."""
        if self._ckpt_writer is None:
            return True
        ok = self._ckpt_writer.flush(timeout=timeout)
        if not ok:
            logger.error(
                "async checkpoint writer did not drain within %.0fs at "
                "%s — the newest snapshot may be missing or partial on "
                "resume (the CRC scan will fall back past it)",
                timeout, context)
        return ok

    def _ckpt_copy(self, params, net_state, opt_state):
        """Fresh (never-donated) device copies of the carried state in one
        dispatch, shardings preserved — what makes handing the trees to a
        background writer safe against the next step's donation."""
        if self._ckpt_copy_fn is None:
            copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)
            self._ckpt_copy_fn = jax.jit(
                lambda p, s, o: (copy(p), copy(s), copy(o)))
        return self._ckpt_copy_fn(params, net_state, opt_state)

    def _emit_checkpoint(self, params, net_state, opt_state, state, neval,
                         asynchronous: bool):
        """The sharded/async snapshot builder (docs/resilience.md "Async
        checkpoints").  Device trees are copied on this thread (cheap,
        on-device); the device→host materialization and every byte of
        pickling/IO happen on the writer thread when ``asynchronous`` —
        the loop's checkpoint-step cost collapses to one copy dispatch +
        an enqueue.  Optimizer-state leaves sharded across processes
        become one ``state.N.shard<r>of<n>`` file (+ CRC sidecar) per
        process; ``load_latest_checkpoint`` reassembles the full tree,
        making the snapshot world-size-agnostic."""
        from bigdl_tpu.resilience import checkpoint as ckpt_mod
        from bigdl_tpu.utils.file import _pickle_architecture

        params_c, net_c, opt_c = self._ckpt_copy(params, net_state,
                                                 opt_state)
        marked, slices = ckpt_mod.split_sharded_state(opt_c)
        nproc = jax.process_count()
        rank = jax.process_index()
        sharded = bool(slices) and nproc > 1
        pipeline = self._train_pipeline
        rng_snap = (pipeline.rng_snapshot() if pipeline is not None
                    else RNG.snapshot())
        files = []
        if sharded:
            files.append((ckpt_mod.shard_file(self.checkpoint_path, neval,
                                              rank, nproc),
                          {"rank": int(rank), "world": int(nproc),
                           "slices": slices}))
        meta = {}
        if rank == 0:
            state_copy = T()
            state_copy.update(state)
            blob = {"state": state_copy,
                    "opt_state": marked if sharded else opt_c,
                    "neval": neval, "rng": rng_snap}
            if sharded:
                blob["opt_shards"] = int(nproc)
            files.append((f"{self.checkpoint_path}/model.{neval}",
                          {"format": "bigdl_tpu.module.v2",
                           "cls": type(self.model).__name__,
                           "architecture": _pickle_architecture(self.model),
                           "params": params_c, "state": net_c}))
            files.append((f"{self.checkpoint_path}/state.{neval}", blob))
            meta = {"event_path": f"{self.checkpoint_path}/model.{neval}",
                    "step": int(neval),
                    "shards": int(nproc) if sharded else 0,
                    "keep": ckpt_mod.keep_count() or None,
                    "ckpt_dir": self.checkpoint_path}
        if not files:
            return
        if asynchronous:
            if self._ckpt_writer is None:
                self._ckpt_writer = ckpt_mod.AsyncCheckpointWriter()
            self._ckpt_writer.submit(files, meta)
            return
        # sharded-but-sync (zero1 multi-host with BIGDL_CKPT_ASYNC=0):
        # write inline, same files, same sidecars
        for path, blob in files:
            File.save(blob, path)
        if meta:
            obs_events.emit("checkpoint", step=int(neval),
                            path=meta["event_path"],
                            shards=meta["shards"])
            if meta.get("keep"):
                from bigdl_tpu.optim.optimizer import prune_checkpoints
                prune_checkpoints(self.checkpoint_path, meta["keep"],
                                  just_written=meta.get("step"))


# a model over this share of the device's memory is taken over by
# ``optimize()`` instead of copied: with its gradient and one optimizer
# buffer of the same size a second copy would pass half the device
_TAKEOVER_SHARE = 0.125


def _fills_device(tree) -> bool:
    """Whether the arrays of ``tree`` take more than ``_TAKEOVER_SHARE`` of
    the first local device's memory (False where the backend does not say
    how much it has, as the CPU's)."""
    stats = jax.local_devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    if not limit:
        return False
    held = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree))
    return held > _TAKEOVER_SHARE * limit


def _model_fingerprint(model):
    """Cheap structure+hyper fingerprint: module tree paths, class names,
    and scalar attributes.  Guards the cached eval jit against in-place
    architecture edits between validations (swap a layer, change a bound)."""
    parts = []

    hyper_types = (int, float, bool, str, bytes, type(None), tuple, list,
                   np.integer, np.floating, np.bool_)
    # runtime-mutable attrs that don't change the compiled computation —
    # including them would recompile on every eager call / mode flip
    skip = {"forward_time", "backward_time", "training_mode", "output",
            "grad_input", "_last_key", "name"}

    def walk(mod, path):
        scalars = tuple(sorted(
            (k, repr(v)) for k, v in mod.__dict__.items()
            if isinstance(v, hyper_types) and k not in skip and
            not k.startswith("_cached_")))
        parts.append((path, type(mod).__name__, scalars))
        for name, child in mod._modules.items():
            walk(child, f"{path}/{name}")

    walk(model, "")
    return tuple(parts)


def _eval_fn(model):
    """One eval forward per model instance, cached on the model (a fresh
    closure per validate() call would recompile at every validation
    trigger; the model->fn->model cycle is ordinary gc fodder) and
    routed through the shared executable cache (``serve/xcache.py``):
    the returned callable resolves an AOT executable per batch shape
    keyed by the model FINGERPRINT, so a process that validates AND
    serves the same (model, shape) pair compiles it exactly once."""
    fp = _model_fingerprint(model)
    cached = getattr(model, "_cached_eval_fn", None)
    if cached is not None and cached[0] == fp:
        return cached[1]
    from bigdl_tpu.nn.module import Context
    from bigdl_tpu.serve import xcache

    @jax.jit
    def fwd(p, s, x):
        out, _ = model.apply(p, x, s,
                             Context(training=False, key=jax.random.PRNGKey(0)))
        return out

    wrapped = xcache.ShapedCallable(fwd, fn_key=("eval", fp))
    model._cached_eval_fn = (fp, wrapped)
    return wrapped


def validate(model, params, net_state, dataset, methods, batch_to_device=jnp.asarray):
    """Shared evaluation loop (ref Validator.scala:24 / LocalValidator.scala:30).

    Returns [(method, merged_result)].  Logs eval throughput, the
    reference's "validate model throughput is %.2f records / second"
    line (LocalOptimizer.scala:231-233).

    Validation batches ride the background prefetcher too (bounded, one
    pass): batch k+1 decodes while batch k's forward + host-side compare
    run.  ``BIGDL_PREFETCH=0`` restores the serial iterator, and a chain
    with RNG-bearing stages (unconventional for eval) stays serial so
    its draws come from the calling thread's stream, not a fresh derived
    stream per validation pass.

    The last PARTIAL batch is zero-padded back to the full batch's row
    count through the serve bucket helper (``serve/bucketing.pad_rows``)
    and its outputs trimmed, so an odd tail reuses the executable the
    first batch compiled instead of paying a second XLA compile per
    distinct tail shape (docs/serving.md).
    """
    from bigdl_tpu.serve import bucketing
    fwd = _eval_fn(model)
    totals = [None] * len(methods)
    count = timed_count = 0
    t0 = None
    full_rows = None
    batches = dataset.data(train=False)
    if prefetch_mod.enabled() and not prefetch_mod.has_stochastic_stage(
            dataset):
        batches = prefetch_mod.background(batches, prefetch_mod.depth())
    for batch in batches:
        data = np.asarray(batch.data)   # converted ONCE: shape probe,
        rows = int(data.shape[0])       # pad and device transfer all
        if full_rows is None:           # reuse the same array
            full_rows = rows
        if rows < full_rows:
            data, _ = bucketing.pad_rows(data, full_rows)
        out = fwd(params, net_state, batch_to_device(data))
        if rows < full_rows:
            out = bucketing.trim(out, rows)
        b = int(np.asarray(batch.labels).shape[0])
        count += b
        for i, m in enumerate(methods):
            r = m(out, batch.labels)  # host-side compare = hard sync
            totals[i] = r if totals[i] is None else totals[i] + r
        if t0 is None:
            # start the throughput clock AFTER the first batch: its jit
            # compile (tens of seconds cold on TPU) would otherwise
            # deflate the logged number ~1000x
            t0 = time.perf_counter()
        else:
            timed_count += b
    dt = time.perf_counter() - (t0 or time.perf_counter())
    if timed_count:
        logger.info("validate model throughput is %.2f records / second "
                    "(%d records in %.3fs, excluding the first batch)",
                    timed_count / max(dt, 1e-9), timed_count, dt)
    else:
        logger.info("validate model throughput unavailable: single-batch "
                    "dataset (first batch carries the compile); "
                    "%d records validated", count)
    return list(zip(methods, totals))


def distri_validate(model, params, net_state, dataset, methods):
    """Distributed evaluation (ref DistriValidator.scala:32): each process
    evaluates its dataset shard, results merge across hosts via the
    ValidationResult ``+`` algebra (the reference reduces driver-side)."""
    local = validate(model, params, net_state, dataset, methods)
    if jax.process_count() == 1:
        return local
    from jax.experimental import multihost_utils
    merged = []
    for method, result in local:
        if hasattr(result, "correct"):
            vec = np.asarray([result.correct, result.count], np.float32)
        else:
            vec = np.asarray([result.loss, result.count], np.float32)
        total = np.asarray(
            multihost_utils.process_allgather(jnp.asarray(vec))).sum(axis=0)
        merged.append((method, type(result)(total[0], int(total[1]))))
    return merged

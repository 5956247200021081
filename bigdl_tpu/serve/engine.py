"""Throughput-oriented inference engine: request queue + dynamic
batching + shape-bucketed AOT executable cache (docs/serving.md).

The reference shipped batch scoring as a first-class subsystem
(DLClassifier / ``Module.predict`` over an RDD); this is the TPU-native
version, built on the same pipeline idioms the training path already
proved out (``dataset/prefetch.py`` double-buffering, the obs event
stream, ``BIGDL_FAULTS`` chaos sites):

- **Submit**: :meth:`ServeEngine.submit` / :meth:`submit_many` enqueue
  single rows and return ``concurrent.futures.Future`` objects — the
  async API a request handler calls.
- **Assemble**: a batcher thread closes a micro-batch on
  size-or-deadline (``BIGDL_SERVE_MAX_BATCH`` rows, or
  ``BIGDL_SERVE_MAX_WAIT_MS`` after the first queued row), rejects
  poisoned rows (non-finite values fail ONLY their own future, with an
  obs ``serve`` error event — the batch proceeds without them) and
  zero-pads to the power-of-two bucket (`serve/bucketing.py`).
- **Transfer**: a dedicated H2D thread double-buffers padded batches to
  the device (the ``prefetch.py`` transfer-thread pattern; bounded
  queues give backpressure).  This is a ``BIGDL_FAULTS`` site
  (``serve_h2d``) so the chaos matrix covers serving.
- **Execute**: a compute thread runs the bucket's ahead-of-time
  compiled executable (``jit(fwd).lower(...).compile()`` per bucket at
  warmup, riding the persistent XLA compilation cache) and resolves the
  futures with trimmed per-row outputs.  After warmup a mixed-size
  stream triggers ZERO new compiles — the single-compile invariant
  ``tests/test_serve.py`` audits.

Weights are captured and pinned to device ONCE at engine start
(``jax.device_put``); :meth:`refresh` re-captures them from the model
(same shapes/dtypes, so the executable cache survives).  An optional
``DTypePolicy`` (e.g. ``tensor.BF16_COMPUTE``) scopes bf16 MXU compute
to the serving forward without touching the process default.

Telemetry: every counter, gauge and the fixed-bucket latency histogram
live in the process-wide mergeable registry (``obs/metrics.py``,
labelled ``engine=<name>``) so per-replica numbers roll up exactly
across a fleet; :meth:`stats` is a thin view over the registry
(p50/p95/p99, queue depth, bucket hits, compile count), and ``serve``
events (start/stop/error) ride the obs stream (docs/observability.md).
Sampled requests carry a trace context (``obs/trace.py``) that the
H2D and compute stages stamp in passing.
"""
from __future__ import annotations

import itertools
import logging
import os
import queue
import threading
import time
from concurrent.futures import Future

import numpy as np

from bigdl_tpu.serve import bucketing
from bigdl_tpu.serve.streaming import SafeFuture

logger = logging.getLogger("bigdl_tpu.serve")

ENV_MAX_BATCH = "BIGDL_SERVE_MAX_BATCH"
ENV_MAX_WAIT_MS = "BIGDL_SERVE_MAX_WAIT_MS"

DEFAULT_MAX_BATCH = 64
DEFAULT_MAX_WAIT_MS = 2.0
#: bounded hand-off depth between assembler -> H2D -> compute (the
#: prefetch double-buffer: one batch in flight per stage, one queued)
_STAGE_DEPTH = 2
#: default engine names: unique per process so registry series never
#: collide between replicas that share one process
_ENGINE_SEQ = itertools.count()
#: count of pinned-policy warmups currently holding the process dtype
#: policy swapped (warmup() below): while > 0 the ambient policy is a
#: TRANSIENT trace-time state, not a drift — _check_policy_drift
#: suspends so a serving ambient-policy engine does not false-positive
#: against a sibling engine's compilation window
_PIN_LOCK = threading.Lock()
_PIN_DEPTH = 0


def max_batch_default() -> int:
    try:
        return max(1, int(os.environ.get(ENV_MAX_BATCH, DEFAULT_MAX_BATCH)))
    except ValueError:
        return DEFAULT_MAX_BATCH


def max_wait_ms_default() -> float:
    try:
        return max(0.0, float(os.environ.get(ENV_MAX_WAIT_MS,
                                             DEFAULT_MAX_WAIT_MS)))
    except ValueError:
        return DEFAULT_MAX_WAIT_MS


class _Request:
    __slots__ = ("x", "future", "t_submit", "trace")

    def __init__(self, x, trace=None):
        self.x = x
        # SafeFuture: a user add_done_callback that raises fails only
        # its own registration (obs error event) — it can never kill
        # the compute thread resolving the batch (serve/streaming.py)
        self.future = SafeFuture()
        self.t_submit = time.perf_counter()
        self.trace = trace       # obs.trace.Trace for sampled requests


class _End:
    pass


_END = _End()


class PoisonedRequestError(ValueError):
    """A submitted row contained non-finite values; only its own future
    fails — the rest of the micro-batch is served normally."""


class DTypePolicyDriftError(RuntimeError):
    """The process-global dtype policy changed between this engine's
    warmup and a submit.  The warmed executables were traced under the
    OLD policy (the policy is baked in at trace time — engine.__init__'s
    ``policy`` caveat), so serving on would silently answer with
    stale-precision outputs; failing the submit loudly makes the caller
    either restore the policy, pin one via ``ServeEngine(policy=...)``,
    or build a fresh engine under the new policy."""


class SheddedError(RuntimeError):
    """The request was rejected by admission control (engine queue bound
    or router overload policy) instead of being served past its
    deadline.  Carries no partial result; the caller may retry against
    a less-loaded endpoint."""


class ServeEngine:
    """Dynamic-batching inference engine over one model.

    ``ServeEngine(model)`` captures ``model.params()``/``state()`` once
    and pins them to device; call :meth:`refresh` after training updates
    the module tree.  ``input_shape``/``input_dtype`` (per-ROW shape,
    no batch dim) enable eager warmup at construction; otherwise every
    bucket compiles on the first batch (still one warmup moment — never
    per mixed size).

    ``policy`` caveat: the dtype policy is process-global at trace time
    (``tensor.set_policy`` is swapped around the warmup lowering and
    restored after), so when serving with a non-default policy NEXT TO
    concurrent training/tracing on other threads, pass ``input_shape``
    so the whole warmup happens synchronously at construction on the
    calling thread — lazy warmup would otherwise briefly apply the
    serving policy to traces racing it.  The converse drift — the
    PROCESS policy changing after an ambient-policy engine warmed — is
    caught at submit: :class:`DTypePolicyDriftError` instead of
    silently serving stale-precision executables.

    ``quant`` (default from ``BIGDL_SERVE_QUANT``: off/int8/fp8) serves
    per-channel quantized weights (docs/serving.md "Quantized
    serving"): the capture quantizes ``model.params()`` through a
    :class:`~bigdl_tpu.quant.WeightQuantizer` (pass ``calibration`` — a
    ``quant.calibrate.Calibration`` — to arm the activation-aware clip
    search), the executables take ``(qweights, scales)`` as ARGUMENTS
    and dequantize on the fly, and every staged rollout re-quantizes
    with the same recipe, so hot weight swaps never recompile.  The
    quant recipe rides the executable-cache function key — quantized
    and full-precision replicas of one architecture never collide.
    """

    def __init__(self, model, max_batch: int | None = None,
                 max_wait_ms: float | None = None, policy=None,
                 input_shape=None, input_dtype=np.float32,
                 max_queue: int | None = None, name: str | None = None,
                 quant: str | None = None, calibration=None,
                 device=None):
        import jax

        self.model = model
        self.name = name or f"engine{next(_ENGINE_SEQ)}"
        #: the jax device this engine's weights, inputs and executables
        #: are pinned to (None = jax's default device)
        self.device = device
        self.max_batch = (max_batch_default() if max_batch is None
                          else max(1, int(max_batch)))
        self.max_wait_s = (max_wait_ms_default() if max_wait_ms is None
                           else max(0.0, float(max_wait_ms))) / 1e3
        #: admission bound: a submit seeing this many queued requests is
        #: shed (fails fast with SheddedError) instead of growing the
        #: backlog past any deadline.  None/0 = unbounded (the default;
        #: the router is the usual shedding layer — docs/serving.md).
        self.max_queue = int(max_queue) if max_queue else None
        self.buckets = bucketing.bucket_sizes(self.max_batch)
        self._policy = policy
        from bigdl_tpu import quant as quant_mod
        from bigdl_tpu.quant.weights import ON_MODES as _WEIGHT_MODES
        self.quant = (quant_mod.weight_mode_default() if quant is None
                      else quant_mod.normalize_mode(
                          quant, _WEIGHT_MODES, "quant"))
        #: maps fp params -> the {"q", "scale"} pack the executables
        #: take; None on the full-precision path.  May raise
        #: UnsupportedQuantError here (fp8 capability gate) — at
        #: construction, never from inside a trace.
        self._quantizer = None
        if self.quant != "off":
            self._quantizer = quant_mod.WeightQuantizer(
                model, self.quant, calibration=calibration)
        # (params, state) swap as ONE tuple so a refresh/commit racing
        # the compute thread can never pair new params with old state —
        # the half-swap audit tests/test_serve.py holds refresh() to
        self._weights = (
            jax.device_put(self._capture(model.params()), device),
            jax.device_put(model.state(), device))
        self.weights_version = 0
        self._staged = None      # (version, (params, state)) or None
        self._prev_weights = None  # one-deep history for revert_weights
        # HBM tenant truth (obs/ledger.py): the pinned weight pack's
        # bytes — under weight quantization this is the int8/fp8 pack
        # size, i.e. the density the quantized-serving docs claim
        from bigdl_tpu.obs import ledger as obs_ledger
        obs_ledger.note_tenant(
            "serve_weights", obs_ledger.tree_nbytes(self._weights),
            engine=self.name, quant=self.quant)

        # ONE compiled-forward path per model: the same xcache-backed
        # eval fn the validators use (optim.local_optimizer._eval_fn) —
        # warmup resolves each bucket through the SHARED executable
        # cache (serve/xcache.py), so a process that validates AND
        # serves a common (model, shape) pair compiles it exactly once.
        # The quantized path gets its own fn (dequant-in-forward) under
        # a fn_key extended with the quant recipe: same cache, disjoint
        # keys.
        if self._quantizer is not None:
            from bigdl_tpu.quant.weights import quantized_eval_fn
            self._fwd = quantized_eval_fn(model, self._quantizer)
        else:
            from bigdl_tpu.optim.local_optimizer import _eval_fn
            self._fwd = _eval_fn(model)
        self._executables: dict = {}   # bucket -> compiled executable
        self._row_shape = None
        self._row_dtype = None
        #: the dtype-policy the warmed executables were traced under
        #: (None until the first warmup, or always when ``policy`` pins
        #: one): submit() refuses to serve across a process-policy
        #: drift (DTypePolicyDriftError)
        self._warm_policy_obj = None
        self._warm_policy_key = None

        self._lock = threading.Lock()
        self._closed = False
        self._queue: "queue.Queue" = queue.Queue()
        self._h2d_q: "queue.Queue" = queue.Queue(maxsize=_STAGE_DEPTH)
        self._exec_q: "queue.Queue" = queue.Queue(maxsize=_STAGE_DEPTH)

        # telemetry: every instrument lives in the process-wide
        # mergeable registry (obs/metrics.py) under engine=<name>, so a
        # replica fleet's numbers roll up exactly; the attribute
        # properties below and stats() are VIEWS over it.  The
        # accepted/shed/completed/failed counters are MONOTONIC from
        # construction and never reset — the router rate-differences
        # consecutive stats() snapshots, so a reset would read as a
        # huge negative rate.  completed+failed+inflight == accepted at
        # every instant; shed requests are counted in none of the other
        # three (their futures fail without entering the pipeline).
        from bigdl_tpu.obs import metrics as obs_metrics
        reg = obs_metrics.get()
        lab = {"engine": self.name}
        self._m_req = {
            outcome: reg.counter(
                "serve_requests_total",
                "engine admission counters by outcome", outcome=outcome,
                **lab)
            for outcome in ("accepted", "shed", "completed", "failed")}
        self._m_batches = reg.counter(
            "serve_batches_total", "micro-batches executed", **lab)
        self._m_compiles = reg.counter(
            "serve_compiles_total", "bucket executables installed", **lab)
        self._m_latency = reg.histogram(
            "serve_latency_seconds",
            "submit-to-resolve request latency", **lab)
        self._m_qdepth = reg.gauge(
            "serve_queue_depth", "requests waiting for a batch", **lab)
        self._m_qmax = reg.gauge(
            "serve_queue_depth_max", "queue-depth high-water mark",
            agg="max", **lab)
        self._m_inflight = reg.gauge(
            "serve_inflight", "accepted, not yet resolved", **lab)
        self._m_version = reg.gauge(
            "serve_weights_version", "committed weight version",
            agg="max", **lab)
        self._m_bucket = {
            b: reg.counter("serve_bucket_hits_total",
                           "batches served per pow2 bucket",
                           bucket=str(b), **lab)
            for b in self.buckets}
        self._inflight = 0       # submitted, future not yet resolved
        self._max_queue_depth = 0

        if input_shape is not None:
            self.warmup(tuple(input_shape), input_dtype)

        self._assembler = threading.Thread(
            target=self._assemble_loop, daemon=True,
            name="bigdl-serve-batcher")
        self._transfer = threading.Thread(
            target=self._h2d_loop, daemon=True, name="bigdl-serve-h2d")
        self._compute = threading.Thread(
            target=self._compute_loop, daemon=True,
            name="bigdl-serve-compute")
        self._assembler.start()
        self._transfer.start()
        self._compute.start()
        self._emit("start", max_batch=self.max_batch,
                   max_wait_ms=self.max_wait_s * 1e3,
                   buckets=list(self.buckets), quant=self.quant)

    # -- registry-backed counter views (monotonic; see __init__) ------------
    @property
    def accepted(self) -> int:
        return int(self._m_req["accepted"].value)

    @property
    def shed(self) -> int:
        return int(self._m_req["shed"].value)

    @property
    def served(self) -> int:
        """Rows completed OK (alias: completed)."""
        return int(self._m_req["completed"].value)

    @property
    def errors(self) -> int:
        """Rows failed (alias: failed)."""
        return int(self._m_req["failed"].value)

    @property
    def batches(self) -> int:
        return int(self._m_batches.value)

    @property
    def compiles(self) -> int:
        return int(self._m_compiles.value)

    def _capture(self, params):
        """Params as the executables expect them: quantized to the
        ``{"q", "scale"}`` pack when this engine serves quantized, the
        fp tree otherwise.  Capture, refresh and every staged rollout
        funnel through here, so a hot swap onto a quantized replica
        re-quantizes with the SAME recipe."""
        if self._quantizer is None:
            return params
        return self._quantizer.quantize(params)

    # -- compilation --------------------------------------------------------
    def warmup(self, row_shape: tuple, row_dtype=np.float32):
        """Pre-lower-and-compile EVERY bucket for rows of ``row_shape``.

        Rides the persistent XLA compilation cache where the process
        enabled it (``utils.engine.enable_compile_cache``), so a
        restarted server re-warms from disk, not from the compiler.  Idempotent;
        returns the number of fresh compiles."""
        import jax

        row_shape = tuple(int(d) for d in row_shape)
        row_dtype = np.dtype(row_dtype)
        with self._lock:
            if self._row_shape is None:
                self._row_shape, self._row_dtype = row_shape, row_dtype
            elif (row_shape != self._row_shape
                  or row_dtype != self._row_dtype):
                raise ValueError(
                    f"engine is warmed for rows {self._row_shape} "
                    f"{self._row_dtype}, not {row_shape} {row_dtype}")
        fresh = 0
        global _PIN_DEPTH
        from bigdl_tpu import tensor as bt
        from bigdl_tpu.serve import xcache
        prev = bt.policy()
        if self._policy is not None:
            with _PIN_LOCK:
                _PIN_DEPTH += 1
            bt.set_policy(self._policy)
        try:
            # record the policy the traces below bake in; submit()
            # compares against it so a later process-policy flip fails
            # fast instead of serving stale-precision executables.
            # Recorded ONLY by the warmup that starts populating the
            # ladder: a re-warmup that compiles nothing must not adopt
            # a drifted key (the existing executables keep their old
            # precision — re-recording would silently defeat the
            # guard), and compiling MORE buckets under a drifted key
            # would mix precisions within one engine — refuse both.
            cur_key = xcache._policy_key()
            with self._lock:
                have = bool(self._executables)
            if not have:
                with self._lock:
                    self._warm_policy_obj = bt.policy()
                    self._warm_policy_key = cur_key
            elif (self._policy is None
                    and cur_key != self._warm_policy_key):
                raise DTypePolicyDriftError(
                    f"cannot re-warm engine {self.name!r} under a "
                    f"drifted dtype policy: its executables were "
                    f"traced under (param/compute/output)="
                    f"{self._warm_policy_key}, the process policy is "
                    f"now {cur_key}.  Restore the policy or build a "
                    f"fresh engine.")
            params, state = self._weights
            for b in self.buckets:
                if b in self._executables:
                    continue
                spec = jax.ShapeDtypeStruct((b,) + row_shape, row_dtype)
                t0 = time.perf_counter()
                # resolve through the SHARED executable cache: another
                # engine over the same architecture, or a validator pass
                # at this batch shape, already paid this compile
                exe, built = xcache.get().get_or_compile(
                    self._fwd.jitted, self._fwd.fn_key,
                    (params, state, spec))
                dt = time.perf_counter() - t0
                with self._lock:
                    self._executables[b] = exe
                self._m_compiles.inc()
                fresh += 1
                logger.info("serve warmup: bucket %d %s in %.3fs", b,
                            "compiled" if built else "cache hit", dt)
        finally:
            if self._policy is not None:
                bt.set_policy(prev)
                with _PIN_LOCK:
                    _PIN_DEPTH -= 1
        return fresh

    def refresh(self):
        """Re-capture (and re-pin) the model's CURRENT params/state.

        The engine freezes weights at construction — training the model
        afterwards does NOT change what is served until this is called.
        Shapes/dtypes must be unchanged, so the per-bucket executables
        (which take params as arguments, not constants) are reused:
        refresh never recompiles.  Implemented as stage+commit, so it is
        atomic against concurrent submits (no future ever observes new
        params paired with old state)."""
        self.stage_weights(self.model.params(), self.model.state())
        self.commit_weights()
        return self

    # -- versioned hot swap (serve/cluster.py rollout protocol) -------------
    def stage_weights(self, params, state, version: int | None = None):
        """Phase 1 of a rollout: pin a new (params, state) pair to device
        WITHOUT serving it.  Serving continues on the committed weights;
        a staged pair costs HBM but no latency.  Shapes must match the
        warmed executables (params are executable ARGUMENTS).  On a
        quantized engine the incoming FULL-PRECISION tree is quantized
        here with the capture recipe, so rollouts ship fp weights and
        every replica applies its own precision."""
        import jax
        params = self._capture(params)
        cur = self._weights[0]
        if jax.tree_util.tree_structure(params) != \
                jax.tree_util.tree_structure(cur):
            raise ValueError("staged params tree does not match the "
                             "serving model's structure")
        # leaf shapes/dtypes must match too: the warmed executables take
        # params as ARGUMENTS at fixed avals, so a wrong-width stage
        # that committed would fail EVERY later batch instead of this
        # rollout (defeating the converge-back-on-failure protocol)
        def _dt(leaf):
            return np.dtype(getattr(leaf, "dtype", type(leaf)))

        for new, old in zip(jax.tree_util.tree_leaves(params),
                            jax.tree_util.tree_leaves(cur)):
            if np.shape(new) != np.shape(old) or _dt(new) != _dt(old):
                raise ValueError(
                    f"staged param leaf {np.shape(new)} {_dt(new)} does "
                    f"not match the served {np.shape(old)} {_dt(old)}")
        staged = (jax.device_put(params, self.device),
                  jax.device_put(state, self.device))
        with self._lock:
            if version is None:
                version = self.weights_version + 1
            # note: version may be LOWER than the serving version — a
            # rollback-by-version rollout intentionally serves an older
            # store entry; only the WeightStore numbering is monotonic
            self._staged = (int(version), staged)
        # a staged pair costs HBM but no latency — exactly what the
        # ledger's tenant breakdown exists to make visible
        from bigdl_tpu.obs import ledger as obs_ledger
        obs_ledger.note_tenant("staged_weights",
                               obs_ledger.tree_nbytes(staged),
                               engine=self.name)
        return self

    def _clear_staged_tenant(self):
        from bigdl_tpu.obs import ledger as obs_ledger
        obs_ledger.note_tenant("staged_weights", 0, engine=self.name)

    def commit_weights(self) -> int:
        """Phase 2: atomically flip serving to the staged weights.  The
        swap is one tuple assignment under the lock — in-flight batches
        finish on the version they captured; every batch assembled after
        this call serves the new version.  Returns the new version."""
        with self._lock:
            if self._staged is None:
                raise RuntimeError("commit_weights without stage_weights")
            version, staged = self._staged
            self._prev_weights = (self.weights_version, self._weights)
            self._weights = staged
            self.weights_version = version
            self._staged = None
        self._clear_staged_tenant()
        self._m_version.set(version)
        self._emit("weights_commit", version=version)
        return version

    def rollback_weights(self):
        """Drop a staged-but-uncommitted pair (rollout aborted before
        the flip).  No-op when nothing is staged."""
        with self._lock:
            self._staged = None
        self._clear_staged_tenant()
        return self

    def revert_weights(self) -> int:
        """Undo the LAST commit (one-deep history): flip back to the
        previously served pair.  The rollout coordinator uses this when
        a peer replica fails mid-commit, so the fleet converges back to
        one version with zero dropped futures."""
        with self._lock:
            if self._prev_weights is None:
                raise RuntimeError("revert_weights without a prior commit")
            version, weights = self._prev_weights
            self._weights = weights
            self.weights_version = version
            self._prev_weights = None
        self._m_version.set(version)
        self._emit("weights_revert", version=version)
        return version

    # -- submit side --------------------------------------------------------
    def submit(self, x, trace=None) -> Future:
        """Queue one row (shape = model input WITHOUT the batch dim);
        returns a future resolving to that row's output array.
        ``trace`` (an ``obs.trace.Trace``) rides the request and is
        stamped by the H2D and compute stages — the router passes one
        for sampled requests.

        A request whose payload is non-finite fails its OWN future with
        :class:`PoisonedRequestError` (the rest of its micro-batch is
        served) — stricter than the pre-engine Predictor loop, which
        forwarded NaN/Inf rows to the model silently.

        Raises :class:`DTypePolicyDriftError` when the process dtype
        policy no longer matches the one the warmed executables were
        traced under (engines constructed with an explicit ``policy``
        pin their own and are immune to process drift)."""
        self._check_policy_drift()
        req = _Request(np.asarray(x), trace=trace)
        # closed-check and enqueue under the lock: close() flips _closed
        # under the same lock, so a request can never slip into the
        # queue after close()'s final leftover drain (its future would
        # hang forever)
        shed = False
        with self._lock:
            if self._closed:
                raise RuntimeError("ServeEngine is closed")
            depth = self._queue.qsize() + 1
            if self.max_queue is not None and depth > self.max_queue:
                # admission shed: fail fast instead of queuing past any
                # deadline; the future fails, the pipeline never sees it
                self._m_req["shed"].inc()
                shed = True
            else:
                self._m_req["accepted"].inc()
                self._inflight += 1
                self._m_inflight.set(self._inflight)
                self._m_qdepth.set(depth)
                if depth > self._max_queue_depth:
                    self._max_queue_depth = depth
                    self._m_qmax.set(depth)
                self._queue.put(req)   # unbounded put: never blocks
        if shed:
            self._emit("shed", queue_depth=self.max_queue)
            req.future.set_exception(SheddedError(
                f"engine queue full ({self.max_queue} requests)"))
        return req.future

    def _check_policy_drift(self):
        """Fail fast when the ambient dtype policy drifted since warmup
        (the docstring caveat made loud).  Engines with an explicit
        ``policy`` re-pin it around every trace, so only ambient-policy
        engines can drift.  Identity fast path first — the hot submit
        path pays one ``is`` check."""
        if self._policy is not None or self._warm_policy_obj is None:
            return
        if _PIN_DEPTH:
            # a sibling engine's pinned-policy warmup holds the process
            # policy swapped for the duration of its compilation; that
            # transient is trace-time state, not a drift of THIS
            # engine's ambient policy — it restores on exit
            return
        from bigdl_tpu import tensor as bt
        cur = bt.policy()
        if cur is self._warm_policy_obj:
            return
        from bigdl_tpu.serve import xcache
        key = xcache._policy_key()
        if key == self._warm_policy_key:
            # same dtypes under a different policy object: executables
            # are still precision-correct — adopt the new identity
            self._warm_policy_obj = cur
            return
        raise DTypePolicyDriftError(
            f"dtype policy drifted since warmup: engine {self.name!r} "
            f"compiled its executables under "
            f"(param/compute/output)={self._warm_policy_key} but the "
            f"process policy is now {key}.  Restore the policy, pin one "
            f"with ServeEngine(policy=...), or build a fresh engine "
            f"under the new policy.")

    def submit_many(self, rows) -> list:
        """Queue an iterable of rows; returns their futures in order."""
        return [self.submit(r) for r in rows]

    def predict(self, features) -> np.ndarray:
        """Synchronous convenience: submit every row of ``features``
        (n, ...) and return the stacked outputs (n, ...)."""
        futs = self.submit_many(np.asarray(features))
        return np.stack([f.result() for f in futs])

    # -- pipeline stages ----------------------------------------------------
    def _assemble_loop(self):
        while True:
            try:
                first = self._queue.get(timeout=0.05)
            except queue.Empty:
                continue
            if isinstance(first, _End):
                self._h2d_q.put(_END)
                return
            reqs = [first]
            deadline = time.perf_counter() + self.max_wait_s
            while len(reqs) < self.max_batch:
                try:
                    # drain whatever is already queued without paying a
                    # condition-variable wakeup per row (measured ~ms
                    # each under load); the timed wait is only for the
                    # deadline tail
                    nxt = self._queue.get_nowait()
                except queue.Empty:
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        break
                    try:
                        nxt = self._queue.get(timeout=remaining)
                    except queue.Empty:
                        break
                if isinstance(nxt, _End):
                    # flush what we have, then propagate shutdown
                    self._dispatch(reqs)
                    self._h2d_q.put(_END)
                    return
                reqs.append(nxt)
            self._dispatch(reqs)

    def _dispatch(self, reqs):
        """Validate rows, pad to the bucket, hand to the H2D stage.
        Never raises: a bad batch fails its own futures, the batcher
        thread lives on."""
        good = []
        for r in reqs:
            err = self._vet(r.x)
            if err is None:
                good.append(r)
            else:
                self._fail([r], err)
        if not good:
            return
        try:
            bucket = bucketing.bucket_for(len(good), self.max_batch)
            xs, n = bucketing.pad_rows(np.stack([r.x for r in good]),
                                       bucket)
            # finiteness is vetted on the STACKED batch (one fused
            # reduction, ~5x cheaper than per-row on the hot thread);
            # only a failing batch pays the per-row scan to isolate and
            # fail the poisoned rows, then the clean rest re-dispatches
            if (np.issubdtype(xs.dtype, np.floating)
                    and not np.all(np.isfinite(xs))):
                clean = []
                for r in good:
                    if np.all(np.isfinite(r.x)):
                        clean.append(r)
                    else:
                        self._fail([r], PoisonedRequestError(
                            "request contains non-finite values"))
                if not clean:
                    return
                bucket = bucketing.bucket_for(len(clean), self.max_batch)
                xs, n = bucketing.pad_rows(
                    np.stack([r.x for r in clean]), bucket)
                good = clean
        except BaseException as e:
            self._fail(good, e)
            return
        self._m_bucket[bucket].inc()
        self._m_qdepth.set(self._queue.qsize())
        self._h2d_q.put((good, xs, bucket, n))

    def _vet(self, x):
        """Admission check for one row: shape against the warmed spec.
        Returns an exception to fail the row's future with, or None.
        (Finiteness is checked batch-level in ``_dispatch``.)"""
        if self._row_shape is not None and tuple(x.shape) != self._row_shape:
            return ValueError(
                f"row shape {tuple(x.shape)} != engine shape "
                f"{self._row_shape}")
        return None

    def _h2d_loop(self):
        import jax
        while True:
            item = self._h2d_q.get()
            if isinstance(item, _End):
                self._exec_q.put(_END)
                return
            reqs, xs, bucket, n = item
            try:
                self._chaos_h2d()
                xdev = jax.device_put(xs, self.device)
            except BaseException as e:
                self._fail(reqs, e)
                continue
            ts = time.perf_counter()
            for r in reqs:
                if r.trace is not None:
                    r.trace.stamp("h2d", ts)
            self._exec_q.put((reqs, xdev, bucket, n))

    def _chaos_h2d(self):
        from bigdl_tpu.resilience import faults
        inj = faults.get()
        if inj is not None and inj.armed("serve_h2d"):
            if inj.fires("serve_h2d"):
                raise OSError("injected serve_h2d transfer failure")

    def _compute_loop(self):
        while True:
            item = self._exec_q.get()
            if isinstance(item, _End):
                return
            reqs, xdev, bucket, n = item
            try:
                exe = self._executables.get(bucket)
                if exe is None:
                    # first traffic before an explicit warmup: compile
                    # the whole ladder NOW so this is the last cold stop
                    self.warmup(tuple(xdev.shape[1:]), xdev.dtype)
                    exe = self._executables[bucket]
                # ONE read of the (params, state) tuple: a concurrent
                # refresh/commit swaps the whole pair atomically, so a
                # batch always serves a consistent weight version
                params, state = self._weights
                out = np.asarray(exe(params, state, xdev))
            except BaseException as e:
                self._fail(reqs, e)
                continue
            out = bucketing.trim(out, n)
            done = time.perf_counter()
            with self._lock:
                # completed inc'd under the SAME lock as the inflight
                # decrement so stats() never sees the transient where
                # completed+failed+inflight != accepted
                self._inflight -= len(reqs)
                self._m_inflight.set(self._inflight)
                self._m_batches.inc()
                self._m_req["completed"].inc(len(reqs))
            for r in reqs:
                self._m_latency.observe(done - r.t_submit)
                if r.trace is not None:
                    # stamped BEFORE set_result: the router's done
                    # callback runs on this thread and stamps complete
                    # after, keeping the hop chain monotone
                    r.trace.stamp("compute", done)
                    # the version this batch actually served — the
                    # replay tool's weight pin (host-only, traced-only)
                    from bigdl_tpu.obs import recorder as obs_recorder
                    obs_recorder.note(r.trace.trace_id,
                                      weights_version=self.weights_version,
                                      engine=self.name)
            for i, r in enumerate(reqs):
                r.future.set_result(out[i])

    def _fail(self, reqs, exc):
        with self._lock:
            self._inflight -= len(reqs)
            self._m_inflight.set(self._inflight)
            self._m_req["failed"].inc(len(reqs))
        self._emit("error", error=f"{type(exc).__name__}: {exc}",
                   requests=len(reqs))
        for r in reqs:
            if not r.future.done():
                r.future.set_exception(exc)

    # -- telemetry ----------------------------------------------------------
    def _emit(self, kind: str, **fields):
        from bigdl_tpu.obs import events
        events.emit("serve", kind=kind, **fields)

    def latency_quantiles(self, qs=(50, 95, 99)) -> dict:
        """Percentiles from the registry's fixed-bucket histogram —
        quantized to the pinned bounds (obs/metrics.LATENCY_BUCKETS),
        which is exactly what makes them mergeable across replicas."""
        from bigdl_tpu.obs import metrics as obs_metrics
        counts = self._m_latency.counts()
        bounds = self._m_latency.bounds
        return {f"p{int(q)}": obs_metrics.quantile(bounds, counts, q)
                for q in qs}

    def inflight(self) -> int:
        """Requests accepted but not yet resolved (the router's
        least-loaded signal)."""
        with self._lock:
            return self._inflight

    def stats(self) -> dict:
        """Snapshot: latency percentiles (seconds), queue depth, bucket
        hit counts, compile count, and the four monotonic admission
        counters (``accepted``/``shed``/``completed``/``failed``) — a
        thin VIEW over this engine's series in the process metrics
        registry (``obs/metrics.py``); the registry is the source of
        truth the fleet merge and the Prometheus exporter read.

        Counter semantics: monotonic from engine construction, NEVER
        reset — rate-difference two snapshots to get a rate (the router
        does exactly that).  ``completed + failed + inflight ==
        accepted`` at every instant; shed requests appear only in
        ``shed``.  ``served``/``errors`` are the pre-router aliases of
        completed/failed and stay for compatibility."""
        with self._lock:
            # the admission counters are read under the same lock their
            # paired inflight updates happen under, so the snapshot
            # satisfies completed+failed+inflight == accepted exactly
            inflight = self._inflight
            queue_depth = self._queue.qsize()
            max_depth = self._max_queue_depth
            version = self.weights_version
            accepted, shed = self.accepted, self.shed
            completed, failed = self.served, self.errors
        out = {
            "accepted": accepted,
            "shed": shed,
            "completed": completed,
            "failed": failed,
            "inflight": inflight,
            "served": completed,
            "batches": self.batches,
            "errors": failed,
            "compiles": self.compiles,
            "weights_version": version,
            "quant": self.quant,
            "queue_depth": queue_depth,
            "max_queue_depth": max_depth,
            "bucket_hits": {b: int(c.value)
                            for b, c in self._m_bucket.items()},
            "buckets": list(self.buckets),
        }
        out.update(self.latency_quantiles())
        return out

    # -- lifecycle ----------------------------------------------------------
    def drain(self, timeout: float = 30.0):
        """Block until every submitted request has resolved (the batcher
        deadline flushes partial batches, so this terminates)."""
        t0 = time.perf_counter()
        while True:
            with self._lock:
                if self._inflight == 0:
                    return self
            if time.perf_counter() - t0 > timeout:
                raise TimeoutError("serve drain timed out")
            time.sleep(0.002)

    def close(self, drain: bool = True):
        """Stop the engine.  ``drain=True`` (default) serves everything
        already queued first; ``drain=False`` fails pending futures."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        if not drain:
            pending = []
            try:
                while True:
                    r = self._queue.get_nowait()
                    if not isinstance(r, _End):
                        pending.append(r)
            except queue.Empty:
                pass
            if pending:
                self._fail(pending, RuntimeError("ServeEngine closed"))
        self._queue.put(_END)
        self._assembler.join(timeout=30.0)
        self._transfer.join(timeout=30.0)
        self._compute.join(timeout=30.0)
        # a submit racing close() may have queued behind the shutdown
        # sentinel; nothing will serve it now — fail it, don't hang it
        leftovers = []
        try:
            while True:
                r = self._queue.get_nowait()
                if not isinstance(r, _End):
                    leftovers.append(r)
        except queue.Empty:
            pass
        if leftovers:
            self._fail(leftovers, RuntimeError("ServeEngine closed"))
        self._emit("stop", **{k: v for k, v in self.stats().items()
                              if not isinstance(v, (dict, list))})

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

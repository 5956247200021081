"""Replica pool: N serve engines behind one SLO router, with versioned
hot weight rollout (docs/serving.md "Control plane").

The ServeEngine (PR 5) maximizes ONE process/chip slice; production
traffic needs the layer above it — the role the reference delegated to
Spark's driver + task scheduler (Engine.nodeNumber executors behind one
job queue).  Here that layer is explicit and TPU-shaped:

- :class:`LocalReplica` — an in-process ServeEngine (one per chip slice
  of this host; on the CPU CI mesh, N replicas share the virtual
  devices).
- :class:`ProcessReplica` — a subprocess running :func:`replica_main`
  with its OWN jax runtime (the production shape: each replica owns its
  slice; a replica crash is a process death, not a pool death),
  speaking a length-prefixed pickle protocol over stdin/stdout.  Killed
  replicas fail their outstanding futures with
  :class:`~bigdl_tpu.serve.router.DeadReplicaError`, which the router
  requeues onto survivors — the 4-replica chaos drill
  (``tests/test_serve_cluster.py``, ``BIGDL_FAULTS=serve_kill@...``)
  proves zero lost futures.  The child is NOT a telemetry black hole:
  its obs events stream to the parent's event log over the same frame
  protocol (``op: event``), its metrics registry snapshots are pulled
  on demand (``op: telemetry``) and merged into the fleet view, its
  stderr is captured into a bounded ring whose tail rides
  :class:`DeadReplicaError` messages and the crash bundle an unexpected
  death dumps, and sampled request traces (``obs/trace.py``) cross the
  boundary on the submit/reply frames with their hop stamps intact
  (``CLOCK_MONOTONIC`` is host-wide, so parent+child hops stay
  subtractable).
- :class:`ReplicaPool` — replicas + :class:`~bigdl_tpu.serve.router.Router`
  + :class:`WeightStore`, with the two-phase rollout protocol::

      rollout(params, state)
        │ 1. STAGE on all   — every replica pins version v+1 next to v;
        │                     serving continues on v (costs HBM only)
        │ 2. COMMIT (flip)  — each replica's flip is ONE tuple swap
        │                     between batches: in-flight batches finish
        │                     on v, every later batch serves v+1
        └─ on ANY failure  — staged-only replicas drop the pair;
                             already-committed replicas revert (one-deep
                             history), the fleet converges back to v,
                             zero in-flight futures dropped

  Every phase emits an obs ``serve`` event (rollout_begin /
  rollout_commit / rollout_rollback) so a postmortem can reconstruct
  which versions served when.

Flags: ``BIGDL_SERVE_REPLICAS`` (pool size default),
``BIGDL_SERVE_SLO_MS`` / ``BIGDL_SERVE_SHED`` (router admission —
serve/router.py), ``BIGDL_SERVE_HOSTS`` / ``BIGDL_SERVE_TOKEN`` /
``BIGDL_SERVE_LIVENESS_S`` (cross-host fleet over TCP replica agents —
serve/remote.py), ``BIGDL_SERVE_MAX_FRAME_MB`` (frame-size bound —
serve/frames.py).
"""
from __future__ import annotations

import itertools
import logging
import os
import pickle
import subprocess
import sys
import threading
import time
from collections import deque
from concurrent.futures import Future

import jax
import numpy as np

from bigdl_tpu.serve.engine import (PoisonedRequestError, ServeEngine,
                                    SheddedError)
from bigdl_tpu.serve.frames import FrameProtocolError
from bigdl_tpu.serve.frames import read_frame as _read_frame
from bigdl_tpu.serve.frames import write_frame as _write_frame
from bigdl_tpu.serve.paging import RequestTooLongError
from bigdl_tpu.serve.router import (DeadReplicaError, Router,
                                    replicas_default)
from bigdl_tpu.serve.streaming import StreamFuture, TokenDelivery
from bigdl_tpu.utils.engine import CHECKOUT, enable_compile_cache

logger = logging.getLogger("bigdl_tpu.serve")

_POOL_SEQ = itertools.count()

#: bounded per-replica stderr ring (lines); the tail is what a
#: postmortem actually needs — the jax traceback right before death
_STDERR_LINES = 256

#: exception names a worker may report, mapped back to real types so
#: router retry logic and caller except-clauses behave identically for
#: local and subprocess replicas
_EXC_TYPES = {
    "PoisonedRequestError": PoisonedRequestError,
    "SheddedError": SheddedError,
    "DeadReplicaError": DeadReplicaError,
    "RequestTooLongError": RequestTooLongError,
    "FrameProtocolError": FrameProtocolError,
    "ValueError": ValueError,
    "RuntimeError": RuntimeError,
    "OSError": OSError,
}


class RolloutError(RuntimeError):
    """A two-phase weight rollout failed and was rolled back; every
    replica is serving the PREVIOUS version."""


class ReplicaSpawnError(RuntimeError):
    """A replica child died (or timed out) during the spawn/warmup
    handshake — before it ever took traffic.  Carries the child's
    stderr ring tail (``stderr_tail``) so the jax traceback that killed
    the warmup is IN the exception, not lost to a raw frame error.
    The autoscaler's retry/backoff + circuit breaker key on this type
    (``serve/autoscale.py``)."""

    def __init__(self, message: str, stderr_tail=None):
        super().__init__(message)
        self.stderr_tail = list(stderr_tail or [])


#: deterministic spawn-failure chaos knob: a replica worker started
#: with BIGDL_SERVE_SPAWN_FAIL=1 in its env exits during the warmup
#: handshake (after the init frame, before `ready`) — the drill site
#: behind the ReplicaSpawnError and circuit-breaker regression tests
ENV_SPAWN_FAIL = "BIGDL_SERVE_SPAWN_FAIL"

#: names the jax platform of a replica worker process; unset, the
#: worker runs where its parent's environment points it
#: (``JAX_PLATFORMS``, else the best backend the machine has)
ENV_WORKER_PLATFORM = "BIGDL_SERVE_WORKER_PLATFORM"


def child_process_env(env=None) -> dict:
    """Environment for a replica worker this process is about to start
    (stdio :class:`ProcessReplica` children and loopback replica
    agents): this process's environment, minus its event-log dir, plus
    the checkout on ``PYTHONPATH`` and the caller's ``env`` overrides.

    Refuses the one combination that cannot work: a chip belongs to ONE
    process, so a parent whose jax runtime already holds the TPU cannot
    start a child that would open it too — the child fails or hangs on
    the chip's lock.  Either the parent stays off jax (build the model
    on the host, let the workers own the chips), or the replicas run
    in-process (:class:`ReplicaPool` places replica ``i`` on local
    device ``i``), or the child is told another platform."""
    child_env = dict(os.environ)
    # the child must NOT inherit the parent's event-log dir: its
    # events reach the parent's log over `op: event` frames
    # (append_foreign, attributed replica=<name>); an inherited
    # BIGDL_OBS_DIR would make the child open the same
    # events.p0.jsonl and double-write every event.  An explicit
    # env={...} override can still opt a child into its own file sink.
    from bigdl_tpu.obs import events as obs_events
    child_env.pop(obs_events.ENV_DIR, None)
    child_env["PYTHONPATH"] = (CHECKOUT + os.pathsep
                               + child_env.get("PYTHONPATH", ""))
    if env:
        child_env.update(env)
    child_platform = (child_env.get(ENV_WORKER_PLATFORM)
                      or child_env.get("JAX_PLATFORMS") or "tpu")
    from jax._src import xla_bridge
    if (child_platform.split(",")[0] == "tpu"
            and xla_bridge.backends_are_initialized()
            and jax.default_backend() == "tpu"):
        raise ReplicaSpawnError(
            "this process already holds the TPU, and a chip belongs to "
            "one process: a child replica started now would fail or "
            "hang opening it.  Keep the parent off jax, run the "
            "replicas in-process, or set "
            f"{ENV_WORKER_PLATFORM} for the child")
    return child_env


def next_local_device(counter):
    """Device for the next in-process replica of a pool: replica ``i``
    takes ``jax.local_devices()[i % n]``, so N replicas on an N-chip
    host own a chip each instead of all landing on chip 0.
    ``counter`` is the pool's ``itertools.count()`` of replicas placed."""
    devices = jax.local_devices()
    return devices[next(counter) % len(devices)]


def init_worker_runtime():
    """jax set-up of a replica worker process (stdio worker and TCP
    agent), before its first backend touch.  The platform is the one
    ``BIGDL_SERVE_WORKER_PLATFORM`` names, else whatever the inherited
    environment gives jax — a worker never quietly drops to the CPU
    under a parent that runs on the chip.  A CPU worker pins its device
    count and full matmul precision (parity with the in-process CPU
    tests); every worker shares the persistent compile cache, so a
    replica's warm-up is a cache read."""
    platform = os.environ.get(ENV_WORKER_PLATFORM)
    if platform:
        jax.config.update("jax_platforms", platform)
    if (platform or os.environ.get("JAX_PLATFORMS")) == "cpu":
        jax.config.update(
            "jax_num_cpu_devices",
            int(os.environ.get("BIGDL_SERVE_WORKER_DEVICES", "1")))
        jax.config.update("jax_default_matmul_precision", "highest")
    enable_compile_cache()
    os.environ.setdefault("BIGDL_CHECK_SINGLETON", "0")


# ---------------------------------------------------------------------------
# weight store
# ---------------------------------------------------------------------------

class WeightStore:
    """Monotonically versioned in-memory checkpoint store for rollouts.

    ``put`` snapshots (params, state) as HOST numpy copies — the
    training loop's donated device buffers are dead after the next
    step, so a rollout must never alias them.  Versions only grow;
    ``get`` of any retained version supports rollback to it."""

    def __init__(self, keep: int = 4):
        self._lock = threading.Lock()
        self._versions: dict = {}
        self._next = 1
        self.keep = max(2, int(keep))

    def _snapshot(self, tree):
        import jax
        return jax.tree_util.tree_map(lambda l: np.array(l), tree)

    def put(self, params, state) -> int:
        snap = (self._snapshot(params), self._snapshot(state))
        with self._lock:
            version = self._next
            self._next += 1
            self._versions[version] = snap
            while len(self._versions) > self.keep:
                del self._versions[min(self._versions)]
            retained = list(self._versions.values())
        # host-RAM tenant truth: every retained snapshot's bytes (the
        # store is host numpy, not HBM — the breakdown table labels it)
        from bigdl_tpu.obs import ledger as obs_ledger
        obs_ledger.note_tenant(
            "weight_store_host",
            sum(obs_ledger.tree_nbytes(s) for s in retained))
        return version

    def put_model(self, model) -> int:
        return self.put(model.params(), model.state())

    def get(self, version: int):
        with self._lock:
            if version not in self._versions:
                raise KeyError(f"weight version {version} not in store "
                               f"(have {sorted(self._versions)})")
            return self._versions[version]

    def latest(self) -> int | None:
        with self._lock:
            return max(self._versions) if self._versions else None

    def versions(self) -> list:
        with self._lock:
            return sorted(self._versions)


# ---------------------------------------------------------------------------
# replicas
# ---------------------------------------------------------------------------

class LocalReplica:
    """One in-process ServeEngine wearing the replica surface the
    router expects (submit/inflight/alive/stats + the rollout verbs)."""

    #: flight-recorder transport attribution (obs/recorder.py)
    transport = "inproc"

    def __init__(self, engine: ServeEngine, name: str = "local"):
        self.engine = engine
        self.name = name

    def submit(self, x, trace=None) -> Future:
        return self.engine.submit(x, trace=trace)

    def registry_snapshot(self) -> dict | None:
        """None: a local replica's engine instruments already live in
        THIS process's registry — the pool's merge would double-count
        them if we returned a copy here."""
        return None

    def inflight(self) -> int:
        return self.engine.inflight()

    def alive(self) -> bool:
        e = self.engine
        return (not e._closed and e._assembler.is_alive()
                and e._compute.is_alive())

    def stats(self) -> dict:
        return self.engine.stats()

    def weights_version(self) -> int:
        return self.engine.weights_version

    def stage_weights(self, params, state, version=None):
        self.engine.stage_weights(params, state, version)

    def commit_weights(self) -> int:
        return self.engine.commit_weights()

    def rollback_weights(self):
        self.engine.rollback_weights()

    def revert_weights(self) -> int:
        return self.engine.revert_weights()

    def close(self, drain: bool = True):
        self.engine.close(drain=drain)


class ProcessReplica:
    """A serve replica in its own OS process (its own jax runtime /
    chip slice).  The parent ships the model once at spawn; requests and
    rollout verbs ride length-prefixed pickle frames over stdin/stdout.
    Process death — including a ``BIGDL_FAULTS=serve_kill@...`` chaos
    kill — fails every outstanding future with :class:`DeadReplicaError`
    so the router can requeue them on a surviving replica.

    Subclasses repoint ``_WORKER_MODULE`` / override :meth:`_init_frame`
    to spawn a different worker over the SAME frame transport — the
    disaggregated fleet's prefill/decode replicas (``serve/fleet.py``)
    ride this class unchanged below the init handshake."""

    #: ``python -m <module>`` entry point of the child worker
    _WORKER_MODULE = "bigdl_tpu.serve.cluster"

    #: flight-recorder transport attribution (obs/recorder.py)
    transport = "stdio"

    def _init_frame(self, model, worker_kwargs) -> dict:
        """The first frame shipped to the child (the spawn handshake)."""
        return {"op": "init", "model": model, "engine": worker_kwargs}

    def __init__(self, model, name: str = "proc", env=None,
                 spawn_timeout: float = 120.0, **engine_kwargs):
        self.name = name
        self._lock = threading.Lock()
        self._wlock = threading.Lock()
        self._futures: dict = {}   # rid -> (future, trace-or-None)
        self._ids = iter(range(1, 1 << 62))
        self._dead = False
        self._closing = False
        self._stderr_ring = deque(maxlen=_STDERR_LINES)
        #: lazy parent-side delivery thread for incremental token
        #: frames (streaming decode replicas) — user callbacks must
        #: never run on, or block, the frame-reader thread
        self._delivery = None

        child_env = child_process_env(env)
        # the child engine's registry series must not collide with a
        # same-named engine in another replica once snapshots merge
        engine_kwargs = dict(engine_kwargs)
        engine_kwargs.setdefault("name", name)
        # stderr CAPTURED, not discarded: the ring tail is the first
        # thing a dead-replica postmortem needs (the old DEVNULL made
        # every child crash an unexplained DeadReplicaError)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", self._WORKER_MODULE],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, env=child_env)
        self._stderr_reader = threading.Thread(
            target=self._stderr_loop, daemon=True,
            name=f"bigdl-serve-{name}-stderr")
        self._stderr_reader.start()
        try:
            _write_frame(self.proc.stdin,
                         self._init_frame(model, engine_kwargs),
                         self._wlock)
        except (OSError, ValueError) as e:
            # the child died before reading its init frame (EPIPE): a
            # raw pipe error carries nothing — raise the typed spawn
            # error with whatever the child said on stderr
            raise self._spawn_error(
                f"replica {name} rejected the init frame: "
                f"{type(e).__name__}: {e}") from e
        self._reader = threading.Thread(target=self._read_loop,
                                        daemon=True,
                                        name=f"bigdl-serve-{name}-reader")
        self._ready = threading.Event()
        self._reader.start()
        if not self._ready.wait(spawn_timeout):
            raise self._spawn_error(
                f"replica {name} did not come up in {spawn_timeout}s")
        if self._dead:
            raise self._spawn_error(
                f"replica {name} died during startup (exit code "
                f"{self.proc.poll()})")

    # -- wire ---------------------------------------------------------------
    def _read_loop(self):
        while True:
            try:
                msg = _read_frame(self.proc.stdout)
            except FrameProtocolError as e:
                # a malformed/corrupt/desynced frame from the child is
                # indistinguishable from death for recovery purposes,
                # but the POSTMORTEM must name the protocol violation
                logger.warning("replica %s: %s; treating as death",
                               self.name, e)
                msg = None
            except (OSError, ValueError, EOFError, pickle.PickleError):
                msg = None
            if msg is None:
                self._on_death()
                return
            op = msg.get("op")
            if op == "ready":
                self._ready.set()
                continue
            if op == "event":
                # a child obs event forwarded over the frame protocol:
                # land it in the PARENT's event log, attributed
                self._forward_event(msg.get("event"))
                continue
            if op == "tokens":
                # an incremental token chunk from a streaming decode
                # request (serve/fleet.py fleet_main): feed the rpc
                # future WITHOUT popping it — the terminal reply frame
                # still resolves it.  The chunk's absolute start index
                # rides the frame so the StreamFuture dedup survives
                # the process hop.  Fed through a parent-side delivery
                # thread, NOT inline: user on_tokens callbacks hang off
                # the piped chain, and a slow (or cross-request
                # blocking) consumer must never park the reader thread
                # that every reply frame from this replica rides.
                with self._lock:
                    entry = self._futures.get(msg.get("id"))
                if entry is not None:
                    self._ensure_delivery().enqueue(
                        entry[0], msg.get("tokens") or [],
                        msg.get("start"), None)
                continue
            with self._lock:
                entry = self._futures.pop(msg.get("id"), None)
            if entry is None:
                continue
            fut, tr = entry
            if msg.get("ok"):
                if tr is not None:
                    # hops the child stamped after the wire crossing
                    tr.extend(msg.get("hops") or ())
                    if msg.get("rec"):
                        # the child's flight-recorder notes merge into
                        # the parent's record (same frame as the hops)
                        from bigdl_tpu.obs import recorder as obs_rec
                        obs_rec.note(tr.trace_id, **msg["rec"])
                if fut.streaming and self._delivery is not None:
                    # streaming submits resolve through the delivery
                    # FIFO so the final token chunk always lands before
                    # result() unblocks (the decoder-side contract)
                    self._delivery.resolve(fut, msg.get("out"))
                else:
                    fut.set_result(msg.get("out"))
            else:
                cls = _EXC_TYPES.get(msg.get("etype"), RuntimeError)
                fut.set_exception(cls(msg.get("error", "replica error")))

    def _stderr_loop(self):
        try:
            for raw in self.proc.stderr:
                self._stderr_ring.append(
                    raw.decode("utf-8", errors="replace").rstrip("\n"))
        except (OSError, ValueError):  # pragma: no cover - pipe teardown
            pass

    def stderr_tail(self, n: int | None = None) -> list:
        """Last captured stderr lines (newest last)."""
        tail = list(self._stderr_ring)
        return tail if n is None else tail[-n:]

    def _tail_suffix(self, n: int = 8) -> str:
        tail = self.stderr_tail(n)
        if not tail:
            return ""
        return "; stderr tail:\n  " + "\n  ".join(tail)

    def _dead_error(self) -> DeadReplicaError:
        return DeadReplicaError(
            f"replica {self.name} (pid {self.proc.pid}) died"
            f"{self._tail_suffix()}")

    def _spawn_error(self, message: str) -> ReplicaSpawnError:
        """Constructor-failure epilogue: kill the child (idempotent),
        drain its stderr to EOF so the tail is complete, and return the
        typed error with the tail attached — a spawn failure must leak
        neither the subprocess nor the reason it died."""
        self._closing = True     # death past this point is expected
        try:
            self.proc.kill()
        except OSError:   # pragma: no cover - already gone
            pass
        try:
            self.proc.wait(timeout=5.0)
        except Exception:   # pragma: no cover - still exiting
            pass
        self._stderr_reader.join(timeout=2.0)
        return ReplicaSpawnError(message + self._tail_suffix(),
                                 stderr_tail=self.stderr_tail())

    def _forward_event(self, event):
        if not isinstance(event, dict):
            return
        try:
            from bigdl_tpu.obs import events as obs_events
            log = obs_events.get()
            if log is not None:
                log.append_foreign(event, replica=self.name)
        except Exception:  # pragma: no cover - telemetry must not kill IO
            logger.warning("replica %s: event forward failed", self.name)

    def _on_death(self):
        with self._lock:
            if self._dead:
                return
            self._dead = True
            orphans = [f for f, _ in self._futures.values()]
            self._futures.clear()
        # release a constructor stuck waiting for the ready frame — a
        # child that crashes during startup must fail fast, not after
        # the full spawn timeout (__init__ re-checks _dead)
        self._ready.set()
        # drain the stderr pipe to EOF before freezing the tail: the
        # stdout EOF that got us here can beat the child's last stderr
        # line by a scheduling quantum
        if threading.current_thread() is not self._stderr_reader:
            self._stderr_reader.join(timeout=2.0)
        # poll only AFTER the drain: a crashing child closes stdout
        # before it finishes dying, and a stale early poll() reading
        # None would skip the crash bundle below for idle-replica
        # deaths (no orphans to trip the other condition)
        exit_code = self.proc.poll()
        if exit_code is None and not self._closing:
            try:
                exit_code = self.proc.wait(timeout=2.0)
            except Exception:  # pragma: no cover - still exiting
                pass
        err = self._dead_error()
        for fut in orphans:
            if not fut.done():
                fut.set_exception(err)
        # an UNEXPECTED death (not close()) leaves a crash bundle with
        # the child's stderr tail — the blackout the old DEVNULL caused
        if not self._closing and (orphans or exit_code not in (0, None)):
            try:
                from bigdl_tpu.obs import diagnostics
                diagnostics.dump_crash_bundle(
                    f"replica-{self.name}",
                    extra={"replica": self.name, "pid": self.proc.pid,
                           "exit_code": exit_code,
                           "orphaned_requests": len(orphans)},
                    texts={"stderr.txt": "\n".join(self.stderr_tail())})
            except Exception:  # pragma: no cover - diagnostics bug
                pass

    def _ensure_delivery(self) -> TokenDelivery:
        if self._delivery is None:
            self._delivery = TokenDelivery(name=self.name)
        return self._delivery

    def _rpc(self, op: str, timeout: float | None = None, **fields):
        fut = self._send(op, **fields)
        return fut.result(timeout=timeout)

    def _send(self, op: str, _trace=None, **fields) -> Future:
        rid = next(self._ids)
        # StreamFuture so decode submits can receive incremental token
        # frames (op: tokens); every other rpc just resolves it
        fut = StreamFuture()
        with self._lock:
            if self._dead:
                fut.set_exception(self._dead_error())
                return fut
            self._futures[rid] = (fut, _trace)
        try:
            _write_frame(self.proc.stdin,
                         dict(fields, op=op, id=rid), self._wlock)
        except FrameProtocolError as e:
            # an over-bound payload fails ONLY this rpc — nothing was
            # written, the stream stays frame-aligned, the replica lives
            with self._lock:
                self._futures.pop(rid, None)
            fut.set_exception(e)
        except (OSError, ValueError):
            self._on_death()
        return fut

    # -- replica surface ----------------------------------------------------
    def submit(self, x, trace=None) -> Future:
        return self._send(
            "submit", _trace=trace, x=np.asarray(x),
            trace=None if trace is None else trace.to_wire())

    def inflight(self) -> int:
        with self._lock:
            return len(self._futures)

    def alive(self) -> bool:
        return not self._dead and self.proc.poll() is None

    def stats(self) -> dict:
        return self._rpc("stats", timeout=30.0)

    def telemetry(self) -> dict:
        """``{"stats": engine.stats(), "registry": <metrics snapshot>}``
        pulled from the child over the frame protocol."""
        return self._rpc("telemetry", timeout=30.0)

    def registry_snapshot(self) -> dict | None:
        """The child process's metrics-registry snapshot (obs/metrics
        wire format) for the pool's fleet merge."""
        return self.telemetry().get("registry")

    def weights_version(self) -> int:
        return self._rpc("version", timeout=30.0)

    def stage_weights(self, params, state, version=None):
        self._rpc("stage", timeout=120.0, params=params, state=state,
                  version=version)

    def commit_weights(self) -> int:
        return self._rpc("commit", timeout=30.0)

    def rollback_weights(self):
        self._rpc("rollback", timeout=30.0)

    def revert_weights(self) -> int:
        return self._rpc("revert", timeout=30.0)

    def close(self, drain: bool = True):
        self._closing = True    # death past this point is expected
        if self.alive():
            try:
                self._rpc("close", timeout=60.0, drain=drain)
            except Exception:
                pass
        try:
            self.proc.wait(timeout=10.0)
        except subprocess.TimeoutExpired:
            self.proc.kill()
        self._on_death()
        # an unexpected death dumps its crash bundle on the READER
        # thread; close() returning means death handling (bundle
        # included) is complete
        if threading.current_thread() is not self._reader:
            self._reader.join(timeout=10.0)
        if self._delivery is not None:
            # flush pending chunks/resolutions, then stop the thread
            self._delivery.close()
            self._delivery = None


def wait_drained(router, victim, timeout: float):
    """Block until a drain-marked replica's backlog (router-outstanding
    + its own inflight) resolves; a victim dying mid-drain counts as
    drained — its orphans ride the requeue-on-death path.  Raises
    TimeoutError (nothing dropped, victim left draining) on expiry.
    Shared by ``ReplicaPool.remove_replica`` and
    ``DecodeFleet.remove_replica``."""
    t0 = time.monotonic()
    while True:
        pending = router.pending_for(victim)
        try:
            if victim.alive():
                pending += victim.inflight()
        except Exception:   # pragma: no cover - racing a death
            pass
        if pending == 0:
            return
        if time.monotonic() - t0 > timeout:
            raise TimeoutError(
                f"replica {getattr(victim, 'name', victim)} did not "
                f"drain in {timeout}s ({pending} pending); left "
                f"draining, nothing dropped")
        time.sleep(0.005)


class DynamicMembership:
    """The shared dynamic-membership surface (docs/serving.md
    "Autoscaling"): membership gauges, the drain-to-zero
    ``remove_replica`` contract, and the autoscaler hookup —
    :class:`ReplicaPool` and :class:`~bigdl_tpu.serve.fleet.DecodeFleet`
    both mix this in so the drain/accounting logic cannot diverge.

    Host-class requirements: ``name``, ``replicas``, ``router``,
    ``_scale_lock`` (RLock) and ``_warming`` exist before
    :meth:`_init_membership` is called; ``add_replica(reason=)`` is
    host-specific (the warm bar differs: weight versions for engine
    pools, compile-only for decode fleets)."""

    def _init_membership(self):
        from bigdl_tpu.obs import metrics as obs_metrics
        self.autoscaler = None
        reg = obs_metrics.get()
        self._m_members = {
            state: reg.gauge(
                "fleet_replicas",
                "pool membership by state (live/warming/draining)",
                state=state, pool=self.name)
            for state in ("live", "warming", "draining")}
        self._m_scale = {
            d: reg.counter("fleet_scale_events_total",
                           "committed scale actions by direction",
                           direction=d, pool=self.name)
            for d in ("up", "down")}
        self._update_membership()

    def membership(self) -> dict:
        """``{"live": n, "warming": n, "draining": n}`` — the counts
        behind the ``fleet_replicas`` gauges and serve_top's ``fleet:``
        line (live excludes draining; dead replicas count nowhere)."""
        live = draining = 0
        for r in list(self.replicas):
            try:
                ok = r.alive()
            except Exception:
                ok = False
            if not ok:
                continue
            if self.router.is_draining(r):
                draining += 1
            else:
                live += 1
        with self._scale_lock:
            warming = self._warming
        return {"live": live, "warming": warming, "draining": draining}

    def _update_membership(self) -> dict:
        m = self.membership()
        try:
            for state, gauge in self._m_members.items():
                gauge.set(m[state])
        except Exception:   # pragma: no cover - registry mid-teardown
            pass
        return m

    def _resolve_victim(self, replica):
        """An instance, a name, or None (→ the newest non-draining
        live replica: scale-down unwinds scale-up, LIFO)."""
        if replica is None:
            for r in reversed(self.replicas):
                try:
                    if r.alive() and not self.router.is_draining(r):
                        return r
                except Exception:
                    continue
            return None
        if isinstance(replica, str):
            return next((r for r in self.replicas
                         if getattr(r, "name", None) == replica), None)
        return replica if replica in self.replicas else None

    def remove_replica(self, replica=None, reason: str = "manual",
                       timeout: float = 120.0):
        """Drain one replica out of the pool with ZERO dropped futures
        (the hot-swap bar): mark it drain-only in the router (dispatch
        skips it, its queued/in-flight requests still complete), wait
        for its backlog to resolve, then detach and close it.  A victim
        dying mid-drain rides the normal requeue-on-death path.
        ``replica`` may be an instance, a name, or None (newest live
        replica).  Raises TimeoutError — replica left draining, nothing
        dropped — if the backlog does not resolve in ``timeout``."""
        from bigdl_tpu.obs import events
        with self._scale_lock:
            victim = self._resolve_victim(replica)
            if victim is None:
                raise ValueError(f"no such live replica: {replica!r}")
            live = [r for r in self.replicas
                    if r is not victim and r.alive()
                    and not self.router.is_draining(r)]
            if not live:
                raise ValueError(
                    "refusing to drain the last live replica")
            self.router.mark_draining(victim)
        self._update_membership()
        try:
            wait_drained(self.router, victim, timeout)
        except TimeoutError:
            self._update_membership()
            raise
        with self._scale_lock:
            self.router.remove_replica(victim)
            if victim in self.replicas:
                self.replicas.remove(victim)
        try:
            victim.close(drain=True)
        except Exception:   # pragma: no cover - died mid-drain
            pass
        self._update_membership()
        self._m_scale["down"].inc()
        events.emit("scale", kind="down",
                    replica=getattr(victim, "name", repr(victim)),
                    reason=reason, replicas=len(self.replicas))
        return victim

    def start_autoscaler(self, **kwargs):
        """Start the SLO-driven autoscaler loop (``serve/autoscale.py``)
        over ``merged_registry()`` and the membership verbs
        (``BIGDL_SERVE_AUTOSCALE=1`` auto-starts one at construction).
        Closed with the pool; idempotent — but kwargs passed to an
        ALREADY-RUNNING autoscaler (e.g. one the env auto-started) are
        a config conflict and logged loudly rather than silently
        dropped."""
        if self.autoscaler is not None:
            if kwargs:
                logger.warning(
                    "start_autoscaler(%s): an autoscaler is already "
                    "running (BIGDL_SERVE_AUTOSCALE auto-start?); the "
                    "new settings are IGNORED — close() it first to "
                    "reconfigure", ", ".join(sorted(kwargs)))
            return self.autoscaler
        from bigdl_tpu.serve import autoscale as autoscale_mod
        self.autoscaler = autoscale_mod.Autoscaler(self, **kwargs).start()
        return self.autoscaler


# ---------------------------------------------------------------------------
# the pool
# ---------------------------------------------------------------------------

class ReplicaPool(DynamicMembership):
    """N replicas + router + weight store: the serving control plane.

    ``ReplicaPool(model, n_replicas=4)`` builds in-process replicas
    (each its own ServeEngine and executable set — all riding the
    shared xcache, so N replicas of one architecture compile each
    bucket ONCE); ``process=True`` spawns subprocess replicas instead.
    ``replicas=[...]`` injects pre-built replicas (tests, heterogeneous
    pools) and ``replica_factory=fn(name)`` overrides how NEW replicas
    are built (tests, custom spawn env).  Requests flow
    ``pool.submit(x, priority=, slo_ms=)`` → router admission →
    least-loaded replica.

    Membership is DYNAMIC (docs/serving.md "Autoscaling"):
    :meth:`add_replica` spawns and warms a replica — through the xcache
    and the fleet's COMMITTED weight version — before the router may
    dispatch to it, and :meth:`remove_replica` drains a victim to zero
    backlog before closing it.  ``BIGDL_SERVE_AUTOSCALE=1`` arms the
    closed loop (``serve/autoscale.py``) over these verbs."""

    def __init__(self, model=None, n_replicas: int | None = None,
                 process: bool = False, replicas=None,
                 slo_ms: float | None = None, shed: bool | None = None,
                 est_ms: float = 50.0, store: WeightStore | None = None,
                 trace_sample: float | None = None,
                 name: str | None = None, replica_factory=None,
                 remote: bool | None = None, hosts=None, token=None,
                 **engine_kwargs):
        self.name = name or f"pool{next(_POOL_SEQ)}"
        self._model = model
        self._process = bool(process)
        self._engine_kwargs = dict(engine_kwargs)
        self._replica_factory = replica_factory
        # cross-host fleet (docs/serving.md "Cross-host fleet"):
        # remote=True (or hosts=/BIGDL_SERVE_HOSTS) leases replica-agent
        # addresses from a HostInventory and speaks TCP instead of
        # spawning local children — the autoscaler then scales across
        # the inventory, and exhaustion surfaces as ReplicaSpawnError
        # (the same circuit-breaker type as a local spawn failure)
        self._inventory = None
        if remote or (remote is None and hosts is not None):
            from bigdl_tpu.serve import remote as remote_mod
            self._inventory = remote_mod.HostInventory(hosts, token=token)
        elif remote is None and hosts is None and token is None:
            from bigdl_tpu.serve import remote as remote_mod
            if remote_mod.hosts_default():
                self._inventory = remote_mod.HostInventory()
        #: serializes membership changes against rollouts: a replica
        #: added mid-rollout must land on the COMMITTED version, never
        #: the staged one (the two-phase-rollout bar)
        self._scale_lock = threading.RLock()
        #: last version a rollout COMMITTED fleet-wide (None = the
        #: construction weights; a late spawn then captures the model's
        #: current weights, the documented engine semantic)
        self._served_version: int | None = None
        self._warming = 0
        self._next_replica = 0
        self._placed = itertools.count()   # in-process replicas built
        if replicas is None:
            if model is None and replica_factory is None:
                raise ValueError(
                    "ReplicaPool needs a model, replicas, or a "
                    "replica_factory")
            n = replicas_default() if n_replicas is None else int(n_replicas)
            replicas = []
            try:
                for _ in range(n):
                    replicas.append(self._spawn_replica(
                        self._next_name()))
            except Exception:
                # one bad replica fails construction CLEANLY: the
                # already-spawned good ones are closed, no subprocess
                # leaks past the raise (the ReplicaSpawnError contract)
                for r in replicas:
                    try:
                        r.close(drain=False)
                    except Exception:   # pragma: no cover - teardown
                        pass
                raise
        self.replicas = list(replicas)
        self._next_replica = max(self._next_replica, len(self.replicas))
        self.router = Router(self.replicas, slo_ms=slo_ms, shed=shed,
                             est_ms=est_ms, trace_sample=trace_sample)
        self.store = store if store is not None else WeightStore()
        self.exporter = None
        self.alerts = None
        self._init_membership()
        try:
            # BIGDL_OBS_HBM_SAMPLE=<s>: cadence HBM sampler for the
            # serving process (process-wide, started once)
            from bigdl_tpu.obs import ledger as obs_ledger
            obs_ledger.maybe_start_sampler_from_env()
        except Exception:   # pragma: no cover - obs layer unavailable
            pass
        from bigdl_tpu.obs import export as obs_export
        port = obs_export.export_port_default()
        if port is not None:
            try:
                self.start_exporter(port=port)
            except OSError as e:
                # e.g. a second pool in this process with a fixed
                # BIGDL_SERVE_EXPORT_PORT: the replicas are already
                # spawned, so a bind failure must not abort (and leak)
                # the pool — serve without the exporter instead
                logger.warning("exporter auto-start on port %d failed "
                               "(%s); pool runs without one", port, e)
        from bigdl_tpu.serve import autoscale as autoscale_mod
        if autoscale_mod.autoscale_default():
            # BIGDL_SERVE_AUTOSCALE=1: close the loop — the SLO-driven
            # autoscaler watches merged_registry() and drives
            # add_replica/remove_replica against the env-declared
            # min/max bounds and cadence
            self.start_autoscaler()

    # -- request path -------------------------------------------------------
    def submit(self, x, priority: int = 1,
               slo_ms: float | None = None) -> Future:
        return self.router.submit(x, priority=priority, slo_ms=slo_ms)

    def submit_many(self, rows, priority: int = 1,
                    slo_ms: float | None = None) -> list:
        return self.router.submit_many(rows, priority=priority,
                                       slo_ms=slo_ms)

    def predict(self, features) -> np.ndarray:
        futs = self.submit_many(np.asarray(features))
        return np.stack([f.result() for f in futs])

    # -- dynamic membership (docs/serving.md "Autoscaling") -----------------
    def _next_name(self) -> str:
        n = self._next_replica
        self._next_replica += 1
        if self._inventory is not None:
            return f"remote{n}"
        return f"{'proc' if self._process else 'local'}{n}"

    def _spawn_replica(self, name: str, env=None, **overrides):
        """Build one replica the way this pool was configured
        (``replica_factory`` > remote lease > subprocess > in-process
        engine).  Construction IS the xcache warmup: the engine
        compiles every bucket before this returns."""
        if self._replica_factory is not None:
            return self._replica_factory(name)
        if self._model is None:
            raise RuntimeError(
                "dynamic membership needs the pool's model (this pool "
                "was built from pre-built replicas; pass "
                "replica_factory= to scale it)")
        kw = dict(self._engine_kwargs)
        kw.update(overrides)
        if self._inventory is not None:
            from bigdl_tpu.serve import remote as remote_mod
            kw.pop("env", None)
            addr = self._inventory.lease()
            try:
                return remote_mod.RemoteReplica(
                    addr, self._model, name=name,
                    token=self._inventory.token,
                    on_release=self._inventory.release, **kw)
            except Exception:
                # failed spawns hand the host back: the autoscaler's
                # retry may succeed once the agent is reachable again
                self._inventory.release(addr)
                raise
        if self._process:
            # a pool-level env={...} (chaos plans, worker platform)
            # lives in engine_kwargs for back-compat with the old
            # inline-construction path; the per-call env= wins
            if env is None:
                env = kw.pop("env", None)
            else:
                kw.pop("env", None)
            return ProcessReplica(self._model, name=name, env=env, **kw)
        if "device" not in kw:
            kw["device"] = next_local_device(self._placed)
        logger.info("replica %s on %s", name, kw["device"])
        return LocalReplica(ServeEngine(self._model, name=name, **kw),
                            name=name)

    def add_replica(self, name: str | None = None,
                    reason: str = "manual", env=None, **overrides):
        """Spawn, WARM, then register one replica.  The warmup bar: the
        replica compiles its executables at construction (through the
        shared xcache — an identical architecture costs zero new
        compiles) and is rolled to the fleet's COMMITTED weight version
        before the router may dispatch to it.  A rollout racing this
        call wins: the warm loop re-stages until the version it warmed
        to is still the committed one at registration time, so a
        scale-up mid-rollout can never serve a staged-but-uncommitted
        version.  Emits a schema-validated ``scale``/``up`` event;
        spawn/warm failure closes the half-built replica and re-raises
        (the autoscaler's retry/backoff + circuit breaker sit above
        this)."""
        from bigdl_tpu.obs import events
        if name is None:
            with self._scale_lock:
                name = self._next_name()
        with self._scale_lock:
            self._warming += 1
        self._update_membership()
        try:
            replica = self._spawn_replica(name, env=env, **overrides)
        except Exception:
            with self._scale_lock:
                self._warming -= 1
            self._update_membership()
            raise
        try:
            while True:
                with self._scale_lock:
                    version = self._served_version
                if (version is not None
                        and replica.weights_version() != version):
                    params, state = self.store.get(version)
                    replica.stage_weights(params, state, version)
                    replica.commit_weights()
                with self._scale_lock:
                    if self._served_version == version:
                        # still the committed version: take traffic
                        self.replicas.append(replica)
                        self.router.add_replica(replica)
                        self._warming -= 1
                        break
                # a rollout committed while we warmed — re-warm to the
                # new served version before touching the dispatch set
        except Exception:
            with self._scale_lock:
                self._warming -= 1
            self._update_membership()
            try:
                replica.close(drain=False)
            except Exception:   # pragma: no cover - already dead
                pass
            raise
        self._update_membership()
        self._m_scale["up"].inc()
        events.emit("scale", kind="up", replica=name, reason=reason,
                    replicas=len(self.replicas))
        return replica

    # -- rollout ------------------------------------------------------------
    def rollout(self, params=None, state=None,
                version: int | None = None) -> int:
        """Two-phase hot swap: stage on every live replica, then flip.
        Pass (params, state) to publish new weights, or ``version`` to
        roll the fleet to/back to a stored version.  Returns the served
        version; raises :class:`RolloutError` (after converging every
        replica back to the prior version) when any replica fails.

        Serialized against dynamic membership (``_scale_lock``): a
        replica being ADDED during the stage→commit window warms to the
        version this rollout commits before it may take traffic, and a
        DRAINING replica is excluded from the target set — its backlog
        finishes on the version it already has, and its mid-drain close
        can never fail the commit."""
        with self._scale_lock:
            return self._rollout_locked(params, state, version)

    def _rollout_locked(self, params, state, version) -> int:
        from bigdl_tpu.obs import events

        if params is not None:
            version = self.store.put(params, state)
        elif version is None:
            version = self.store.latest()
            if version is None:
                raise ValueError("rollout with an empty WeightStore")
        params, state = self.store.get(version)
        reps = self.router.live_replicas(draining=False)
        if not reps:
            raise RolloutError("no live replica to roll out to")
        events.emit("serve", kind="rollout_begin", version=version,
                    replicas=len(reps))

        staged = []
        try:
            for r in reps:
                r.stage_weights(params, state, version)
                staged.append(r)
        except Exception as e:
            for r in staged:
                try:
                    r.rollback_weights()
                except Exception:  # pragma: no cover - replica died too
                    pass
            events.emit("serve", kind="rollout_rollback", version=version,
                        phase="stage", error=f"{type(e).__name__}: {e}")
            raise RolloutError(
                f"stage phase failed on replica "
                f"{getattr(reps[len(staged)], 'name', '?')}: {e}") from e

        committed = []
        try:
            for r in reps:
                r.commit_weights()
                committed.append(r)
        except Exception as e:
            # converge BACK: flip committed replicas to the previous
            # pair, drop the stage on the rest — no mixed-version fleet
            for r in committed:
                try:
                    r.revert_weights()
                except Exception:  # pragma: no cover
                    pass
            for r in reps[len(committed):]:
                try:
                    r.rollback_weights()   # no-op when already consumed
                except Exception:  # pragma: no cover
                    pass
            events.emit("serve", kind="rollout_rollback", version=version,
                        phase="commit", error=f"{type(e).__name__}: {e}")
            raise RolloutError(
                f"commit phase failed; fleet reverted: {e}") from e

        self._served_version = version
        events.emit("serve", kind="rollout_commit", version=version,
                    replicas=len(committed))
        return version

    @property
    def served_version(self) -> int | None:
        """The last version a rollout committed fleet-wide (None until
        the first rollout: replicas serve their construction capture).
        The warm bar :meth:`add_replica` rolls a new replica to."""
        with self._scale_lock:
            return self._served_version

    # -- telemetry / lifecycle ----------------------------------------------
    def merged_registry(self) -> dict:
        """One metrics snapshot covering the WHOLE fleet: this
        process's registry (the router + every LocalReplica engine +
        decoders + xcache) folded with each subprocess replica's
        registry snapshot, pulled over the frame protocol.  Histograms
        merge exactly (pinned bounds), counters/gauges per their agg —
        the fleet p99 this returns IS the pooled p99
        (``obs/metrics.merge``).

        Scope: the in-process half is the PROCESS-LIFETIME registry
        (Prometheus default-registry semantics), so series from earlier
        pools or engines in this process are included; counters stay
        monotonic across pool turnover.  Per-pool deltas come from
        rate-differencing two snapshots, not from a fresh-at-zero
        registry."""
        from bigdl_tpu.obs import metrics as obs_metrics
        snaps = [obs_metrics.get().snapshot()]
        for r in list(self.replicas):   # membership may change under us
            try:
                snaps.append(r.registry_snapshot())
            except Exception:  # pragma: no cover - racing a death
                logger.warning("telemetry pull failed for replica %s",
                               getattr(r, "name", r))
        return obs_metrics.merge(snaps)

    def prometheus(self) -> str:
        """The merged fleet registry in Prometheus text exposition
        format (what the exporter's ``/metrics`` serves)."""
        from bigdl_tpu.obs import metrics as obs_metrics
        return obs_metrics.render_prometheus(self.merged_registry())

    def start_exporter(self, port: int = 0, host: str = "127.0.0.1"):
        """Start the pull exporter over :meth:`merged_registry`
        (``BIGDL_SERVE_EXPORT_PORT`` auto-starts one at pool
        construction).  Returns the exporter; idempotent."""
        if self.exporter is None:
            from bigdl_tpu.obs import export as obs_export
            self.exporter = obs_export.MetricsExporter(
                self.merged_registry, port=port, host=host)
        return self.exporter

    def start_alerts(self, rules=None, interval: float = 5.0,
                     **rule_kwargs):
        """Start the declarative alert engine (``obs/alerts.py``) over
        :meth:`merged_registry` — the fleet-truth signal surface the
        autoscaler story consumes.  ``rules=None`` installs the default
        set (SLO burn, shed rate, queue depth, step-time regression,
        HBM headroom; ``rule_kwargs`` tune its bounds).  Fired/resolved
        transitions emit ``alert`` events and ``alert_active`` gauges,
        which ride THIS process's registry and therefore the exporter
        and ``serve_top``'s ``alerts:`` line.  Closed with the pool;
        idempotent."""
        if self.alerts is None:
            from bigdl_tpu.obs import alerts as obs_alerts
            if rules is None:
                rules = obs_alerts.default_rules(**rule_kwargs)
            self.alerts = obs_alerts.AlertEngine(
                self.merged_registry, rules,
                interval=interval).start()
        return self.alerts

    def stats(self) -> dict:
        """Fleet snapshot: the router's counters, one entry per replica
        (its ``engine.stats()`` view), and ``merged`` — the TRUE merge
        of every replica's metrics registry (fleet-pooled latency
        quantiles, summed admission counters), not a dict of dicts."""
        from bigdl_tpu.obs import metrics as obs_metrics
        out = {"router": self.router.stats(), "replicas": []}
        snaps = [obs_metrics.get().snapshot()]
        for r in list(self.replicas):
            entry = {"name": getattr(r, "name", repr(r)),
                     "alive": False}
            try:
                entry["alive"] = r.alive()
                if entry["alive"]:
                    tele = getattr(r, "telemetry", None)
                    if tele is not None:
                        # ONE frame round-trip per subprocess replica:
                        # telemetry() ships stats + registry together
                        t = tele()
                        entry.update(t["stats"])
                        if t.get("registry"):
                            snaps.append(t["registry"])
                    else:
                        entry.update(r.stats())
                        snap = r.registry_snapshot()
                        if snap:
                            snaps.append(snap)
            except Exception:  # pragma: no cover - racing a death
                pass
            out["replicas"].append(entry)
        out["merged"] = obs_metrics.serving_summary(obs_metrics.merge(snaps))
        return out

    def drain(self, timeout: float = 60.0):
        self.router.drain(timeout)
        return self

    def close(self, drain: bool = True):
        if self.autoscaler is not None:
            # first: a scale decision must not race the teardown
            self.autoscaler.close()
            self.autoscaler = None
        if drain:
            try:
                self.router.drain()
            except TimeoutError:  # pragma: no cover - shutdown path
                pass
        if self.alerts is not None:
            self.alerts.close()
            self.alerts = None
        if self.exporter is not None:
            self.exporter.close()
            self.exporter = None
        self.router.close()
        for r in list(self.replicas):
            try:
                r.close(drain=drain)
            except Exception:  # pragma: no cover
                pass
        try:
            # uniquely-labelled, possibly short-lived membership/scale
            # series die with the pool (the decoder/tier precedent)
            from bigdl_tpu.obs import metrics as obs_metrics
            obs_metrics.get().drop_series(pool=self.name)
        except Exception:   # pragma: no cover - registry mid-teardown
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------------------
# transport-agnostic worker op dispatch
# ---------------------------------------------------------------------------

class WorkerOps:
    """Transport-agnostic op dispatch for one replica worker.

    The SAME handler instance answers frames whether they arrived over
    a ProcessReplica's stdio pipe (:func:`worker_main`) or a
    :class:`~tools.replica_agent.ReplicaAgent` TCP session — the op-code
    set cannot diverge between transports because there is exactly one
    implementation of it.  ``send(msg)`` is the transport's reply
    channel (frame writer or session outbox); :meth:`handle` returns
    False when the worker should shut down (the ``close`` op).

    Subclasses own a ``target`` (engine / decode replica / prefill
    replica) and extend :meth:`_handle_role` with role-specific ops."""

    role = "worker"

    def __init__(self, send):
        from bigdl_tpu.resilience import faults
        self.send = send
        self.injector = faults.get()
        self.target = None

    # -- reply plumbing -----------------------------------------------------
    def _ok(self, rid, out):
        self.send({"id": rid, "ok": True, "out": out})

    def _err(self, rid, exc):
        self.send({"id": rid, "ok": False, "etype": type(exc).__name__,
                   "error": str(exc)})

    def _reply(self, rid, fut, tr=None):
        try:
            out = fut.result()
            msg = {"id": rid, "ok": True, "out": out}
            if tr is not None:
                # only the hops stamped on THIS side of the wire; the
                # parent extends its original context with them
                msg["hops"] = tr.new_hops()
                from bigdl_tpu.obs import recorder as obs_rec
                rec = obs_rec.export_notes(tr.trace_id)
                if rec:
                    # this side's flight-recorder notes (decode flags,
                    # committed row, page counters, weight version)
                    # ride the SAME reply frame as the hops
                    msg["rec"] = rec
            self.send(msg)
        except BaseException as e:
            self._err(rid, e)

    def _chaos_kill(self):
        """``BIGDL_FAULTS=serve_kill@at=N``: die at the Nth submitted
        request — the requeue-on-replica-death chaos site.  For a TCP
        agent this kills the whole agent process (real death, not a
        blip — ``serve_partition`` is the blip site)."""
        inj = self.injector
        if (inj is not None and inj.armed("serve_kill")
                and inj.fires("serve_kill")):
            # last words on stderr: the parent's ring captures them and
            # the kill drill asserts the tail survives into
            # DeadReplicaError + the crash bundle
            print(f"serve_kill chaos fired: {self.role} replica pid "
                  f"{os.getpid()} exiting", file=sys.stderr, flush=True)
            sys.stdout.flush()
            os._exit(1)   # induced replica death (chaos drill)

    # -- dispatch -----------------------------------------------------------
    def handle(self, msg) -> bool:
        """Answer one frame; False = close requested (worker exits)."""
        op, rid = msg.get("op"), msg.get("id")
        try:
            if op == "ping":
                # connection-liveness probe (RemoteReplica's heartbeat;
                # harmless no-op over stdio)
                self._ok(rid, {"pong": True, "role": self.role})
            elif op == "stats":
                self._ok(rid, self.target.stats())
            elif op == "telemetry":
                from bigdl_tpu.obs import metrics as obs_metrics
                self._ok(rid, {"stats": self.target.stats(),
                               "registry": obs_metrics.get().snapshot()})
            elif op == "close":
                self.target.close(drain=msg.get("drain", True))
                self._ok(rid, None)
                return False
            else:
                return self._handle_role(op, rid, msg)
        except BaseException as e:
            self._err(rid, e)
        return True

    def _handle_role(self, op, rid, msg) -> bool:
        self.send({"id": rid, "ok": False, "etype": "ValueError",
                   "error": f"unknown op {op!r} for role "
                            f"{self.role!r}"})
        return True

    def close_abrupt(self):
        """EOF/protocol-death epilogue: close the target undrained."""
        if self.target is not None:
            self.target.close(drain=False)


class EngineOps(WorkerOps):
    """The serve-engine worker ops (submit + stats/telemetry + the
    two-phase rollout verbs) — :func:`replica_main`'s historical op set,
    now shared verbatim with the TCP agent."""

    role = "engine"

    def __init__(self, init, send):
        super().__init__(send)
        self.target = ServeEngine(init["model"], **init.get("engine", {}))

    def _handle_role(self, op, rid, msg) -> bool:
        engine = self.target
        if op == "submit":
            self._chaos_kill()
            from bigdl_tpu.obs import trace as obs_trace
            tr = (obs_trace.Trace.from_wire(msg["trace"])
                  if msg.get("trace") else None)
            fut = engine.submit(msg["x"], trace=tr)
            fut.add_done_callback(
                lambda f, r=rid, t=tr: self._reply(r, f, t))
        elif op == "version":
            self._ok(rid, engine.weights_version)
        elif op == "stage":
            engine.stage_weights(msg["params"], msg["state"],
                                 msg.get("version"))
            self._ok(rid, None)
        elif op == "commit":
            self._ok(rid, engine.commit_weights())
        elif op == "rollback":
            engine.rollback_weights()
            self._ok(rid, None)
        elif op == "revert":
            self._ok(rid, engine.revert_weights())
        else:
            return super()._handle_role(op, rid, msg)
        return True


def build_worker_ops(init, send) -> WorkerOps:
    """The ops handler for one ``init`` frame: engine by default, the
    fleet roles (decode/prefill) when the frame names one.  Shared by
    :func:`worker_main` (stdio) and the TCP replica agent."""
    role = init.get("role", "engine")
    if role == "engine":
        return EngineOps(init, send)
    from bigdl_tpu.serve import fleet as fleet_mod
    return fleet_mod.build_fleet_ops(init, send)


# ---------------------------------------------------------------------------
# subprocess replica worker
# ---------------------------------------------------------------------------

def worker_main(stdin=None, stdout=None):
    """Entry point of a ProcessReplica child: build the ops handler the
    init frame names (engine / decode / prefill) and answer frames
    until EOF/close.  Runs with its own jax runtime
    (:func:`init_worker_runtime` — on a real fleet each replica process
    owns its accelerator slice).

    ``BIGDL_FAULTS=serve_kill@at=N[,proc=...]`` kills this process at
    the Nth submitted request (``os._exit``) — the chaos drill for the
    router's requeue-on-replica-death path.  A malformed frame on stdin
    (:class:`~bigdl_tpu.serve.frames.FrameProtocolError`) is fatal for
    the worker: it logs the violation to stderr and exits rather than
    resynchronizing against a corrupt stream."""
    stdin = stdin or sys.stdin.buffer
    stdout = stdout or sys.stdout.buffer

    init_worker_runtime()

    init = _read_frame(stdin)
    if init is None or init.get("op") != "init":
        return 2
    if os.environ.get(ENV_SPAWN_FAIL, "0") != "0":
        # deterministic spawn-failure chaos: die during the warmup
        # handshake (init consumed, `ready` never sent) — the parent
        # must surface a typed ReplicaSpawnError with this line in the
        # stderr tail, and the autoscaler's circuit breaker must trip
        # instead of crash-looping
        print(f"induced spawn failure ({ENV_SPAWN_FAIL}): replica pid "
              f"{os.getpid()} exiting", file=sys.stderr, flush=True)
        return 7
    from bigdl_tpu.obs import events as obs_events
    wlock = threading.Lock()

    def send(msg):
        _write_frame(stdout, msg, wlock)

    # stream THIS process's obs events to the parent as they happen —
    # the sink is registered before the engine exists so even its
    # `start` event crosses the boundary.  Write failures are swallowed
    # by add_sink's contract (a dying pipe must not kill the emitter).
    log = obs_events.get()
    if log is not None:
        log.add_sink(lambda ev: send({"op": "event", "event": ev}))

    ops = build_worker_ops(init, send)
    send({"op": "ready", "pid": os.getpid()})

    while True:
        try:
            msg = _read_frame(stdin)
        except FrameProtocolError as e:
            print(f"frame protocol error on stdin: {e}; worker exiting",
                  file=sys.stderr, flush=True)
            break
        if msg is None:
            break
        if not ops.handle(msg):
            return 0
    ops.close_abrupt()
    return 0


def replica_main(stdin=None, stdout=None):
    """Back-compat alias: the engine worker entry point (init frames
    without a ``role`` build an :class:`EngineOps`)."""
    return worker_main(stdin, stdout)


if __name__ == "__main__":
    sys.exit(worker_main())

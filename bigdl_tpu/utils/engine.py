"""Engine — cluster topology + device runtime singleton.

Reference: ``utils/Engine.scala:208``.  There the Engine holds node/core
counts, two JVM thread pools (``Engine.default`` for replica parallelism,
``Engine.model`` for intra-op parallelism) and builds a pinned SparkConf.

On TPU the thread pools dissolve into XLA (intra-op parallelism is the
compiler's job) and Spark's executor topology becomes the JAX process/device
topology.  What remains is the topology bookkeeping that the data and
optimizer layers query: node_number (hosts), core_number (local devices),
plus mesh construction for the distributed optimizer.
"""
from __future__ import annotations

import os
import signal
import threading
import time
import numpy as np
import jax


#: the checkout that holds this package: where the compile cache lives
#: unless the environment places it elsewhere, and what worker processes
#: get on their PYTHONPATH
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.  Entry points (smoke, bench, profilers, perf
    CLIs, serving workers, the test suite) call this once before their
    first compile, so a second process pays a cache read, not a compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no directory is set in code; otherwise the cache is
    ``<checkout>/.xla_cache`` — a fixed path, because the path is part
    of the cache key and a directory that moves never hits.  Either way
    the size/time thresholds drop to zero so every program is cached."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".xla_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class _Engine:
    def __init__(self):
        self._initialized = False
        self._node_number = 1
        self._core_number = 1
        self._mesh = None
        self._singleton_fd = None
        self._preempted = threading.Event()
        self._preempted_at = None
        self._preempt_armed = False
        self._prev_handlers = {}

    # -- lifecycle (ref Engine.init Engine.scala:339) ---------------------
    def init(self, node_number: int | None = None, core_number: int | None = None,
             distributed: bool = False):
        """Initialize topology.  Defaults to the live JAX topology.

        ``distributed=True`` with multiple hosts expects
        ``jax.distributed.initialize`` to have been called by the launcher
        (one process per TPU VM host — the Spark-executor role in the
        reference, DistriOptimizer.scala).
        """
        # env-var topology (ref DL_NODE_NUMBER/DL_CORE_NUMBER consumed on
        # executors, Engine.scala:234-264) wins over the live JAX topology
        # so launchers can pin it the way scripts/bigdl.sh did
        if node_number is None:
            env = os.environ.get("BIGDL_NODE_NUMBER",
                                 os.environ.get("DL_NODE_NUMBER"))
            node_number = int(env) if env else jax.process_count()
        if core_number is None:
            env = os.environ.get("BIGDL_CORE_NUMBER",
                                 os.environ.get("DL_CORE_NUMBER"))
            core_number = int(env) if env else jax.local_device_count()
        self._node_number = int(node_number)
        self._core_number = int(core_number)
        self._initialized = True
        return self

    def init_distributed(self, coordinator_address: str = None,
                         num_processes: int = None, process_id: int = None):
        """Multi-host bring-up: one JAX process per TPU VM host (the Spark
        executor role, SURVEY.md §2.9/§3.1).  Wraps
        ``jax.distributed.initialize``; with no args, reads the standard
        TPU metadata (works out of the box on Cloud TPU pods).

        With ``BIGDL_ELASTIC=1`` (and explicit coordinates) the bring-up
        routes through ``resilience.elastic.initialize`` instead: same
        coordination service, but with heartbeat windows stretched so the
        runtime never self-terminates on a dead peer — the file watchdog
        is the failure detector, and the training loop re-forms the fleet
        (docs/resilience.md "Elastic training")."""
        kwargs = {}
        if coordinator_address is not None:
            kwargs = dict(coordinator_address=coordinator_address,
                          num_processes=num_processes, process_id=process_id)
        from bigdl_tpu.resilience import elastic
        if elastic.enabled():
            if coordinator_address is None:
                # silently falling through to the stock bring-up would
                # leave the flag a no-op discovered only at the first
                # peer death — fail at init, where it is fixable
                raise ValueError(
                    "BIGDL_ELASTIC=1 requires explicit coordinates "
                    "(coordinator_address/num_processes/process_id): "
                    "the elastic bring-up builds the coordination "
                    "service itself and cannot ride the TPU-metadata "
                    "auto-init — pass the coordinates or unset the flag")
            elastic.initialize(coordinator_address, num_processes,
                               process_id)
        else:
            jax.distributed.initialize(**kwargs)
        return self.init()

    def _ensure_init(self):
        if not self._initialized:
            self.init()

    # -- singleton guard (ref Engine.checkSingleton Engine.scala:222-232) --
    def check_singleton(self) -> bool:
        """Detect a second training process contending for this host's TPU.

        The reference guards against two BigDL tasks landing in one executor
        JVM (they would corrupt the shared thread pools); the TPU analog is
        two processes trying to own the same local chips.  Uses a pid lock
        file per host; stale locks (dead pid) are reclaimed.  Disable with
        ``BIGDL_CHECK_SINGLETON=0`` (the ``bigdl.check.singleton`` knob,
        ref Optimizer.scala:63).
        """
        if os.environ.get("BIGDL_CHECK_SINGLETON", "1") == "0":
            return True
        if self._singleton_fd is not None:
            return True  # this process already holds the lock
        import fcntl
        import tempfile
        path = os.path.join(tempfile.gettempdir(),
                            f"bigdl_tpu_engine_{jax.process_index()}.lock")
        # flock on a long-lived fd: the kernel releases it when the process
        # dies, so there are no stale locks and no pid-file TOCTOU races —
        # exactly one live process can hold LOCK_EX at a time
        try:
            fd = os.open(path, os.O_CREAT | os.O_RDWR, 0o644)
        except PermissionError:
            # lock file owned by another user on a shared host: someone
            # else is (or was) using this host's chips — report contention
            return False
        try:
            fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
        except OSError:
            os.close(fd)
            return False
        os.truncate(fd, 0)
        os.write(fd, str(os.getpid()).encode())  # diagnostics only
        self._singleton_fd = fd  # keep open for the process lifetime
        return True

    # -- preemption (docs/resilience.md) ----------------------------------
    def install_preemption_handler(self, signals=(signal.SIGTERM,)):
        """Arm SIGTERM-as-preemption: the cluster scheduler's eviction
        notice (GCE preemption, k8s pod termination, SLURM timeout) sets
        a flag instead of killing the process, and the training loop's
        next iteration checkpoints and exits cleanly
        (checkpoint-and-exit, ``LocalOptimizer._checkpoint_and_stop``).

        Multi-host: install on EVERY process (same launcher code path) —
        the distributed loop merges the flag across hosts each iteration
        while armed, so one host's SIGTERM stops all of them at the same
        step and nobody hangs in a half-abandoned collective.  Previous
        handlers are chained.  Idempotent."""
        if self._preempt_armed:
            return self
        for sig in signals:
            def _handler(signum, frame, _sig=sig):
                # flag + timestamp only: anything heavier (logging, I/O)
                # is unsafe here; the obs layer reads preempted_at() from
                # the training loop's clean epilogue instead
                self._preempted_at = time.time()
                self._preempted.set()
                prev = self._prev_handlers.get(_sig)
                if callable(prev):
                    prev(signum, frame)
            self._prev_handlers[sig] = signal.signal(sig, _handler)
        self._preempt_armed = True
        return self

    def preemption_armed(self) -> bool:
        return self._preempt_armed

    def preempted(self) -> bool:
        """True once a preemption notice arrived (signal or
        ``request_preemption``)."""
        return self._preempted.is_set()

    def request_preemption(self):
        """Programmatic preemption notice (tests, custom schedulers) —
        same effect as the armed signal arriving.  Multi-host: the
        distributed loop only merges (and honors) the flag while
        ``install_preemption_handler`` has been called on every process;
        requesting preemption unarmed in a multi-process run is ignored
        with a warning (an unmerged one-host stop would strand the other
        hosts in a dead collective)."""
        self._preempted_at = time.time()
        self._preempted.set()
        return self

    def preempted_at(self) -> float | None:
        """Unix timestamp of the preemption notice (None if never
        preempted) — stamped into the obs ``preempt`` event so the
        postmortem can measure notice-to-checkpoint latency."""
        return self._preempted_at

    def clear_preemption(self):
        """Reset the flag (a new run in the same process)."""
        self._preempted.clear()
        self._preempted_at = None
        return self

    def engine_type(self) -> str:
        """Compute-backend tag (the reference returns MklBlas,
        Engine.scala:273-289); here the backend is XLA on the visible
        platform."""
        return f"Xla:{jax.devices()[0].platform}"

    # -- topology queries (ref Engine.scala:234-264) ----------------------
    def node_number(self) -> int:
        self._ensure_init()
        return self._node_number

    def core_number(self) -> int:
        self._ensure_init()
        return self._core_number

    def device_count(self) -> int:
        return jax.device_count()

    def local_device_count(self) -> int:
        return jax.local_device_count()

    def process_index(self) -> int:
        return jax.process_index()

    # -- mesh construction -------------------------------------------------
    def mesh(self, axis_names=("data",), shape=None, devices=None):
        """Build a ``jax.sharding.Mesh`` over the visible devices.

        With the default single "data" axis this is the topology the
        reference's DistriOptimizer assumes (pure data parallelism, one
        replica per node — DistriOptimizer.scala:361-404).  Pass
        ``axis_names=("data","model")`` + ``shape`` for hybrid shardings.
        """
        if devices is None:
            devices = np.array(jax.devices())
        else:
            devices = np.array(devices)
        if shape is None:
            shape = (len(devices),) if len(axis_names) == 1 else None
        if shape is None:
            raise ValueError("shape required for multi-axis mesh")
        devices = devices.reshape(shape)
        return jax.sharding.Mesh(devices, axis_names)

    def set_mesh(self, mesh):
        self._mesh = mesh

    def get_mesh(self):
        if self._mesh is None:
            self._mesh = self.mesh()
        return self._mesh

    def reset(self):
        if self._singleton_fd is not None:
            os.close(self._singleton_fd)  # releases the flock
        for sig, prev in self._prev_handlers.items():
            try:  # un-arm preemption: restore whatever was there before
                signal.signal(sig, prev if prev is not None
                              else signal.SIG_DFL)
            except (ValueError, TypeError, OSError):
                pass  # non-main thread / exotic prior handler
        self.__init__()


Engine = _Engine()

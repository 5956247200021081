"""Compile-time cost/memory ledger + HBM accounting
(docs/observability.md "Performance observatory").

Every executable this process runs flows through one chokepoint — the
shared executable cache (``serve/xcache.py``) — yet XLA's own
``cost_analysis()``/``memory_analysis()`` used to be consulted ad-hoc
(``bench.py``, ``tools/profile_step.py``), so MFU existed only as an
offline bench number and nobody could answer "where did HBM go" at
runtime.  This module is the shared cost-truth plane:

- :class:`CostLedger` — a process-wide ledger of every compiled
  executable's flops, bytes-accessed and (for AOT compiles) peak/temp/
  argument HBM, captured AT COMPILE TIME and keyed by the same keys the
  executable cache resolves (``ExecutableCache.key_for``).  Warm
  dispatches never touch the ledger: ``xcache`` calls :meth:`capture_*`
  only on the dispatch that compiles.  Each capture publishes
  ``ledger_*`` registry gauges (agg ``max`` — the same key IS the same
  program, so merging replica snapshots is idempotent, per-replica cost
  truth without double counting) and emits a schema-validated
  ``ledger`` obs event, so ``ReplicaPool.merged_registry()`` carries
  fleet cost truth next to the serving numbers.
- Live utilization readers: the optimizer loops marry
  :meth:`CostLedger.newest` flops with their windowed step walls to
  publish ``train_mfu``; the continuous decoder publishes
  ``decode_model_flops_util`` per sync boundary.  ``bench.py`` and
  ``tools/profile_step.py`` resolve their flops through
  :meth:`capture_compiled` — one code path, one number, so the bench
  MFU and the ledger MFU can never silently diverge (the cross-check
  ``tests/test_obs_ledger.py`` pins).
- Static HBM tenants: the known large device allocations (KV page
  pools + scale arrays, served/staged weight packs, host-side
  ``WeightStore`` snapshots) register their bytes via
  :func:`note_tenant` so ``tools/obs_report.py`` renders an HBM
  breakdown table.
- :class:`DeviceMemorySampler` — a cadence thread over
  ``utils/profiler.device_memory_stats()`` publishing in-use/limit/
  watermark gauges and ``ledger``/``hbm`` timeline events.  Close is
  stop-event + join (the ``Router.close`` SIGABRT lesson: a daemon
  thread racing interpreter teardown must be joined, not abandoned).

Master switch ``BIGDL_LEDGER=0`` disables capture entirely (the
executable cache works unchanged); everything here is best-effort by
design — a telemetry bug must never fail a compile.
"""
from __future__ import annotations

import hashlib
import itertools
import logging
import math
import os
import threading
import time

logger = logging.getLogger("bigdl_tpu.obs")

ENV_LEDGER = "BIGDL_LEDGER"
ENV_HBM_SAMPLE = "BIGDL_OBS_HBM_SAMPLE"

#: bf16 dense peak flops per chip (datasheet) — the MFU denominator.
#: One table for bench.py, the live gauges and the report tools: two
#: peak tables would let two MFUs diverge by construction.
PEAK_FLOPS = {
    "TPU v2": 45e12, "TPU v3": 123e12, "TPU v4": 275e12,
    "TPU v5 lite": 197e12, "TPU v5e": 197e12, "TPU v5": 459e12,
    "TPU v5p": 459e12, "TPU v6 lite": 918e12, "TPU v6e": 918e12,
}

def device_peak_flops(device=None) -> float | None:
    """Datasheet peak for ``device`` (default: the first jax device),
    or None on the CPU — a CPU run has no MFU, and callers publish
    none.  Any other device kind missing from :data:`PEAK_FLOPS` is an
    error: dividing by another chip's peak would print a plausible,
    wrong utilization."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    kind = device.device_kind
    known = max((k for k in PEAK_FLOPS if kind.startswith(k)), key=len,
                default=None)
    if known is None:
        raise KeyError(
            f"no datasheet peak for device kind {kind!r}: add it to "
            f"bigdl_tpu.obs.ledger.PEAK_FLOPS (known: "
            f"{sorted(PEAK_FLOPS)})")
    return PEAK_FLOPS[known]


def enabled() -> bool:
    return os.environ.get(ENV_LEDGER, "1") != "0"


def _fn_label(fn_key) -> str:
    """Stable short label for the gauge's ``fn`` dimension: the leading
    element of a tuple key (``train_step``, ``decode_step_paged``, ...)
    or the whole key's string."""
    if isinstance(fn_key, tuple) and fn_key:
        return str(fn_key[0])
    return str(fn_key)


def _key_hash(key) -> str:
    """8-hex digest of a ledger key — the gauge label that keeps two
    shapes of the same fn distinct without exploding label size."""
    return hashlib.md5(repr(key).encode()).hexdigest()[:8]


class LedgerEntry:
    """One compiled executable's cost truth.  ``flops``/
    ``bytes_accessed`` come from cost analysis (jit and AOT captures);
    the ``*_bytes`` HBM fields only from AOT captures (memory analysis
    needs the compiled object) and are None on jit-path entries."""

    __slots__ = ("fn_key", "key", "flops", "bytes_accessed",
                 "argument_bytes", "output_bytes", "temp_bytes",
                 "generated_code_bytes", "peak_bytes", "source", "ts",
                 "seq")

    def __init__(self, fn_key, key, flops=float("nan"),
                 bytes_accessed=float("nan"), argument_bytes=None,
                 output_bytes=None, temp_bytes=None,
                 generated_code_bytes=None, source="aot", seq=0):
        self.fn_key = fn_key
        self.key = key
        self.flops = float(flops)
        self.bytes_accessed = float(bytes_accessed)
        self.argument_bytes = argument_bytes
        self.output_bytes = output_bytes
        self.temp_bytes = temp_bytes
        self.generated_code_bytes = generated_code_bytes
        #: the executable's whole-program HBM footprint while running:
        #: arguments + outputs + XLA scratch + device code
        self.peak_bytes = None
        if temp_bytes is not None:
            self.peak_bytes = int((argument_bytes or 0)
                                  + (output_bytes or 0) + temp_bytes
                                  + (generated_code_bytes or 0))
        self.source = source
        self.ts = time.time()
        self.seq = seq

    def as_dict(self) -> dict:
        # fn_key reprs embed whole model fingerprints (kilobytes); the
        # event carries a capped prefix — `key` is the unique handle
        fk = repr(self.fn_key)
        if len(fk) > 120:
            fk = fk[:120] + "..."
        d = {"fn": _fn_label(self.fn_key), "fn_key": fk,
             "key": _key_hash(self.key), "flops": self.flops,
             "bytes_accessed": self.bytes_accessed,
             "source": self.source}
        for k in ("argument_bytes", "output_bytes", "temp_bytes",
                  "generated_code_bytes", "peak_bytes"):
            v = getattr(self, k)
            if v is not None:
                d[k] = int(v)
        return d


class CostLedger:
    """Process-wide compile-time cost ledger.  Thread-safe (serve
    replicas warm concurrently with a validating training thread, like
    the executable cache it mirrors)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries = {}        # key -> LedgerEntry (insertion-ordered)
        self._seq = itertools.count()
        self.captures = 0         # fresh captures (the warm-path audit
        #                           pins this to the compile count)

    # -- capture (compile-time only) ---------------------------------------
    def _record(self, entry: LedgerEntry):
        with self._lock:
            if entry.key in self._entries:
                return self._entries[entry.key]
            entry.seq = next(self._seq)
            self._entries[entry.key] = entry
            self.captures += 1
        self._publish(entry)
        return entry

    def capture_compiled(self, fn_key, compiled, key=None):
        """Ledger a ``jax.stages.Compiled`` (the AOT path): cost AND
        memory analysis.  ``key`` defaults to a per-call sequence so
        standalone probes (bench, profile_step) get distinct entries;
        ``xcache`` passes its own cache key.  Returns the entry (or
        None when the ledger is disabled) and never raises."""
        if not enabled():
            return None
        try:
            ca = compiled.cost_analysis() or {}
            kw = dict(flops=ca.get("flops", float("nan")),
                      bytes_accessed=ca.get("bytes accessed",
                                            float("nan")))
            try:
                ma = compiled.memory_analysis()
            except Exception:
                ma = None
            if ma is not None:
                kw.update(
                    argument_bytes=int(ma.argument_size_in_bytes),
                    output_bytes=int(ma.output_size_in_bytes),
                    temp_bytes=int(ma.temp_size_in_bytes),
                    generated_code_bytes=int(
                        ma.generated_code_size_in_bytes))
            if key is None:
                key = (fn_key, "call", id(compiled))
            return self._record(LedgerEntry(fn_key, key, source="aot",
                                            **kw))
        except Exception as e:   # pragma: no cover - defensive
            logger.warning("ledger AOT capture failed for %r: %s",
                           fn_key, e)
            return None

    def capture_lowered(self, fn_key, key, jitted, args):
        """Ledger a tracked-jit key from its LOWERING only (no second
        XLA compile): ``Lowered.cost_analysis()`` yields flops/bytes
        without building an executable, so the extra compile-time cost
        is one trace, and the first real dispatch still owns the
        compile.  HBM fields stay None (memory analysis needs the
        compiled object).  Must run BEFORE the dispatch — the dispatch
        may donate the argument buffers."""
        if not enabled():
            return None
        try:
            with self._lock:
                if key in self._entries:
                    return self._entries[key]
            ca = jitted.lower(*args).cost_analysis() or {}
            return self._record(LedgerEntry(
                fn_key, key, source="jit",
                flops=ca.get("flops", float("nan")),
                bytes_accessed=ca.get("bytes accessed", float("nan"))))
        except Exception as e:   # pragma: no cover - defensive
            logger.warning("ledger jit capture failed for %r: %s",
                           fn_key, e)
            return None

    def _publish(self, entry: LedgerEntry):
        """Registry gauges + the ``ledger`` obs event for one fresh
        capture.  agg='max': the same key is the same program, so a
        fleet merge of identical entries is idempotent, not additive."""
        try:
            from bigdl_tpu.obs import metrics
            reg = metrics.get()
            lab = {"fn": _fn_label(entry.fn_key),
                   "key": _key_hash(entry.key)}
            if math.isfinite(entry.flops):
                reg.gauge("ledger_flops",
                          "per-dispatch flops of one compiled "
                          "executable (XLA cost analysis)",
                          agg="max", **lab).set(entry.flops)
            if math.isfinite(entry.bytes_accessed):
                reg.gauge("ledger_bytes_accessed",
                          "per-dispatch HBM bytes accessed (XLA cost "
                          "analysis)", agg="max",
                          **lab).set(entry.bytes_accessed)
            if entry.peak_bytes is not None:
                reg.gauge("ledger_peak_hbm_bytes",
                          "whole-program HBM while running: args + "
                          "outputs + scratch + code", agg="max",
                          **lab).set(entry.peak_bytes)
        except Exception:   # pragma: no cover - obs layer mid-teardown
            pass
        try:
            from bigdl_tpu.obs import events
            events.emit("ledger", kind="exec", **entry.as_dict())
        except Exception:   # pragma: no cover - defensive
            pass

    # -- lookup (the MFU readers) ------------------------------------------
    def newest(self, fn_key):
        """Most recently captured entry whose fn_key equals ``fn_key``
        (the optimizer/decoder step programs re-key per shape; the
        newest shape is the one running)."""
        with self._lock:
            best = None
            for e in self._entries.values():
                if e.fn_key == fn_key and (best is None
                                           or e.seq > best.seq):
                    best = e
            return best

    def flops_for(self, fn_key) -> float | None:
        """Finite per-dispatch flops for ``fn_key``'s newest entry, or
        None (absent / analysis unavailable)."""
        e = self.newest(fn_key)
        if e is None or not math.isfinite(e.flops):
            return None
        return e.flops

    def entries(self) -> list:
        with self._lock:
            return list(self._entries.values())

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._entries),
                    "captures": self.captures}

    def clear(self):
        with self._lock:
            self._entries.clear()
            self.captures = 0


# -- process-wide singleton -------------------------------------------------

_LEDGER: CostLedger | None = None
_LOCK = threading.Lock()


def get() -> CostLedger:
    global _LEDGER
    if _LEDGER is None:
        with _LOCK:
            if _LEDGER is None:
                _LEDGER = CostLedger()
    return _LEDGER


def reset():
    """Drop every entry (tests; wired into the suite's autouse fixture
    like ``serve.xcache``/``obs.metrics``).  Also stops an env-started
    memory sampler so its thread never outlives the test that made it."""
    get().clear()
    stop_global_sampler()


# -- static HBM tenants -----------------------------------------------------

def note_tenant(tenant: str, nbytes, **labels):
    """Register one known large allocation's CURRENT bytes (KV page
    pools incl. scale arrays, weight packs, staged rollout pairs,
    host-side WeightStore snapshots).  Gauge semantics: call again with
    the new size (0 frees it from the breakdown); series labelled with
    the owner's own labels (``decoder=...``/``engine=...``) so the
    owner's existing ``drop_series`` teardown reclaims them.  Also
    emits a ``ledger`` event (kind=tenant) so obs_report can render
    the breakdown without a live registry.  Best-effort, never raises."""
    try:
        from bigdl_tpu.obs import metrics
        metrics.get().gauge(
            "hbm_tenant_bytes",
            "bytes held by one named large allocation",
            tenant=tenant, **labels).set(float(nbytes))
    except Exception:   # pragma: no cover - obs layer unavailable
        pass
    try:
        from bigdl_tpu.obs import events
        events.emit("ledger", kind="tenant", tenant=tenant,
                    bytes=int(nbytes), **labels)
    except Exception:   # pragma: no cover - defensive
        pass


def tree_nbytes(tree) -> int:
    """Total array bytes of a pytree (tenant sizing helper).  Never
    raises: the call sites are construction/staging paths where a
    telemetry bug must not fail serving — a leaf that cannot be sized
    (extended dtypes like PRNG keys, exotic objects) contributes 0."""
    import numpy as np

    try:
        import jax
        leaves = jax.tree_util.tree_leaves(tree)
    except Exception:   # pragma: no cover - jax-less context
        leaves = [tree]
    total = 0
    for leaf in leaves:
        try:
            size = getattr(leaf, "size", None)
            dt = getattr(leaf, "dtype", None)
            if size is None or dt is None:
                leaf = np.asarray(leaf)
                size, dt = leaf.size, leaf.dtype
            total += int(size) * int(np.dtype(dt).itemsize)
        except Exception:   # unsizable leaf: skip, never raise
            continue
    return total


# -- device-memory sampler --------------------------------------------------

class DeviceMemorySampler:
    """Cadence thread over ``utils/profiler.device_memory_stats()``:
    publishes per-device ``hbm_bytes_in_use`` / ``hbm_bytes_limit`` /
    ``hbm_bytes_peak`` gauges (agg='max' — several replicas share the
    physical device; summing would invent HBM) and one ``ledger`` event
    (kind=hbm) per tick, the timeline obs_report renders.

    Lifecycle: ``start()`` spawns the daemon thread, ``close()`` sets
    the stop event and JOINS it (bounded) — never leave the thread
    racing interpreter teardown.  Backends that expose no memory stats
    (CPU PJRT) sample cleanly to nothing; ``stats_fn`` is injectable
    for tests."""

    def __init__(self, interval: float = 10.0, stats_fn=None,
                 registry=None, emit_events: bool = True):
        if stats_fn is None:
            from bigdl_tpu.utils.profiler import device_memory_stats
            stats_fn = device_memory_stats
        self.interval = max(float(interval), 1e-3)
        self._stats_fn = stats_fn
        self._registry = registry
        self._emit_events = emit_events
        self._stop = threading.Event()
        self._thread = None
        self._peaks = {}          # device -> watermark bytes
        self.samples = 0          # ticks that saw at least one device

    def _reg(self):
        if self._registry is not None:
            return self._registry
        from bigdl_tpu.obs import metrics
        return metrics.get()

    def sample_once(self) -> dict:
        """One tick: read, publish, return the per-device dict actually
        observed ({} when the backend exposes nothing)."""
        try:
            raw = self._stats_fn() or {}
        except Exception as e:   # pragma: no cover - backend hiccup
            logger.warning("device memory sample failed: %s", e)
            return {}
        seen = {}
        for dev, st in raw.items():
            if not st:
                continue
            in_use = st.get("bytes_in_use")
            if in_use is None:
                continue
            peak = max(int(st.get("peak_bytes_in_use", 0)), int(in_use),
                       self._peaks.get(dev, 0))
            self._peaks[dev] = peak
            seen[dev] = {"in_use": int(in_use), "peak": peak}
            limit = st.get("bytes_limit")
            if limit is not None:
                seen[dev]["limit"] = int(limit)
        if not seen:
            return {}
        self.samples += 1
        try:
            reg = self._reg()
            for dev, row in seen.items():
                reg.gauge("hbm_bytes_in_use", "device HBM in use",
                          agg="max", device=dev).set(row["in_use"])
                reg.gauge("hbm_bytes_peak",
                          "device HBM in-use watermark", agg="max",
                          device=dev).set(row["peak"])
                if "limit" in row:
                    reg.gauge("hbm_bytes_limit", "device HBM capacity",
                              agg="max", device=dev).set(row["limit"])
        except Exception:   # pragma: no cover - obs layer mid-teardown
            pass
        if self._emit_events:
            try:
                from bigdl_tpu.obs import events
                events.emit(
                    "ledger", kind="hbm",
                    in_use=sum(r["in_use"] for r in seen.values()),
                    peak=sum(r["peak"] for r in seen.values()),
                    limit=sum(r.get("limit", 0) for r in seen.values()),
                    devices=seen)
            except Exception:   # pragma: no cover - defensive
                pass
        return seen

    def _run(self):
        while not self._stop.wait(self.interval):
            self.sample_once()

    def start(self):
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, daemon=True,
                name="bigdl-hbm-sampler")
            self._thread.start()
        return self

    def close(self, timeout: float = 10.0):
        """Stop-event + bounded join — idempotent."""
        self._stop.set()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=timeout)
        self._thread = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.close()


_GLOBAL_SAMPLER: DeviceMemorySampler | None = None


def maybe_start_sampler_from_env() -> DeviceMemorySampler | None:
    """Start (once) the process-wide sampler when
    ``BIGDL_OBS_HBM_SAMPLE=<seconds>`` is set — called by the long-
    lived entry points (ReplicaPool construction, optimizer run start)
    so a serving or training process self-measures without code
    changes.  Returns the sampler (or None when the env is unset/0)."""
    global _GLOBAL_SAMPLER
    raw = os.environ.get(ENV_HBM_SAMPLE, "").strip()
    if not raw:
        return None
    try:
        interval = float(raw)
    except ValueError:
        logger.warning("ignoring malformed %s=%r", ENV_HBM_SAMPLE, raw)
        return None
    if interval <= 0:
        return None
    with _LOCK:
        if _GLOBAL_SAMPLER is None:
            _GLOBAL_SAMPLER = DeviceMemorySampler(
                interval=interval).start()
    return _GLOBAL_SAMPLER


def stop_global_sampler():
    global _GLOBAL_SAMPLER
    s = _GLOBAL_SAMPLER
    _GLOBAL_SAMPLER = None
    if s is not None:
        s.close()

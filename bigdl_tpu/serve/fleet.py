"""Disaggregated serving fleet: prefix-affinity routing, prefill/decode
split, and the host-RAM KV tier behind one admission point
(docs/serving.md "Disaggregated fleet").

The single-replica serving levers are all in place — paged KV with
prefix reuse (``serve/prefix.py``), int8 KV pages (``quant/kv.py``),
the SLO router (``serve/router.py``) — but a FLEET of N decoders is
still dumb: each replica's prefix cache is private, so a shared-prefix
workload sees roughly 1/N the hit rate, and every admission burst runs
its prefill on the same chips that are mid-decode for live streams.
This module is the DistServe/Splitwise-style decomposition built from
the repo's own parts:

- **Prefix-affinity routing** (:class:`FleetRouter`,
  ``BIGDL_SERVE_AFFINITY``): the router sees every request's tokens
  and the prefix chain-hash (``serve/prefix.chain_keys``) is
  deterministic, so admission hashes the seed's page chain and
  dispatches to the replica whose cache holds the LONGEST matching
  chain — recovering near single-replica hit rates on N replicas.  The
  router's view (:class:`AffinityIndex`) is an optimistic LRU mirror
  updated at dispatch (the request's own pages are donated at retire);
  a stale entry costs one replica-local miss, never correctness.  No
  match falls back to least-loaded; EDF deadlines, shed-before-miss
  and requeue-on-replica-death are inherited unchanged from
  :class:`~bigdl_tpu.serve.router.Router`.
- **Prefill/decode disaggregation** (:class:`PrefillReplica`,
  ``BIGDL_SERVE_PREFILL_REPLICAS``): prefill is compute-bound (one
  ``_lm_forward_window`` pass over the seed), decode is HBM/latency
  bound.  Dedicated prefill replicas compute the seed's full KV pages
  (int8 + per-page scales when the fleet runs quantized KV) and ship
  them — over the existing length-prefixed ProcessReplica frames for
  subprocess fleets — to the chosen decode replica, which adopts them
  into its prefix cache (``ContinuousDecoder.adopt_pages``) and admits
  the request at the page-aligned divergence point.  A prefill replica
  dying mid-burst loses ZERO futures: the dispatch falls back to
  colocated prefill (the decode replica computes its own seed KV),
  only the offload is lost.
- **Host-RAM KV tier** (``serve/kvtier.py``,
  ``BIGDL_SERVE_KV_HOST_MB``): each decode replica's evicted prefix
  pages spill D2H and re-admit on chain-hash hit — the per-replica
  effective prefix cache grows by roughly host/HBM.

Shipped, spilled and locally-written pages all hold bit-identical K/V
(the window pass is the same math the decode step runs; quantized
pages ship value+scale verbatim), so the fleet's decoded streams stay
token-identical to single-replica ``lm_decode`` — the parity contract
``tests/test_fleet.py`` pins across shipping, spilling and quantized
pages.

Request payloads are plain dicts ``{"seed": [...], "n_words": n}``
(pickle-friendly across the frame protocol); :class:`DecodeFleet` is
the facade that builds the replicas and the router and exposes
``submit(seed, n_words)``.
"""
from __future__ import annotations

import itertools
import logging
import os
import sys
import threading
import time
from collections import OrderedDict
from concurrent.futures import Future

import numpy as np

from bigdl_tpu.serve import cluster as cluster_ops
from bigdl_tpu.serve.cluster import (ENV_SPAWN_FAIL, DynamicMembership,
                                     ProcessReplica, _read_frame,
                                     _write_frame)
from bigdl_tpu.serve.decode import (DEFAULT_PAGE_SIZE, ENV_PAGE_SIZE,
                                    ContinuousDecoder, _env_int)
from bigdl_tpu.serve.kvtier import HostKVTier, host_mb_default
from bigdl_tpu.serve.prefix import chain_keys
from bigdl_tpu.serve.router import (DeadReplicaError, Router,
                                    replicas_default)
from bigdl_tpu.serve.streaming import StreamFuture

logger = logging.getLogger("bigdl_tpu.serve")

ENV_AFFINITY = "BIGDL_SERVE_AFFINITY"
ENV_PREFILL = "BIGDL_SERVE_PREFILL_REPLICAS"

_FLEET_SEQ = itertools.count()


def affinity_default() -> bool:
    return os.environ.get(ENV_AFFINITY, "1") != "0"


def prefill_replicas_default() -> int:
    try:
        return max(0, int(os.environ.get(ENV_PREFILL, "0")))
    except ValueError:
        return 0


def _page_size_default(decoder_kwargs: dict) -> int:
    ps = decoder_kwargs.get("page_size")
    return max(1, int(ps) if ps is not None
               else _env_int(ENV_PAGE_SIZE, DEFAULT_PAGE_SIZE))


# ---------------------------------------------------------------------------
# the router's optimistic view of each replica's prefix cache
# ---------------------------------------------------------------------------

class AffinityIndex:
    """Replica → LRU set of prefix chain keys the router believes that
    replica's cache holds.

    Optimistic by design: entries are noted at DISPATCH (the request's
    seed pages will be donated to that replica's cache at retire), and
    replica-side eviction is never reported back — a stale entry makes
    one dispatch land on a replica that misses locally (and then
    re-caches), which is exactly the least-loaded baseline's cost.  The
    per-replica LRU bound keeps the mirror a rough shadow of the real
    cache size, so staleness is bounded too."""

    def __init__(self, max_keys: int = 4096):
        self.max_keys = int(max_keys)
        self._lock = threading.Lock()
        self._chains: dict = {}    # name -> OrderedDict(key -> True)

    def note(self, name: str, keys):
        with self._lock:
            d = self._chains.setdefault(name, OrderedDict())
            for k in keys:
                if k in d:
                    d.move_to_end(k)
                else:
                    d[k] = True
            while len(d) > self.max_keys:
                d.popitem(last=False)

    def match_len(self, name: str, keys) -> int:
        """Longest leading run of ``keys`` noted for ``name`` (the
        chain property: page j is only useful if 0..j-1 match too)."""
        with self._lock:
            d = self._chains.get(name)
            if not d:
                return 0
            n = 0
            for k in keys:
                if k not in d:
                    break
                d.move_to_end(k)
                n += 1
            return n

    def forget(self, name: str):
        with self._lock:
            self._chains.pop(name, None)

    def stats(self) -> dict:
        with self._lock:
            return {name: len(d) for name, d in self._chains.items()}


# ---------------------------------------------------------------------------
# decode replicas
# ---------------------------------------------------------------------------

class DecodeReplica:
    """An in-process continuous-batching decode replica: one
    :class:`~bigdl_tpu.serve.decode.ContinuousDecoder` plus a driver
    thread calling ``step_boundary`` whenever work is queued, wearing
    the router's replica surface (``submit/inflight/alive/stats``).

    ``submit`` takes the fleet payload ``{"seed", "n_words"}`` with
    optional shipped prefill ``"pages"`` (adopted into the prefix cache
    before the request queues, so admission sees a prefix hit) and
    never blocks on device work: requests land in a host-side inbox
    the driver drains at each boundary, so a step window mid-flight on
    this replica cannot head-of-line block the router's dispatcher.
    ``host_mb`` > 0 attaches a per-replica host KV tier; with
    ``host_mb=None`` the decoder's own ``BIGDL_SERVE_KV_HOST_MB`` path
    applies (which correctly skips the tier for non-paged decoders)."""

    #: flight-recorder transport attribution (obs/recorder.py)
    transport = "inproc"

    def __init__(self, model, name: str = "decode0",
                 host_mb: int | None = None, host_tier=None,
                 **decoder_kwargs):
        self.name = name
        self._tier_owned = False
        if host_tier is None and host_mb is not None and int(host_mb) > 0:
            host_tier = HostKVTier(int(host_mb), name=f"{name}-tier")
            self._tier_owned = True
        decoder_kwargs.setdefault("prefix_cache", True)
        self.decoder = ContinuousDecoder(
            model, host_tier=host_tier, prefill_adopt=True,
            name=name, **decoder_kwargs)
        self._tier = host_tier
        self._cv = threading.Condition()
        self._inbox: list = []      # (payload dict, proxy future)
        self._closed = False
        self._dead = False
        self._inflight: dict = {}   # id(future) -> proxy (death sweep)
        self._thread = threading.Thread(
            target=self._drive, daemon=True,
            name=f"bigdl-serve-{name}-driver")
        self._thread.start()

    # -- replica surface ----------------------------------------------------
    def submit(self, x, trace=None) -> Future:
        fut = StreamFuture()
        if isinstance(x, dict) and x.get("stream"):
            # stream intent travels in the payload (it can cross a
            # process boundary ahead of the consumer pipe): the driver
            # pipes the decoder's chunks into this proxy from admission
            fut.request_stream()
        with self._cv:
            if self._dead or self._closed:
                raise DeadReplicaError(
                    f"decode replica {self.name} is closed")
            self._inbox.append((x, fut, trace))
            self._inflight[id(fut)] = fut
            self._cv.notify()
        fut.add_done_callback(
            lambda f: self._inflight.pop(id(f), None))
        if trace is not None:
            # one replica-side hop: registered before the router's
            # done-callback, so it lands before the terminal "complete"
            fut.add_done_callback(lambda _f: trace.stamp("compute"))
        return fut

    def inflight(self) -> int:
        with self._cv:
            queued = len(self._inbox)
        return queued + self.decoder.outstanding()

    def alive(self) -> bool:
        return (not self._dead and not self._closed
                and self._thread.is_alive())

    def stats(self) -> dict:
        return {"role": "decode", "name": self.name,
                **self.decoder.stats()}

    def registry_snapshot(self):
        """None: an in-process replica's series already live in this
        process's registry (the ``ReplicaPool`` merge contract)."""
        return None

    # -- driver -------------------------------------------------------------
    def _admit_inbox(self, items):
        """Adopt shipped pages and queue inbox requests on the decoder
        (driver thread only — the decoder is single-threaded state)."""
        for x, fut, trace in items:
            try:
                if x.get("pages"):
                    try:
                        self.decoder.adopt_pages(x["seed"], x["pages"])
                    except Exception:
                        # adoption is an optimization; the request
                        # decodes correctly via colocated prefill
                        logger.warning(
                            "replica %s: shipped-page adoption failed",
                            self.name, exc_info=True)
                inner = self.decoder.submit(x["seed"], x["n_words"],
                                            trace=trace,
                                            sampling=x.get("sampling"))
            except Exception as e:
                if not fut.done():
                    fut.set_exception(e)
                continue
            if fut.streaming:
                # chunks flow decoder → proxy on the decoder's
                # delivery thread, before the result copy below (the
                # delivery FIFO resolves `inner` after its last chunk)
                inner.pipe_to(fut)
            inner.add_done_callback(
                lambda f, proxy=fut: self._copy_result(f, proxy))

    @staticmethod
    def _copy_result(inner, proxy):
        if proxy.done():
            return
        exc = inner.exception()
        if exc is not None:
            proxy.set_exception(exc)
        else:
            proxy.set_result(inner.result())

    def _drive(self):
        while True:
            with self._cv:
                while (not self._closed and not self._dead
                        and not self._inbox
                        and self.decoder.outstanding() == 0):
                    self._cv.wait(timeout=0.05)
                if self._dead or (self._closed and not self._inbox
                                  and self.decoder.outstanding() == 0):
                    return
                items, self._inbox = self._inbox, []
            # device work runs OUTSIDE the lock: submit() stays
            # wait-free while a step window is in flight
            try:
                self._admit_inbox(items)
                self.decoder.step_boundary()
            except Exception as e:  # pragma: no cover - device fault
                self._fail_outstanding(e)
                return

    def _fail_outstanding(self, exc):
        self._dead = True
        err = DeadReplicaError(
            f"decode replica {self.name} driver died: "
            f"{type(exc).__name__}: {exc}")
        logger.warning("decode replica %s driver died", self.name,
                       exc_info=True)
        for fut in list(self._inflight.values()):
            if not fut.done():
                fut.set_exception(err)
        self._inflight.clear()
        self._inbox = []

    def kill(self):
        """Simulated replica death (chaos drills): every outstanding
        future fails with :class:`DeadReplicaError` — the router's
        requeue path takes it from there."""
        with self._cv:
            self._dead = True
            self._fail_outstanding(RuntimeError("killed"))
            self._cv.notify_all()
        self._thread.join(timeout=10.0)

    def close(self, drain: bool = True):
        with self._cv:
            if not drain and not self._dead:
                self._fail_outstanding(RuntimeError("closed undrained"))
            self._closed = True
            self._cv.notify_all()
        self._thread.join(timeout=60.0)
        self.decoder.emit_decode_event()
        self.decoder.close()
        if self._tier is not None and self._tier_owned:
            self._tier.close()


def pages_nbytes(pages) -> int:
    """Wire weight (bytes) of one shipped KV page payload list — the
    numpy buffers only, the measure behind ``fleet_ship_bytes_total``
    (int8 pages carry value+scale and land near 3.2x tokens/byte vs
    float32; bench_serve's ``ship_bytes_per_s`` column reads this)."""
    total = 0
    for page in pages or ():
        for arr in (page if isinstance(page, (tuple, list)) else (page,)):
            nb = getattr(arr, "nbytes", None)
            if nb is not None:
                total += int(nb)
    return total


def _note_ship_bytes(replica: str, transport: str, pages):
    """Count one prefill→decode page shipment's bytes onto
    ``fleet_ship_bytes_total{transport,replica}``."""
    if not pages:
        return
    try:
        from bigdl_tpu.obs import metrics as obs_metrics
        obs_metrics.get().counter(
            "fleet_ship_bytes_total",
            "KV page payload bytes shipped prefill→decode, by wire",
            transport=transport, replica=replica,
        ).inc(pages_nbytes(pages))
    except Exception:   # pragma: no cover - registry mid-teardown
        pass


class ProcessDecodeReplica(ProcessReplica):
    """A decode replica in its own OS process (its own jax runtime /
    chip slice), speaking the cluster frame protocol with a fleet
    worker (:func:`fleet_main`).  Shipped prefill pages ride the submit
    frame as plain numpy payloads; death fails outstanding futures with
    :class:`DeadReplicaError` exactly like the engine replicas."""

    _WORKER_MODULE = "bigdl_tpu.serve.fleet"

    def _init_frame(self, model, worker_kwargs) -> dict:
        return {"op": "init", "role": "decode", "model": model,
                "decoder": worker_kwargs}

    def submit(self, x, trace=None) -> Future:
        _note_ship_bytes(self.name, "stdio", x.get("pages"))
        return self._send(
            "submit", _trace=trace,
            seed=[int(t) for t in x["seed"]],
            n_words=int(x["n_words"]), pages=x.get("pages"),
            stream=bool(x.get("stream")),
            sampling=x.get("sampling"),
            trace=None if trace is None else trace.to_wire())


# ---------------------------------------------------------------------------
# prefill replicas
# ---------------------------------------------------------------------------

class PrefillReplica:
    """A dedicated prefill worker: one compiled
    ``_lm_forward_window`` pass over the seed per pow2 page-count
    bucket, returning the seed's full KV pages as host payloads the
    decode replicas adopt.

    Only pages every position of which lies strictly inside the seed
    are shippable — ``(len(seed) - 1) // page_size``, the same cap as a
    prefix-cache match (the last seed position is re-fed on the decode
    replica for the first logits).  Seeds longer than
    ``max_seed_pages * page_size`` ship their leading chain and the
    decode replica prefills the rest colocated.  ``kv_quant`` must
    match the decode replicas' pools (int8 pages ship value+scale
    verbatim — bit-identical adoption)."""

    def __init__(self, model, name: str = "prefill0",
                 page_size: int | None = None, max_seed_pages: int = 8,
                 kv_quant: str | None = None):
        import jax.numpy as jnp

        from bigdl_tpu.models.transformer import (_lm_forward_window,
                                                  _lm_handles)
        from bigdl_tpu.optim.local_optimizer import _model_fingerprint
        from bigdl_tpu.quant import kv as kvq
        from bigdl_tpu.quant import kv_mode_default, normalize_mode
        from bigdl_tpu.serve import xcache

        self.name = name
        self.page_size = (max(1, int(page_size)) if page_size is not None
                          else _env_int(ENV_PAGE_SIZE, DEFAULT_PAGE_SIZE))
        self.kv_quant = (kv_mode_default() if kv_quant is None
                         else normalize_mode(kv_quant, kvq.ON_MODES,
                                             "kv_quant"))
        self._closed = False
        self._inflight = 0
        self._lock = threading.Lock()
        self.prefills = 0        # this replica's lifetime (stats());
        self.pages_shipped = 0   # the registry counters merge fleetwide
        h = _lm_handles(model)
        L, H, hd = h.n_layers, h.n_heads, h.hd
        ps = self.page_size
        self.buckets = []
        b = 1
        while b <= max(1, int(max_seed_pages)):
            self.buckets.append(b)
            b *= 2
        self.max_pages = self.buckets[-1]
        pe = jnp.asarray(model.modules[1].table(self.max_pages * ps))
        fp = _model_fingerprint(model)
        quant = self.kv_quant == "int8"

        def make(npages):
            S = npages * ps
            ptab = jnp.arange(npages, dtype=jnp.int32)[None, :]
            pos = jnp.arange(S, dtype=jnp.int32)[None, :]

            def prefill_fn(seed_row, valid):
                z = jnp.zeros
                shape = (L, npages, ps, H, hd)
                if quant:
                    ss = kvq.scale_shape(shape)
                    caches = (z(shape, jnp.int8), z(shape, jnp.int8),
                              z(ss, jnp.float32), z(ss, jnp.float32))
                else:
                    caches = (z(shape, jnp.float32),
                              z(shape, jnp.float32))
                _, caches = _lm_forward_window(
                    seed_row, pos, caches, h, pe, (ptab, ps),
                    valid=valid)
                return caches

            return xcache.tracked_jit(
                prefill_fn,
                ("fleet_prefill", fp, npages, ps, self.kv_quant))

        self._progs = {b: make(b) for b in self.buckets}

        from bigdl_tpu.obs import metrics as obs_metrics
        reg = obs_metrics.get()
        lab = {"replica": self.name}
        self._m_reqs = reg.counter(
            "fleet_prefill_requests_total",
            "seeds prefilled on a dedicated prefill replica", **lab)
        self._m_pages = reg.counter(
            "fleet_prefill_pages_total",
            "KV pages computed and shipped by prefill replicas", **lab)
        self._m_lat = reg.histogram(
            "fleet_prefill_seconds", "seed prefill wall time", **lab)
        # uniquely-labelled, possibly short-lived: drop the series at
        # close/GC (the decoder/tier precedent); held handles keep
        # serving stats() after the drop
        import weakref
        self._drop_series = weakref.finalize(
            self, reg.drop_series, replica=self.name)

        # warm every bucket at construction: the prefill path inherits
        # the serving zero-cold-compile property
        for b in self.buckets:
            row = np.zeros((1, b * ps), np.int32)
            valid = np.zeros((1, b * ps), bool)
            np.asarray(self._progs[b](row, valid)[0])

        self._pool = None   # lazy single-thread executor for async calls

    # -- prefill ------------------------------------------------------------
    def prefill(self, seed) -> list:
        """The shippable KV pages for ``seed``: a list of per-page
        payload tuples (the decoder's per-array page slices), computed
        with the SAME window math the decode step runs — adoption is
        bit-identical to local prefill."""
        t0 = time.perf_counter()
        ps = self.page_size
        n_ship = min(max(0, (len(seed) - 1) // ps), self.max_pages)
        if n_ship == 0:
            return []
        bucket = next(b for b in self.buckets if b >= n_ship)
        n_tok = n_ship * ps
        row = np.zeros((1, bucket * ps), np.int32)
        row[0, :n_tok] = np.asarray(seed[:n_tok], np.int32)
        valid = np.zeros((1, bucket * ps), bool)
        valid[0, :n_tok] = True
        caches = self._progs[bucket](row, valid)
        host = [np.asarray(c) for c in caches]
        pages = [tuple(a[:, j] for a in host) for j in range(n_ship)]
        with self._lock:
            self.prefills += 1
            self.pages_shipped += len(pages)
        self._m_reqs.inc()
        self._m_pages.inc(len(pages))
        self._m_lat.observe(time.perf_counter() - t0)
        return pages

    def prefill_async(self, seed) -> Future:
        """``prefill`` on this replica's own worker thread — the
        router's dispatch loop must not block on a window pass."""
        from concurrent.futures import ThreadPoolExecutor
        with self._lock:
            if self._closed:
                raise DeadReplicaError(
                    f"prefill replica {self.name} is closed")
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=1,
                    thread_name_prefix=f"bigdl-serve-{self.name}")
            self._inflight += 1
        fut = self._pool.submit(self.prefill, seed)
        fut.add_done_callback(lambda _f: self._dec())
        return fut

    def _dec(self):
        with self._lock:
            self._inflight -= 1

    # -- replica surface ----------------------------------------------------
    def inflight(self) -> int:
        with self._lock:
            return self._inflight

    def alive(self) -> bool:
        return not self._closed

    def stats(self) -> dict:
        return {"role": "prefill", "name": self.name,
                "page_size": self.page_size, "kv_quant": self.kv_quant,
                "buckets": list(self.buckets),
                "prefills": self.prefills,
                "pages_shipped": self.pages_shipped}

    def registry_snapshot(self):
        return None

    def close(self, drain: bool = True):
        with self._lock:
            self._closed = True
            pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=drain)
        self._drop_series()


class ProcessPrefillReplica(ProcessReplica):
    """A prefill replica in its own OS process; ``prefill_async`` rides
    the frame protocol and resolves to the page payload list.  Death
    fails in-flight prefills with :class:`DeadReplicaError`, which the
    fleet router converts into colocated prefill — never a lost
    request."""

    _WORKER_MODULE = "bigdl_tpu.serve.fleet"

    def _init_frame(self, model, worker_kwargs) -> dict:
        return {"op": "init", "role": "prefill", "model": model,
                "prefill": worker_kwargs}

    def prefill_async(self, seed) -> Future:
        return self._send("prefill", seed=[int(t) for t in seed])

    def prefill(self, seed, timeout: float = 120.0) -> list:
        return self.prefill_async(seed).result(timeout=timeout)


# ---------------------------------------------------------------------------
# the affinity router
# ---------------------------------------------------------------------------

class FleetRouter(Router):
    """:class:`~bigdl_tpu.serve.router.Router` with prefix-affinity
    dispatch and the prefill-replica hop.

    ``_pick_for``: hash the request seed's page chain and prefer the
    live replica whose :class:`AffinityIndex` mirror holds the longest
    matching run (``fleet_affinity_hits_total``); no match falls back
    to least-loaded (``fleet_affinity_misses_total``).  ``_submit_to``:
    when prefill replicas are configured and the seed spans at least
    one full page, the seed's KV pages are computed on a prefill
    replica and shipped with the request; ANY prefill failure (death
    included) falls back to colocated prefill on the decode replica —
    the request itself is never lost, and decode-replica death still
    rides the base requeue-once idempotence machinery."""

    def __init__(self, replicas, prefill=None, affinity: bool | None = None,
                 page_size: int | None = None, index_keys: int = 4096,
                 affinity_max_skew: int = 8, **router_kwargs):
        self.page_size = (max(1, int(page_size)) if page_size is not None
                          else _env_int(ENV_PAGE_SIZE, DEFAULT_PAGE_SIZE))
        self.affinity_enabled = (affinity_default() if affinity is None
                                 else bool(affinity))
        #: load guard: an affinity pick whose backlog exceeds the
        #: least-loaded replica's by more than this many requests is
        #: overridden — a hot prefix family (steep Zipf) must not
        #: funnel onto one replica while the rest idle; re-caching the
        #: chain on a second replica costs one miss, a deadline shed
        #: costs the request
        self.affinity_max_skew = max(0, int(affinity_max_skew))
        self.index = AffinityIndex(max_keys=index_keys)
        self.prefill_replicas = list(prefill or [])
        self._prefill_dead: set = set()
        self._aff_counters: dict = {}
        super().__init__(replicas, **router_kwargs)
        from bigdl_tpu.obs import metrics as obs_metrics
        reg = obs_metrics.get()
        for r in self.replicas:
            reg.gauge("serve_replica_role", "replica role (1 = present)",
                      role="decode", replica=getattr(r, "name", "?"),
                      router=self.name).set(1)
        for p in self.prefill_replicas:
            reg.gauge("serve_replica_role", "replica role (1 = present)",
                      role="prefill", replica=getattr(p, "name", "?"),
                      router=self.name).set(1)
        self._m_ship = reg.counter(
            "fleet_prefill_shipped_total",
            "requests dispatched with prefill-replica pages",
            router=self.name)
        self._m_fallback = reg.counter(
            "fleet_prefill_fallback_total",
            "requests served via colocated prefill after a prefill "
            "miss/failure", router=self.name)
        self._m_skip = reg.counter(
            "fleet_prefill_skipped_total",
            "prefill hops skipped because the affinity pick already "
            "caches the chain", router=self.name)

    # -- affinity dispatch --------------------------------------------------
    def _aff_counter(self, replica_name: str, outcome: str):
        key = (replica_name, outcome)
        with self._lock:
            c = self._aff_counters.get(key)
        if c is None:
            from bigdl_tpu.obs import metrics as obs_metrics
            c = obs_metrics.get().counter(
                f"fleet_affinity_{outcome}_total",
                "affinity dispatch outcomes per decode replica",
                replica=replica_name, router=self.name)
            with self._lock:
                c = self._aff_counters.setdefault(key, c)
        return c

    def _seed_keys(self, req) -> list:
        x = req.x
        seed = x.get("seed") if isinstance(x, dict) else None
        if not seed:
            return []
        n = max(0, (len(seed) - 1) // self.page_size)
        return list(chain_keys(seed, n, self.page_size))

    def _pick_for(self, req):
        if not self.affinity_enabled:
            return self._pick()
        keys = self._seed_keys(req)
        best, best_match = None, 0
        if keys:
            # drain-marked replicas are not affinity candidates: a
            # scale-down victim only finishes what it already holds
            for r in self.live_replicas(draining=False):
                m = self.index.match_len(getattr(r, "name", ""), keys)
                if m > best_match:
                    best, best_match = r, m
        load = 0
        if best is not None:
            try:
                if not best.alive():
                    raise RuntimeError("replica died")
                load = best.inflight()
            except Exception:
                self._mark_dead(best)
                best = None
        if best is not None:
            with self._lock:
                load += len(self._outstanding.get(id(best), {}))
            # load guard: never let a hot family starve idle replicas
            ll_replica, ll_load = self._pick()
            if (ll_replica is not None and ll_replica is not best
                    and load > ll_load + self.affinity_max_skew):
                best = None
        if best is None:
            replica, load = self._pick()
            if replica is not None and keys:
                # bookkeeping is DEFERRED to dispatch (_submit_to): a
                # request shed before dispatch must not inflate the
                # miss count or seed the index with undonated chains
                req.affinity = 0
                req.aff_note = (getattr(replica, "name", "?"), keys,
                                "misses")
            return replica, load
        name = getattr(best, "name", "?")
        req.affinity = best_match
        req.aff_note = (name, keys, "hits")
        return best, load

    def _consume_aff_note(self, req):
        note, req.aff_note = req.aff_note, None
        if note:
            name, keys, outcome = note
            self._aff_counter(name, outcome).inc()
            self.index.note(name, keys)
            if req.trace is not None:
                from bigdl_tpu.obs import recorder as obs_recorder
                obs_recorder.note(req.trace.trace_id,
                                  affinity=outcome,
                                  affinity_pages=req.affinity)

    def _mark_dead(self, replica):
        self.index.forget(getattr(replica, "name", ""))
        super()._mark_dead(replica)

    def _role_gauge(self, replica, present: bool, role: str = "decode"):
        from bigdl_tpu.obs import metrics as obs_metrics
        obs_metrics.get().gauge(
            "serve_replica_role", "replica role (1 = present)",
            role=role, replica=getattr(replica, "name", "?"),
            router=self.name).set(1 if present else 0)

    def add_replica(self, replica):
        super().add_replica(replica)
        self._role_gauge(replica, True)
        return replica

    def remove_replica(self, replica):
        super().remove_replica(replica)
        self.index.forget(getattr(replica, "name", ""))
        # drop the role series entirely (not just zero it): serve_top
        # derives the replica set from the series LABELS, and a fleet
        # under autoscale churn would otherwise accumulate one stale
        # series per ever-lived replica
        try:
            from bigdl_tpu.obs import metrics as obs_metrics
            obs_metrics.get().drop_series(
                replica=getattr(replica, "name", "?"), role="decode",
                router=self.name)
        except Exception:   # pragma: no cover - registry mid-teardown
            pass

    # -- the prefill hop ----------------------------------------------------
    def _pick_prefill(self):
        best, best_load = None, None
        for p in self.prefill_replicas:
            if id(p) in self._prefill_dead:
                continue
            try:
                if not p.alive():
                    self._mark_prefill_dead(p)
                    continue
                load = p.inflight()
            except Exception:
                self._mark_prefill_dead(p)
                continue
            if best_load is None or load < best_load:
                best, best_load = p, load
        return best

    def _mark_prefill_dead(self, replica):
        with self._lock:
            if id(replica) in self._prefill_dead:
                return
            self._prefill_dead.add(id(replica))
        name = getattr(replica, "name", repr(replica))
        logger.warning("serve fleet: prefill replica %s marked dead; "
                       "falling back to colocated prefill", name)
        self._emit("replica_dead", replica=name, role="prefill")

    @staticmethod
    def _note_prefill(req, outcome: str, pages: int | None = None):
        """Prefill-ship attribution on the request's flight record."""
        if req.trace is not None:
            from bigdl_tpu.obs import recorder as obs_recorder
            obs_recorder.note(req.trace.trace_id, prefill=outcome,
                              shipped_pages=pages)

    def _submit_direct(self, replica, req, x):
        if req.trace is not None and self._accepts_trace(replica):
            return replica.submit(x, trace=req.trace)
        return replica.submit(x)

    def _submit_to(self, replica, req):
        # past the shed check now — commit the affinity bookkeeping
        self._consume_aff_note(req)
        x = req.x
        if (not self.prefill_replicas or not isinstance(x, dict)
                or x.get("pages") is not None
                or (len(x.get("seed") or []) - 1) // self.page_size < 1):
            return super()._submit_to(replica, req)
        n_ship = (len(x["seed"]) - 1) // self.page_size
        if req.affinity is not None and req.affinity >= n_ship:
            # the affinity pick predicts the replica already caches the
            # whole shippable chain — the prefill hop would recompute
            # pages the admission will match locally.  Affinity does
            # not just route better, it SHEDS prefill work.
            self._m_skip.inc()
            self._note_prefill(req, "skipped")
            return super()._submit_to(replica, req)
        pf = self._pick_prefill()
        if pf is None:
            self._m_fallback.inc()
            self._note_prefill(req, "fallback")
            return super()._submit_to(replica, req)

        outer = StreamFuture()
        if req.future.streaming:
            # mark intent NOW: the async prefill hop may land (and
            # pipe the replica chunks in) before the base router
            # registers its outer→client pipe — the backlog replays to
            # that late registration, so no chunk is lost either way
            outer.request_stream()

        def land(pages):
            x2 = dict(x)
            if pages:
                x2["pages"] = pages
                self._m_ship.inc()
                self._note_prefill(req, "shipped", len(pages))
            else:
                self._m_fallback.inc()
                self._note_prefill(req, "fallback")
            try:
                inner = self._submit_direct(replica, req, x2)
            except Exception as e:
                outer.set_exception(e)
                return
            if outer.streaming and hasattr(inner, "pipe_to"):
                # the base router pipes from `outer`; chain the replica
                # chunks through it (index-preserving)
                inner.pipe_to(outer)
            inner.add_done_callback(_copy)

        def _copy(inner):
            exc = inner.exception()
            if exc is not None:
                outer.set_exception(exc)
            else:
                outer.set_result(inner.result())

        def on_prefill(f):
            pages = None
            try:
                pages = f.result()
            except Exception as e:
                # the prefill hop is best-effort: ANY failure (replica
                # death included) serves via colocated prefill — the
                # future is never lost to the offload
                if isinstance(e, DeadReplicaError):
                    self._mark_prefill_dead(pf)
                else:
                    logger.warning("prefill on %s failed; colocated "
                                   "prefill serves the request: %s",
                                   getattr(pf, "name", pf), e)
            land(pages)

        try:
            pfut = pf.prefill_async(x["seed"])
        except Exception:
            self._mark_prefill_dead(pf)
            self._m_fallback.inc()
            return super()._submit_to(replica, req)
        pfut.add_done_callback(on_prefill)
        return outer

    # -- telemetry ----------------------------------------------------------
    def stats(self) -> dict:
        out = super().stats()
        with self._lock:   # the dispatcher inserts counters lazily
            counters = list(self._aff_counters.items())
        hits = sum(int(c.value) for (_, o), c in counters
                   if o == "hits")
        misses = sum(int(c.value) for (_, o), c in counters
                     if o == "misses")
        out.update(affinity=self.affinity_enabled,
                   affinity_hits=hits, affinity_misses=misses,
                   prefill_replicas=len(self.prefill_replicas),
                   prefill_shipped=int(self._m_ship.value),
                   prefill_fallback=int(self._m_fallback.value),
                   prefill_skipped=int(self._m_skip.value),
                   index=self.index.stats())
        return out


# ---------------------------------------------------------------------------
# the fleet facade
# ---------------------------------------------------------------------------

class DecodeFleet(DynamicMembership):
    """N decode replicas (+ optional prefill replicas) behind one
    :class:`FleetRouter` — the disaggregated-serving entry point.

    ``DecodeFleet(model, n_decode=2, n_prefill=1)`` builds in-process
    replicas; ``process=True`` spawns each as its own OS process over
    the cluster frame protocol.  ``replicas=`` / ``prefill=`` inject
    pre-built replicas (tests, heterogeneous fleets, per-replica chaos
    env).  Requests flow ``fleet.submit(seed, n_words, priority=,
    slo_ms=)`` → affinity/least-loaded dispatch → (optional prefill
    hop) → decode replica; every admission/SLO/requeue guarantee is the
    base router's.

    Knobs: ``BIGDL_SERVE_REPLICAS`` (decode count default),
    ``BIGDL_SERVE_PREFILL_REPLICAS``, ``BIGDL_SERVE_AFFINITY``,
    ``BIGDL_SERVE_KV_HOST_MB`` (per-replica host tier) plus every
    decoder knob (page size, spec-k, KV quant...)."""

    def __init__(self, model=None, n_decode: int | None = None,
                 n_prefill: int | None = None, process: bool = False,
                 replicas=None, prefill=None,
                 affinity: bool | None = None, host_mb: int | None = None,
                 slo_ms: float | None = None, shed: bool | None = None,
                 est_ms: float = 50.0, trace_sample: float | None = None,
                 max_seed_pages: int = 8, decode_env=None,
                 prefill_env=None, name: str | None = None,
                 replica_factory=None, remote: bool | None = None,
                 hosts=None, token=None, **decoder_kwargs):
        ps = _page_size_default(decoder_kwargs)
        decoder_kwargs["page_size"] = ps
        kv_quant = decoder_kwargs.get("kv_quant")
        self.name = name or f"fleet{next(_FLEET_SEQ)}"
        self._model = model
        self._process = bool(process)
        self._decoder_kwargs = dict(decoder_kwargs)
        self._host_mb = host_mb
        self._decode_env = decode_env
        self._replica_factory = replica_factory
        # cross-host decode fleet: lease replica-agent addresses instead
        # of spawning local children (docs/serving.md "Cross-host
        # fleet"); prefill replicas stay local — pages ship to the
        # remote decoders over TCP (fleet_ship_bytes_total{transport})
        self._inventory = None
        if remote or (remote is None and hosts is not None):
            from bigdl_tpu.serve import remote as remote_mod
            self._inventory = remote_mod.HostInventory(hosts, token=token)
        self._scale_lock = threading.RLock()
        self._warming = 0
        self._next_decode = 0
        self._placed = itertools.count()   # in-process replicas built
        if replicas is None:
            if model is None and replica_factory is None:
                raise ValueError("DecodeFleet needs a model, replicas, "
                                 "or a replica_factory")
            n = (replicas_default() if n_decode is None
                 else max(1, int(n_decode)))
            replicas = []
            try:
                for _ in range(n):
                    replicas.append(
                        self._spawn_replica(self._next_name()))
            except Exception:
                # one bad replica fails construction cleanly: close the
                # good ones, leak no subprocess (the ReplicaPool /
                # ReplicaSpawnError contract)
                for r in replicas:
                    try:
                        r.close(drain=False)
                    except Exception:   # pragma: no cover - teardown
                        pass
                raise
        self.replicas = list(replicas)
        self._next_decode = max(self._next_decode, len(self.replicas))
        if prefill is None:
            m = (prefill_replicas_default() if n_prefill is None
                 else max(0, int(n_prefill)))
            if m and model is None:
                raise ValueError("prefill replicas need the model")
            if process:
                prefill = [
                    ProcessPrefillReplica(
                        model, name=f"prefill{i}", env=prefill_env,
                        page_size=ps, max_seed_pages=max_seed_pages,
                        kv_quant=kv_quant)
                    for i in range(m)]
            else:
                prefill = [
                    PrefillReplica(model, name=f"prefill{i}",
                                   page_size=ps,
                                   max_seed_pages=max_seed_pages,
                                   kv_quant=kv_quant)
                    for i in range(m)]
        self.prefill_replicas = list(prefill)
        self.router = FleetRouter(
            self.replicas, prefill=self.prefill_replicas,
            affinity=affinity, page_size=ps, slo_ms=slo_ms, shed=shed,
            est_ms=est_ms, trace_sample=trace_sample)
        self._init_membership()
        from bigdl_tpu.obs import events
        events.emit("serve", kind="fleet_start",
                    replicas=len(self.replicas),
                    prefill_replicas=len(self.prefill_replicas),
                    affinity=self.router.affinity_enabled,
                    page_size=ps)
        from bigdl_tpu.serve import autoscale as autoscale_mod
        if autoscale_mod.autoscale_default():
            self.start_autoscaler()

    # -- dynamic membership (docs/serving.md "Autoscaling") -----------------
    def _next_name(self) -> str:
        n = self._next_decode
        self._next_decode += 1
        return f"decode{n}"

    def _spawn_replica(self, name: str, env=None):
        """Build one decode replica the way this fleet was configured
        (``replica_factory`` > remote lease > subprocess > in-process).
        Construction IS the warmup: the decoder pre-compiles its
        step/admit/retire programs through the xcache (an identical
        configuration costs zero new compiles) before the router may
        dispatch to it."""
        if self._replica_factory is not None:
            return self._replica_factory(name)
        if self._model is None:
            raise RuntimeError(
                "dynamic membership needs the fleet's model (this "
                "fleet was built from pre-built replicas; pass "
                "replica_factory= to scale it)")
        if self._inventory is not None:
            from bigdl_tpu.serve import remote as remote_mod
            addr = self._inventory.lease()
            try:
                return remote_mod.RemoteDecodeReplica(
                    addr, self._model, name=name,
                    token=self._inventory.token,
                    on_release=self._inventory.release,
                    host_mb=self._host_mb, **self._decoder_kwargs)
            except Exception:
                self._inventory.release(addr)
                raise
        if self._process:
            return ProcessDecodeReplica(
                self._model, name=name,
                env=env if env is not None else self._decode_env,
                host_mb=self._host_mb, **self._decoder_kwargs)
        kw = dict(self._decoder_kwargs)
        if kw.get("mesh") is None and "device" not in kw:
            kw["device"] = cluster_ops.next_local_device(self._placed)
            logger.info("decode replica %s on %s", name, kw["device"])
        return DecodeReplica(self._model, name=name,
                             host_mb=self._host_mb, **kw)

    # membership()/_update_membership()/remove_replica()/
    # start_autoscaler() come from DynamicMembership — only the decode
    # replicas scale (prefill replicas are not autoscaled)

    def add_replica(self, name: str | None = None,
                    reason: str = "manual", env=None):
        """Spawn and warm one decode replica, then register it with the
        affinity router (``scale``/``up`` event; the ReplicaPool
        contract — decode replicas carry no weight versions, so warmup
        is the construction compile pass alone)."""
        from bigdl_tpu.obs import events
        with self._scale_lock:
            if name is None:
                name = self._next_name()
            self._warming += 1
        self._update_membership()
        try:
            replica = self._spawn_replica(name, env=env)
        except Exception:
            with self._scale_lock:
                self._warming -= 1
            self._update_membership()
            raise
        with self._scale_lock:
            self.replicas.append(replica)
            self.router.add_replica(replica)
            self._warming -= 1
        self._update_membership()
        self._m_scale["up"].inc()
        events.emit("scale", kind="up", replica=name, reason=reason,
                    replicas=len(self.replicas))
        return replica

    # -- request path -------------------------------------------------------
    def submit(self, seed, n_words: int, priority: int = 1,
               slo_ms: float | None = None, ttft_ms: float | None = None,
               on_tokens=None, stream: bool = False,
               sampling=None) -> Future:
        """One decode request through the fleet.  ``on_tokens`` (or
        ``stream=True``) turns on incremental token delivery: chunks
        flow decode replica → router → the returned
        :class:`~bigdl_tpu.serve.streaming.StreamFuture` (across the
        frame protocol for subprocess replicas), byte-identical to the
        resolved row's tail, and the request joins the per-token SLO
        class (``ttft_ms`` / ``BIGDL_SERVE_SLO_TTFT_MS``).

        ``sampling`` (:class:`~bigdl_tpu.serve.sampling.SamplingParams`
        or its dict form) rides the request payload: the PRNG seed is
        RESOLVED here — before the payload can be requeued after a
        replica death — so re-delivery redraws the exact same token
        stream."""
        x = {"seed": [int(t) for t in seed], "n_words": int(n_words)}
        if stream or on_tokens is not None:
            x["stream"] = True
        if sampling is not None:
            from bigdl_tpu.serve.sampling import SamplingParams
            params = SamplingParams.of(sampling).resolved()
            if not params.is_default:
                x["sampling"] = params.to_dict()
        return self.router.submit(x, priority=priority, slo_ms=slo_ms,
                                  ttft_ms=ttft_ms, on_tokens=on_tokens)

    def submit_many(self, seeds, n_words: int, priority: int = 1,
                    slo_ms: float | None = None) -> list:
        return [self.submit(s, n_words, priority=priority, slo_ms=slo_ms)
                for s in seeds]

    # -- telemetry ----------------------------------------------------------
    def merged_registry(self) -> dict:
        """One snapshot covering the whole fleet (the ``ReplicaPool``
        merge contract: this process's registry + every subprocess
        replica's snapshot)."""
        from bigdl_tpu.obs import metrics as obs_metrics
        snaps = [obs_metrics.get().snapshot()]
        for r in list(self.replicas) + list(self.prefill_replicas):
            try:
                snap = r.registry_snapshot()
                if snap:
                    snaps.append(snap)
            except Exception:  # pragma: no cover - racing a death
                logger.warning("telemetry pull failed for replica %s",
                               getattr(r, "name", r))
        return obs_metrics.merge(snaps)

    def stats(self) -> dict:
        out = {"router": self.router.stats(), "replicas": []}
        for r in list(self.replicas) + list(self.prefill_replicas):
            entry = {"name": getattr(r, "name", repr(r)),
                     "role": "prefill" if r in self.prefill_replicas
                     else "decode", "alive": False}
            try:
                entry["alive"] = r.alive()
                if entry["alive"]:
                    entry.update(r.stats())
            except Exception:  # pragma: no cover - racing a death
                pass
            out["replicas"].append(entry)
        return out

    # -- lifecycle ----------------------------------------------------------
    def drain(self, timeout: float = 120.0):
        self.router.drain(timeout)
        return self

    def close(self, drain: bool = True):
        if self.autoscaler is not None:
            self.autoscaler.close()
            self.autoscaler = None
        if drain:
            try:
                self.router.drain()
            except TimeoutError:  # pragma: no cover - shutdown path
                pass
        rstats = self.router.stats()
        self.router.close()
        for r in list(self.replicas) + list(self.prefill_replicas):
            try:
                r.close(drain=drain)
            except Exception:  # pragma: no cover
                pass
        from bigdl_tpu.obs import events
        events.emit("serve", kind="fleet_stop",
                    replicas=len(self.replicas),
                    prefill_replicas=len(self.prefill_replicas),
                    affinity_hits=rstats.get("affinity_hits", 0),
                    affinity_misses=rstats.get("affinity_misses", 0),
                    prefill_shipped=rstats.get("prefill_shipped", 0),
                    prefill_fallback=rstats.get("prefill_fallback", 0))
        try:
            from bigdl_tpu.obs import metrics as obs_metrics
            obs_metrics.get().drop_series(pool=self.name)
        except Exception:   # pragma: no cover - registry mid-teardown
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------------------
# subprocess fleet worker
# ---------------------------------------------------------------------------

class DecodeOps(cluster_ops.WorkerOps):
    """Fleet decode-worker ops: ``submit`` with optional shipped pages
    and incremental token frames (each chunk crosses the wire with its
    absolute start index, so the parent-side StreamFuture dedup holds
    across the process/TCP hop)."""

    role = "decode"

    def __init__(self, init, send):
        super().__init__(send)
        self.target = DecodeReplica(init["model"],
                                    **init.get("decoder", {}))

    def _handle_role(self, op, rid, msg) -> bool:
        if op != "submit":
            return super()._handle_role(op, rid, msg)
        self._chaos_kill()
        from bigdl_tpu.obs import trace as obs_trace
        x = {"seed": msg["seed"], "n_words": msg["n_words"]}
        if msg.get("pages"):
            x["pages"] = msg["pages"]
        if msg.get("stream"):
            x["stream"] = True
        if msg.get("sampling"):
            x["sampling"] = msg["sampling"]
        tr = (obs_trace.Trace.from_wire(msg["trace"])
              if msg.get("trace") else None)
        fut = self.target.submit(x, trace=tr)
        if msg.get("stream"):
            fut.on_tokens_indexed(
                lambda toks, start, r=rid: self.send(
                    {"op": "tokens", "id": r, "tokens": toks,
                     "start": start}))
        fut.add_done_callback(
            lambda f, r=rid, t=tr: self._reply(r, f, t))
        return True


class PrefillOps(cluster_ops.WorkerOps):
    """Fleet prefill-worker ops: ``prefill`` resolving to the seed's
    shippable KV page payloads."""

    role = "prefill"

    def __init__(self, init, send):
        super().__init__(send)
        self.target = PrefillReplica(init["model"],
                                     **init.get("prefill", {}))

    def _handle_role(self, op, rid, msg) -> bool:
        if op != "prefill":
            return super()._handle_role(op, rid, msg)
        self._chaos_kill()
        fut = self.target.prefill_async(msg["seed"])
        fut.add_done_callback(lambda f, r=rid: self._reply(r, f))
        return True


def build_fleet_ops(init, send):
    """The fleet-role dispatcher behind
    :func:`bigdl_tpu.serve.cluster.build_worker_ops` — decode and
    prefill workers share the base op set with the engine workers."""
    role = init.get("role")
    if role == "decode":
        return DecodeOps(init, send)
    if role == "prefill":
        return PrefillOps(init, send)
    raise ValueError(f"unknown fleet worker role {init.get('role')!r}")


def fleet_main(stdin=None, stdout=None):
    """Entry point of a fleet ProcessReplica child: host one decode or
    prefill replica (the init frame's ``role``) and answer frames until
    EOF/close — :func:`bigdl_tpu.serve.cluster.worker_main` with the
    fleet ops (:class:`DecodeOps` / :class:`PrefillOps`).

    ``BIGDL_FAULTS=serve_kill@at=N`` kills this process at the Nth
    submitted request / prefill — the chaos site behind the fleet
    drill's prefill-death and decode-requeue assertions."""
    return cluster_ops.worker_main(stdin, stdout)


if __name__ == "__main__":
    sys.exit(fleet_main())

"""Shared executable cache — one compile registry for every entry point
(docs/serving.md "Control plane", docs/performance.md).

PR 5 put the ahead-of-time ``jit(fwd).lower(...).compile()`` ladder
inside the ServeEngine, so only the serving path had the
zero-cold-compile property; the validators' pad-and-trim trick and the
train loop's jit cache were separate mechanisms with separate
accounting.  This module lifts that cache out into ONE process-wide
registry keyed by::

    (fn_key, leaf shapes/dtypes, mesh fingerprint, dtype-policy)

so that train dispatch, ``optim.validate`` and every serve replica ride
the same entries:

- ``optim.local_optimizer._eval_fn`` wraps its jitted forward in
  :class:`ShapedCallable` — each distinct batch shape resolves to one
  AOT-compiled executable here;
- ``ServeEngine.warmup`` asks this cache for each bucket's executable
  with the SAME ``fn_key`` (the model fingerprint), so a process that
  validates AND serves a common (model, shape) pair compiles it exactly
  once — the compile-counter audit ``tests/test_serve_cluster.py``
  holds both to;
- the train-step builders (``LocalOptimizer``/``DistriOptimizer``)
  register their jit dispatches through :func:`tracked_jit`, which
  keys on the batch operands only (a model-sized pytree walk per step
  would be host overhead the async pipeline just removed).

Two registration modes, one key space:

- **AOT** (:meth:`ExecutableCache.get_or_compile`): lower-and-compile
  now, return the executable; a later request for the same key gets
  the cached executable — zero new XLA work.
- **tracked jit** (:func:`tracked_jit`): the function stays a normal
  ``jax.jit`` dispatch (donation, sharding and weak-type semantics
  untouched — the train step donates its carried state), but the first
  dispatch of each key is recorded as a compile so ``stats()`` is a
  process-truthful compile counter across ALL entry points.

The cache never evicts (an executable is a few MB of device code; a
serving process wants them all resident); :func:`reset` exists for
tests and is wired into the suite's autouse fixture.
"""
from __future__ import annotations

import threading

import numpy as np

#: process-wide singleton (identity is stable across :func:`reset` so
#: closures built by ``tracked_jit``/``ShapedCallable`` never go stale)
_CACHE = None
_LOCK = threading.Lock()


def _policy_key():
    """Dtype-policy component of a cache key: the policy's three dtypes
    (stable across policy object identities)."""
    try:
        from bigdl_tpu import tensor as bt
        p = bt.policy()
        return (str(p.param_dtype), str(p.compute_dtype),
                str(p.output_dtype))
    except Exception:  # pragma: no cover - tensor layer absent
        return None


def _mesh_key(mesh):
    """Mesh component of a cache key: axis names/sizes + device ids (two
    meshes over different devices must not share executables)."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


def _leaf_sharding(leaf, default_device):
    """Sharding component of one leaf's key: None for host numpy,
    ShapeDtypeStructs and jax arrays on the default device (those
    interconvert freely — an AOT executable commits host inputs to its
    device); the device id for an array on ANOTHER single device (an
    executable is built for one device, so replicas placed one per chip
    each own their entries); a distinguishing string for MULTI-device
    shardings (an executable lowered against mesh-sharded operands
    rejects differently-placed inputs)."""
    s = getattr(leaf, "sharding", None)
    if s is None:
        return None
    devices = s.device_set
    if len(devices) > 1:
        return str(s)
    (device,) = devices
    return None if device == default_device else int(device.id)


def _shapes_key(args):
    """Leaf (shape, dtype, sharding) tuple of an argument pytree.
    Accepts real arrays, ShapeDtypeStructs, and python scalars."""
    import jax

    out = []
    default_device = jax.devices()[0]
    for leaf in jax.tree_util.tree_leaves(args):
        dt = getattr(leaf, "dtype", None)
        if dt is None:
            dt = np.asarray(leaf).dtype
        out.append((tuple(np.shape(leaf)), str(dt),
                    _leaf_sharding(leaf, default_device)))
    return tuple(out)


_METRIC_HANDLES = (None, -1, {})   # (registry, generation, name->Counter)


def _note_metric(name: str):
    """Mirror a compile/hit tick into the obs metrics registry so the
    fleet exporter sees the process-truthful compile counter next to
    the serving numbers.  Counter handles are cached per (registry,
    generation) — this runs on every tracked_jit dispatch (once per
    train step), which must not pay a registry-lock resolution each
    time; a reset()/clear() bumps the generation and forces
    re-registration.  Best-effort by design: the executable cache must
    work even if the obs layer is mid-teardown."""
    global _METRIC_HANDLES
    try:
        from bigdl_tpu.obs import metrics
        reg = metrics.get()
        cache_reg, gen, handles = _METRIC_HANDLES
        if cache_reg is not reg or gen != reg.generation:
            handles = {}
            _METRIC_HANDLES = (reg, reg.generation, handles)
        c = handles.get(name)
        if c is None:
            c = handles[name] = reg.counter(name,
                                            "shared executable cache")
        c.inc()
    except Exception:  # pragma: no cover - obs layer unavailable
        pass


class ExecutableCache:
    """The process-wide registry.  Thread-safe: serve replicas warm
    concurrently with a validating training thread."""

    def __init__(self):
        self._lock = threading.RLock()
        self._exes = {}       # key -> AOT-compiled executable
        self._jit_keys = set()  # keys registered via tracked_jit
        self.compiles = 0     # fresh XLA builds (or first jit dispatches)
        self.hits = 0         # key re-resolutions that cost nothing

    def key_for(self, fn_key, args, mesh=None):
        return (fn_key, _shapes_key(args), _mesh_key(mesh), _policy_key())

    def get_or_compile(self, jitted, fn_key, args, mesh=None):
        """Resolve (or build) the AOT executable for ``jitted`` at the
        shapes of ``args`` (arrays or ShapeDtypeStructs).  Returns
        ``(executable, fresh)``."""
        key = self.key_for(fn_key, args, mesh)
        with self._lock:
            exe = self._exes.get(key)
            if exe is not None:
                self.hits += 1
                _note_metric("xcache_hits_total")
                return exe, False
        # compile outside the lock: tens of seconds cold on a chip, and
        # another thread may be resolving a different bucket meanwhile
        exe = jitted.lower(*args).compile()
        with self._lock:
            if key in self._exes:   # lost a benign race: count the hit
                self.hits += 1
                _note_metric("xcache_hits_total")
                return self._exes[key], False
            self._exes[key] = exe
            self.compiles += 1
        _note_metric("xcache_compiles_total")
        # cost/HBM ledger capture rides the compile, keyed by the SAME
        # cache key (obs/ledger.py); hits above never reach this line,
        # so the warm path stays ledger-free
        try:
            from bigdl_tpu.obs import ledger as obs_ledger
            obs_ledger.get().capture_compiled(fn_key, exe, key=key)
        except Exception:   # pragma: no cover - obs layer unavailable
            pass
        return exe, True

    def note_jit_dispatch(self, fn_key, key_args, mesh=None) -> bool:
        """Record one jit dispatch keyed by ``key_args`` shapes; returns
        True when this key is new (the dispatch that compiles)."""
        key = self.key_for(fn_key, key_args, mesh)
        with self._lock:
            if key in self._jit_keys:
                self.hits += 1
                fresh = False
            else:
                self._jit_keys.add(key)
                self.compiles += 1
                fresh = True
        _note_metric("xcache_compiles_total" if fresh
                     else "xcache_hits_total")
        return fresh

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._exes) + len(self._jit_keys),
                    "aot_entries": len(self._exes),
                    "compiles": self.compiles, "hits": self.hits}

    def clear(self):
        with self._lock:
            self._exes.clear()
            self._jit_keys.clear()
            self.compiles = 0
            self.hits = 0


def get() -> ExecutableCache:
    global _CACHE
    if _CACHE is None:
        with _LOCK:
            if _CACHE is None:
                _CACHE = ExecutableCache()
    return _CACHE


def reset():
    """Drop every entry and zero the counters (tests).  Executables
    already handed out keep working — the registry only forgets them."""
    get().clear()


class ShapedCallable:
    """A jitted function routed through the shared cache: each call
    resolves the AOT executable for its argument shapes and invokes it —
    after the first call per shape, the serving/eval path never touches
    ``jax.jit`` again.

    Key resolution walks the argument pytree (validate's per-batch
    cadence tolerates that; the ServeEngine's hot path does NOT go
    through here — it caches the resolved executable per bucket), with
    an identity fast path for the dominant eval pattern: the same
    (params, state) objects fed batch after batch skip the tree walk
    entirely.

    ``.jitted`` and ``.fn_key`` are public so the ServeEngine can warm
    buckets through the SAME key space this callable resolves from.
    """

    __slots__ = ("jitted", "fn_key", "mesh", "_fast")

    def __init__(self, jitted, fn_key, mesh=None):
        self.jitted = jitted
        self.fn_key = fn_key
        self.mesh = mesh
        #: (id-tuple of leading args, tail shape/dtype key, policy key,
        #: executable) — identity of the big operands is sufficient:
        #: same objects => same shapes/shardings, and values are
        #: executable ARGUMENTS, never baked in
        self._fast = None

    def __call__(self, *args):
        fast = self._fast
        if fast is not None:
            ids = tuple(id(a) for a in args[:-1])
            tail = args[-1]
            tkey = (tuple(np.shape(tail)),
                    str(getattr(tail, "dtype", "")))
            if (fast[0] == ids and fast[1] == tkey
                    and fast[2] == _policy_key()):
                return fast[3](*args)
        exe, _ = get().get_or_compile(self.jitted, self.fn_key, args,
                                      self.mesh)
        if len(args) > 1:
            tail = args[-1]
            self._fast = (tuple(id(a) for a in args[:-1]),
                          (tuple(np.shape(tail)),
                           str(getattr(tail, "dtype", ""))),
                          _policy_key(), exe)
        return exe(*args)

    def lower(self, *args):   # AOT escape hatch, parity with jit fns
        return self.jitted.lower(*args)


def tracked_jit(fn, fn_key, key_argnums=None, mesh=None, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)`` with its dispatches registered in
    the shared cache.

    The wrapper keys on ``key_argnums`` (default: all args) — train
    steps pass the batch operand indices only, so the per-step cost is
    two shape probes, not a model-sized pytree walk.  Dispatch
    semantics (donation, shardings, weak types) are exactly jit's.
    """
    import jax

    jitted = jax.jit(fn, **jit_kwargs)
    cache = get()

    def wrapper(*args):
        sel = args if key_argnums is None else tuple(
            args[i] for i in key_argnums)
        fresh = cache.note_jit_dispatch(fn_key, sel, mesh)
        if fresh:
            # ledger capture on the dispatch that compiles, BEFORE the
            # dispatch runs — it may donate these argument buffers.
            # Cost comes from the lowering alone (one extra trace, no
            # second XLA compile); warm dispatches skip this entirely.
            try:
                from bigdl_tpu.obs import ledger as obs_ledger
                obs_ledger.get().capture_lowered(
                    fn_key, cache.key_for(fn_key, sel, mesh), jitted,
                    args)
            except Exception:  # pragma: no cover - obs layer unavailable
                pass
        return jitted(*args)

    wrapper.jitted = jitted
    wrapper.fn_key = fn_key
    return wrapper

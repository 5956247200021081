"""In-jit scalar taps: training-health scalars computed INSIDE the
compiled train step (docs/observability.md).

The reference surfaces loss and wall-clock only; gradient explosions or
a silently saturating update show up steps later (or never).  These
taps — gradient global-norm, parameter norm, update/parameter ratio and
the non-finite-element count — are a handful of VPU reductions fused
into the existing backward, returned alongside the step outputs exactly
like PR 1's jit-folded skip-step flag:

- the step stays ONE dispatch (the taps are extra outputs of the same
  executable, not a second program);
- the host does NOT synchronize on them every step: the loop holds the
  device scalars and materializes (blocks + converts) only every
  ``cadence`` steps, so the happy path pays zero extra device→host
  syncs beyond the loss read it already does.

Gating: ``BIGDL_OBS_TAPS`` (default on), cadence ``BIGDL_OBS_TAPS_CADENCE``
(default 10); ``LocalOptimizer.set_taps`` overrides both per run.
"""
from __future__ import annotations

import os
from collections import deque

import numpy as np

ENV_TAPS = "BIGDL_OBS_TAPS"
ENV_CADENCE = "BIGDL_OBS_TAPS_CADENCE"

#: keys of the dict ``compute`` returns, in a fixed order so event
#: consumers and the report tool can rely on the names
TAP_NAMES = ("grad_norm", "param_norm", "update_ratio", "nonfinite_grads")


def enabled(override: bool | None = None) -> bool:
    if override is not None:
        return bool(override)
    return os.environ.get(ENV_TAPS, "1") != "0"


def cadence(override: int | None = None) -> int:
    if override is not None:
        return max(1, int(override))
    return max(1, int(os.environ.get(ENV_CADENCE, "10")))


def compute(grads, params, new_params):
    """The tap dict, traced inside the train step.

    All reductions run in f32 (bf16 squares overflow at ~256) and cost a
    single fused pass over tensors the backward already has in HBM.
    ``new_params`` should be the POST-skip-select values so
    ``update_ratio`` reads 0 on a skipped step.  Under ``shard_map`` the
    caller merges the scalars across replicas (see ``_core_step``'s
    ``taps_merge``) — per-replica values there are local-gradient taps,
    so the merged ``grad_norm`` is the replica-mean of local norms, not
    the norm of the mean gradient (documented in docs/observability.md).
    """
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_leaves(grads)
    g2 = jnp.zeros((), jnp.float32)
    bad = jnp.zeros((), jnp.float32)
    for g in leaves:
        gf = g.astype(jnp.float32)
        g2 = g2 + jnp.sum(jnp.square(gf))
        bad = bad + jnp.sum(~jnp.isfinite(gf)).astype(jnp.float32)
    p2 = jnp.zeros((), jnp.float32)
    d2 = jnp.zeros((), jnp.float32)
    for p, q in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(new_params)):
        pf = p.astype(jnp.float32)
        p2 = p2 + jnp.sum(jnp.square(pf))
        d2 = d2 + jnp.sum(jnp.square(q.astype(jnp.float32) - pf))
    pnorm = jnp.sqrt(p2)
    return {
        "grad_norm": jnp.sqrt(g2),
        "param_norm": pnorm,
        "update_ratio": jnp.sqrt(d2) / (pnorm + 1e-12),
        "nonfinite_grads": bad,
    }


def module_counters(net_state):
    """Counters that modules leave in their buffers, as taps: every scalar
    buffer named ``tap_<name>`` becomes ``<name>/<i>``, ``i`` counting the
    modules that hold one in the model's own order (``DroplessMoE`` keeps
    ``assignments_held`` and ``expert_max``).  They ride the step's
    outputs with the taps above, materialized at the same cadence."""
    out, seen = {}, {}

    def walk(tree):
        for key, value in tree.get("~", {}).items():
            if key.startswith("tap_"):
                name = key[len("tap_"):]
                out[f"{name}/{seen.setdefault(name, 0)}"] = value
                seen[name] += 1
        for key in sorted((k for k in tree if k != "~"),
                          key=lambda k: (not k.isdigit(),
                                         int(k) if k.isdigit() else k)):
            walk(tree[key])

    walk(net_state)
    return out


class TapsMonitor:
    """Host-side cadence gate for the device tap scalars.

    ``push(step, taps)`` stores the latest DEVICE values (no sync) and
    materializes them to floats only once at least ``cadence``
    iterations have passed since the previous materialization;
    ``flush()`` materializes a pending tail (end of run, so a 4-step
    smoke with cadence 10 still logs one sample).
    ``materialized_steps`` is the audit trail the dispatch-count test
    asserts on: host syncs happen at cadence boundaries, nowhere else.

    The gate is elapsed-iterations, not ``step % cadence == 0``: under
    ``iters_per_dispatch = n`` the pushed step numbers advance by n, and
    for most (n, cadence) pairs an exact-multiple test would NEVER fire
    (neval 1, 9, 17, ... never lands on a multiple of 10) — the same
    chunk-boundary trap ``LocalOptimizer._fired_within`` solves for
    triggers.
    """

    def __init__(self, cadence_override: int | None = None,
                 enabled_override: bool | None = None):
        self.enabled = enabled(enabled_override)
        self.cadence = cadence(cadence_override)
        # bounded: an always-on telemetry path must not grow with run
        # length (a 10M-step run would otherwise bank ~1M samples; the
        # durable record is the event stream, this is the live window)
        self.history = deque(maxlen=1024)  # (step, {name: float})
        self.materialized_steps = deque(maxlen=1024)
        self._pending = None
        self._last_materialized = 0

    def push(self, step: int, taps) -> dict | None:
        """Returns the materialized {name: float} dict at cadence
        boundaries, None otherwise (including when taps are off)."""
        if not taps:
            return None
        self._pending = (int(step), taps)
        if step - self._last_materialized >= self.cadence:
            return self._materialize()
        return None

    def flush(self) -> dict | None:
        if self._pending is None:
            return None
        return self._materialize()

    def _materialize(self) -> dict:
        step, taps = self._pending
        self._pending = None
        self._last_materialized = step
        # chunked dispatch (iters_per_dispatch > 1) stacks (n,) values:
        # report the chunk's LAST step, same convention as state['loss']
        vals = {k: float(np.asarray(v).reshape(-1)[-1])
                for k, v in taps.items()}
        self.materialized_steps.append(step)
        self.history.append((step, vals))
        return vals

    def last(self) -> dict | None:
        return self.history[-1][1] if self.history else None

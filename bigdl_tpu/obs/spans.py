"""Nested wall-clock spans around the training loop's phases
(docs/observability.md).

The reference's Metrics.scala names flat counters ("computing time
average", "get weights average"); spans keep that — every span IS a
``optim.Metrics`` entry named ``span: <path>`` — and add four things:

- nesting: ``span("call")`` inside ``span("dispatch")`` records the
  path ``dispatch/call``, so the totals read as a tree;
- device-trace visibility: each span body runs under a
  ``jax.profiler`` TraceAnnotation (``utils/profiler.annotation``) named
  by its path, so the same names line up in XProf/TensorBoard traces;
- a step timeline: beside the totals, a bounded ring of
  ``(path, start, end, step)`` for every span the loop's thread opens,
  the spans of one iteration sharing its step (:meth:`begin_step` /
  :meth:`end_step`), so that a run leaves a distribution and its slowest
  iterations behind and not means alone (:meth:`step_timeline`);
- a cross-process breakdown with the deadlock-safe pattern Metrics
  already has: the TOP-LEVEL phase names are declared as distributed
  entries on EVERY process at construction (``Metrics.declare``), so the
  epoch-end ``collect_per_node`` gather walks the identical name list on
  every host even when a phase only ran on process 0 (checkpoint
  writes), and process 0 can render the per-host table afterwards from
  the cache alone.
"""
from __future__ import annotations

import math
import time
import weakref
from collections import deque
from contextlib import contextmanager

#: top-level phases every optimizer declares — the fixed, every-process
#: name set that keeps the per-node allgather deadlock-free.  ``h2d``
#: is the host→device batch transfer (inline, or credited from the
#: prefetch transfer thread via :meth:`SpanTracker.record`); ``host-wait``
#: is the cadence-boundary device→host sync the loops pay instead of a
#: per-step ``float(loss)``; ``flush`` is the host work that follows it
#: (logging, the finite ledger, step events, gauges) and ``bookkeep`` the
#: loop's own counters, epoch rollover and trigger probes
#: (docs/observability.md "host pipeline").
PHASES = ("data-load", "h2d", "dispatch", "host-wait", "flush", "bookkeep",
          "aggregate", "validate", "checkpoint")

_PREFIX = "span: "

#: records the ring holds: an iteration of the local loop writes seven
#: (``data-load``, ``dispatch/prepare``, ``dispatch/call``, ``dispatch``,
#: two ``bookkeep``, ``loop``) and two more at a flush, so about two
#: thousand iterations
RING_RECORDS = 16384
#: iterations of the ring's tail that a crash bundle takes
TAIL_ITERATIONS = 32

#: every tracker alive in the process, for the crash bundle's ring tails
_TRACKERS = weakref.WeakSet()


class SpanTracker:
    def __init__(self, metrics, phases=PHASES):
        self.metrics = metrics
        self.phases = tuple(phases)
        self._stack: list = []   # [(path, start)] of the open spans
        self._paths: list = []   # insertion-ordered distinct span paths
        self.ring = deque(maxlen=RING_RECORDS)
        self.appended = 0        # records ever written to the ring
        self._step = None        # the iteration the next records serve
        self._step_t0 = None
        for name in self.phases:
            metrics.declare(_PREFIX + name, distributed=True)
        _TRACKERS.add(self)

    @contextmanager
    def span(self, name: str):
        """Time a phase; nested calls build slash paths.  Top-level
        phases from ``PHASES`` feed the distributed per-host breakdown;
        ad-hoc/nested names stay process-local."""
        from bigdl_tpu.utils.profiler import annotation
        path = self._stack[-1][0] + "/" + name if self._stack else name
        t0 = time.perf_counter()
        self._stack.append((path, t0))
        try:
            with annotation(path):
                yield
        finally:
            self._stack.pop()
            t1 = time.perf_counter()
            self._book(path, t0, t1)
            if path not in self._paths:
                self._paths.append(path)
            self.metrics.add(_PREFIX + path, t1 - t0,
                             distributed=(path in self.phases))

    def _book(self, path, t0, t1):
        self.ring.append((path, t0, t1, self._step))
        self.appended += 1

    def begin_step(self, step: int) -> float:
        """The top of an iteration: the spans opened from here on carry
        ``step`` (those outside an iteration, the run-end flush or a
        validation, carry the last one).  Returns the clock reading the
        iteration's wall is counted from."""
        self._step = int(step)
        self._step_t0 = time.perf_counter()
        return self._step_t0

    def end_step(self):
        """The iteration's end: its wall since :meth:`begin_step` is
        booked to the counter ``loop``, so that time under no span is
        measured (``loop`` minus the spans) and not inferred, and closes
        the iteration's records in the ring."""
        t1 = time.perf_counter()
        self._book("loop", self._step_t0, t1)
        self.record("loop", t1 - self._step_t0)

    def record(self, name: str, seconds: float, count: int = 1):
        """Credit an externally-timed interval to a span — work measured
        on a background thread (the prefetch pipeline's H2D transfers)
        whose timing the main thread drains and books here.  ``count=0``
        adds seconds to an interval already counted once (accumulating a
        phase across drains without inflating its sample count)."""
        if seconds <= 0 and count <= 0:
            return
        if name not in self._paths:
            self._paths.append(name)
        self.metrics.accumulate(_PREFIX + name, seconds, count=count,
                                distributed=(name in self.phases))

    # -- rendering ---------------------------------------------------------
    def rows(self):
        """(path, depth, mean_s, total_s, count) per span, tree order."""
        out = []
        for path in sorted(self._paths):
            total, count = self.metrics.get(_PREFIX + path)
            out.append((path, path.count("/"), self.metrics.mean(
                _PREFIX + path), total, count))
        return out

    # -- the step timeline -------------------------------------------------
    def records_since(self, mark: int = 0):
        """The ring's records written after ``mark`` (an earlier reading
        of ``appended``), oldest first; those the ring has dropped are
        gone."""
        n = min(self.appended - mark, len(self.ring))
        ring = list(self.ring)
        return ring[len(ring) - n:]

    def step_timeline(self, since: int = 0, slowest: int = 5):
        """What the ring holds of the iterations closed after ``since``:
        the distribution (p50 / p95 / max, milliseconds) of the
        iteration's wall, of ``dispatch/call`` and of what lies between
        two calls (the wall less the call), and the ``slowest``
        iterations with their step and their longest span.  None where no
        iteration was closed."""
        by_step = {}
        for rec in self.records_since(since):
            by_step.setdefault(rec[3], []).append(rec)
        rows = []       # (wall, call, step, longest span's path, its ms)
        for step, recs in by_step.items():
            loop = next((r for r in recs if r[0] == "loop"), None)
            if loop is None:
                continue    # cut by the ring's head, or not closed yet
            inside = [r for r in recs if r is not loop and r[2] <= loop[2]]
            paths = {r[0] for r in inside}
            leaves = [r for r in inside if not any(
                p.startswith(r[0] + "/") for p in paths)]
            top = max(leaves, key=lambda r: r[2] - r[1], default=None)
            call = sum(r[2] - r[1] for r in inside
                       if r[0] == "dispatch/call")
            rows.append((loop[2] - loop[1], call, step,
                         top[0] if top else None,
                         _ms(top[2] - top[1]) if top else 0.0))
        if not rows:
            return None
        worst = sorted(rows, key=lambda r: -r[0])[:slowest]
        return {
            "sampled": len(rows),
            "iter_ms": _dist([r[0] for r in rows]),
            "call_ms": _dist([r[1] for r in rows]),
            "between_calls_ms": _dist([r[0] - r[1] for r in rows]),
            "slowest": [{"step": r[2], "ms": _ms(r[0]), "span": r[3],
                         "span_ms": r[4]} for r in worst]}

    def tail(self):
        """For a crash bundle: the spans open now and the records of the
        last ``TAIL_ITERATIONS`` iterations (of a loop that closes no
        iteration, as many records as those would be at most), on the
        ``perf_counter`` clock that ``now`` reads."""
        records, loops = [], 0
        for rec in reversed(self.records_since()):
            loops += rec[0] == "loop"
            if loops > TAIL_ITERATIONS or \
                    len(records) >= 16 * TAIL_ITERATIONS:
                break
            records.append(rec)
        return {"now": time.perf_counter(), "step": self._step,
                "open": [list(s) for s in self._stack],
                "records": [list(r) for r in reversed(records)]}

    def per_host_report(self) -> str:
        """Per-process mean seconds for each top-level phase.

        CONTRACT: multi-process callers must have run
        ``metrics.collect_per_node()`` (a collective every process joins,
        e.g. the end of ``DistriOptimizer.optimize``) first — this method
        then reads the cached snapshot and is safe from process 0 alone.
        """
        rows = [(name, self.metrics.per_node(_PREFIX + name))
                for name in self.phases]
        n_hosts = max(len(vals) for _, vals in rows)
        header = f"{'phase':<14}" + "".join(
            f"{'host' + str(i):>12}" for i in range(n_hosts))
        lines = [header]
        for name, vals in rows:
            lines.append(f"{name:<14}" + "".join(
                f"{v:>12.4f}" for v in vals))
        return "\n".join(lines)

    def emit_phase_events(self, events_log, step: int):
        """One ``phase`` event per span path (cumulative mean + count),
        emitted at epoch boundaries and run end."""
        if events_log is None:
            return
        for path, _, mean, total, count in self.rows():
            if count:
                events_log.emit("phase", name=path, seconds=mean,
                                total=total, count=count, step=int(step))


def _ms(seconds: float) -> float:
    return round(seconds * 1e3, 3)


def _dist(seconds):
    """p50 / p95 / max of a list of seconds, in milliseconds (nearest
    rank: a reading that occurred)."""
    ordered = sorted(seconds)
    rank = lambda q: ordered[max(0, math.ceil(q * len(ordered)) - 1)]
    return {"p50": _ms(rank(0.5)), "p95": _ms(rank(0.95)),
            "max": _ms(ordered[-1])}


def render_timeline(t: dict) -> str:
    """The ``step_timeline`` event as one log line."""
    dist = lambda d: f"p50 {d['p50']:.3f} p95 {d['p95']:.3f} max " \
                     f"{d['max']:.3f} ms"
    counts = lambda d: ", ".join(f"{k}: {v}" for k, v in d.items()) or "-"
    kept = {k: f"{v['kept']} of {v['count']}"
            for k, v in t["flushes"].items()}
    slowest = "; ".join(
        f"step {s['step']} {s['ms']:.3f} ms ({s['span']} "
        f"{s['span_ms']:.3f})" for s in t["slowest"])
    return (f"step timeline, {t['steps']} iterations ({t['sampled']} in "
            f"the ring): iteration {dist(t['iter_ms'])}; dispatch/call "
            f"{dist(t['call_ms'])}; between two calls "
            f"{dist(t['between_calls_ms'])}; steps in flight at a "
            f"dispatch {{{counts(t['in_flight'])}}}; dispatched to an "
            f"empty device after {{{counts(t['device_empty'])}}}; "
            f"flushes that left a step in flight {{{counts(kept)}}}; "
            f"slowest: {slowest}")


def timeline_tails():
    """:meth:`SpanTracker.tail` of every tracker alive whose ring holds
    something (``obs/diagnostics.py``'s crash bundle)."""
    return [t.tail() for t in list(_TRACKERS) if t.ring]

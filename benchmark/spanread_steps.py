"""What the ``loop.*`` and ``step.gap_*`` readers share: the loop's step
timeline (PR 37), read from the span totals and from the trace.

The program splits its ``dispatch`` span where its work divides,
``dispatch/prepare`` (the rate, the key) and ``dispatch/call`` (the jitted
call alone), and between the two counts the steps the device still holds:
the counters ``dispatch/in-flight`` (the counts summed, one booking a
dispatch) and ``dispatch/device-empty`` (one booking for each dispatch that
found none).  The ``loop.*`` readers take these from ``obs["spans"]``.

The ``step.gap_*`` readers work on the trace's own clock.  *Steps on the
device*: the operations of the entry computation of the step's text recur
in one order, and a step starts where that order starts again, at the
operation that comes first in the text among those traced once a step.
*A gap* lies between the last such operation of one step and the first of
the next; device operations in it belong to no step (the key's
``fold_in``, the rate's copy) and are printed, not subtracted.  *Pairing*:
the window closes on a drained device, so the k-th last step on the device
is the k-th last ``dispatch/call`` (only the loop's thread opens that
span); steps at the trace's head whose call the trace does not hold are
left out.  *The split*:
the part of a gap before the next step's ``dispatch/call`` began is the
host's (the loop had not called yet), the rest is spent with the call
open, or returned, and the device not started; the two sum to the gap for
every step and for the means.

A program without ``dispatch/call`` (the parent of that PR) makes every
reader here return None, and so does a run that is not on an accelerator.
"""
from __future__ import annotations

import bisect
import collections
import sys

from benchmark import spanread
from benchmark.trace import op_short_name

CALL = "dispatch/call"
PREPARE = "dispatch/prepare"
IN_FLIGHT = "dispatch/in-flight"
EMPTY = "dispatch/device-empty"
# the spans of the loop's thread that the late part of a gap is put down to
LATE_SPANS = ("data-load", "h2d", PREPARE, "host-wait", "flush", "bookkeep",
              "validate", "checkpoint")


# -- span totals ------------------------------------------------------------

def _spans(obs):
    """The window's span totals, of a run on an accelerator (the harness
    gives ``peaks`` for a TPU only) whose program splits its dispatch.  On
    the CPU backend the same spans and counters would describe XLA's CPU
    client under names that speak of the device: a rehearsal leaves them
    out, as it leaves out ``*.step_mfu``."""
    spans = obs.get("spans")
    if not spans or not obs.get("steps") or CALL not in spans \
            or obs.get("peaks") is None:
        return None
    return spans


def span_ms(obs, path):
    """Milliseconds a step of one of the loop's nested spans."""
    spans = _spans(obs)
    if spans is None or path not in spans:
        return None
    return spans[path][0] / obs["steps"] * 1e3


def between_calls_ms(obs):
    """The counter ``loop`` less ``dispatch/call``, a step: everything the
    host does between two calls."""
    spans = _spans(obs)
    if spans is None or "loop" not in spans:
        return None
    return (spans["loop"][0] - spans[CALL][0]) / obs["steps"] * 1e3


def in_flight_steps(obs):
    """Mean steps the device still held at a dispatch."""
    spans = _spans(obs)
    total, bookings = (spans or {}).get(IN_FLIGHT, (0.0, 0))
    return total / bookings if bookings else None


def device_empty_pct(obs):
    """Share of the dispatches that found nothing in flight."""
    spans = _spans(obs)
    bookings = (spans or {}).get(IN_FLIGHT, (0.0, 0))[1]
    if not bookings:
        return None
    return 100.0 * spans.get(EMPTY, (0.0, 0))[1] / bookings


# -- the steps on the device ------------------------------------------------

def device_steps(events, program):
    """([(first operation's start, last operation's end)] of each step on
    one chip, [(name, start, end)] of the operations between two steps).

    ``events`` are the chip's [(name, start_ns, dur_ns)].  The operations
    of the entry computation that the trace holds as often as most of them
    (once a step) mark the steps: the one that comes first in the text
    starts a step, the last of them to end before the next start ends it.
    What starts after that and before the next step belongs to no step,
    whatever its name (a small program's ``fusion`` may be called as one of
    the step's)."""
    order = {instr["name"]: i
             for i, instr in enumerate(program.comps[program.entry])}
    events = sorted((start, start + dur, op_short_name(name))
                    for name, start, dur in events)
    seen = collections.Counter(name for _, _, name in events
                               if name in order)
    if not seen:
        return [], []
    once = collections.Counter(seen.values()).most_common(1)[0][0]
    regular = {name for name, n in seen.items() if n == once}
    anchor = min(regular, key=order.get)
    starts = [start for start, _, name in events if name == anchor]
    ends = [0] * len(starts)
    for start, end, name in events:
        k = bisect.bisect_right(starts, start) - 1
        if k >= 0 and name in regular:
            ends[k] = max(ends[k], end)
    between = [(name, start, end) for start, end, name in events
               if (k := bisect.bisect_right(starts, start) - 1) >= 0
               and start >= ends[k]]
    return list(zip(starts, ends)), between


def _host_events(trace):
    """(the ``dispatch/call`` intervals, every host event as (name, start,
    end)).  The profile gives all of a process's Python threads one line
    name, so a thread is not told by it: the spans are found by their
    names, each of which one thread only opens."""
    events = [(name, start, start + dur)
              for name, start, dur, _ in trace.host_spans]
    calls = sorted((start, end) for name, start, end in events
                   if name == CALL)
    return calls, events


def step_gaps(obs):
    """The gaps between the steps on the first chip, each split at the
    start of the next step's ``dispatch/call``.  {'gap_ms', 'late_ms',
    'in_call_ms': means over the gaps; 'gaps': how many; 'gap_s',
    'foreign_s': all gaps, and the operations inside them}; None without a
    device trace, the step's text, or a ``dispatch/call`` in the trace."""
    if "_step_gaps" not in obs:
        obs["_step_gaps"] = _step_gaps(obs)
    return obs["_step_gaps"]


def _step_gaps(obs):
    trace, program = obs.get("trace"), obs.get("program_text")
    if trace is None or program is None or not trace.device_ops:
        return None
    calls, events = _host_events(trace)
    if not calls:
        return None
    plane = sorted(trace.device_ops)[0]
    steps, between = device_steps(trace.device_ops[plane], program)
    n = min(len(steps), len(calls))
    first = len(steps) - n              # the first step whose call is held
    calls = calls[len(calls) - n:]
    rows = []   # (gap, late, where the gap starts, the step's ordinal, call)
    for j in range(max(first, 1), len(steps)):
        at = steps[j - 1][1]
        gap = max(0, steps[j][0] - at)
        late = min(gap, max(0, calls[j - first][0] - at))
        rows.append((gap, late, at, j + 1, calls[j - first]))
    if not rows:
        return None
    gap_ns = sum(r[0] for r in rows)
    late_ns = sum(r[1] for r in rows)
    foreign = [op for op in between
               if any(r[2] <= op[1] < r[2] + r[0] for r in rows)]
    gap_ms = round(gap_ns / len(rows) / 1e6, 6)
    late_ms = round(late_ns / len(rows) / 1e6, 6)
    out = {"gap_ms": gap_ms, "late_ms": late_ms,
           "in_call_ms": round(gap_ms - late_ms, 6), "gaps": len(rows),
           "gap_s": gap_ns / 1e9,
           "foreign_s": sum(end - start for _, start, end in foreign) / 1e9}
    _print_tables(out, rows, foreign, events, steps, calls, first)
    return out


def late_by_span(rows, events):
    """{span of the loop's thread, ``dispatch`` (its own time, outside
    both halves) or 'under no span': ns of the gaps' late parts}."""
    late = spanread.merged([(r[2], r[2] + r[1]) for r in rows])
    by_name = collections.defaultdict(list)
    for name, start, end in events:
        if name in LATE_SPANS or name in ("dispatch", CALL):
            by_name[name].append((start, end))
    out, named = {}, []
    for name in LATE_SPANS:
        spans = spanread.merged(by_name[name])
        out[name] = spanread.total(spanread.intersect(late, spans))
        named += spans
    own = spanread.subtract(spanread.merged(by_name["dispatch"]),
                            spanread.merged(named + by_name[CALL]))
    out["dispatch"] = spanread.total(spanread.intersect(late, own))
    out["under no span"] = spanread.total(late) - sum(out.values())
    return {k: v for k, v in out.items() if v}


def in_call_by_event(rows, events):
    """{where a gap's in-call part was spent: ns}.  What of it lies after
    the call returned is 'the call had returned' (the launch had not
    landed); the rest goes to the innermost of the runtime's own events
    inside the call (not the Python tracer's ``$...`` nor the feed
    threads' spans; any other thread's event is told apart by lying inside
    the call's interval, which is all the profile allows) that covers at
    least half of it, else to ``dispatch/call`` itself."""
    inner = sorted((start, end, name) for name, start, end in events
                   if not name.startswith("$")
                   and name not in (CALL, spanread.FETCH, spanread.H2D))
    out = collections.Counter()
    for gap, late, at, _, (call_start, call_end) in rows:
        lo, hi = at + late, at + gap
        out["the call had returned"] += hi - max(lo, min(hi, call_end))
        hi = min(hi, call_end)
        if hi <= lo:
            continue
        best = (call_end - call_start, CALL)
        first = bisect.bisect_left(inner, (call_start,))
        for start, end, name in inner[first:]:
            if start >= hi:
                break
            if end <= call_end and end - start < best[0] and \
                    2 * (min(end, hi) - max(start, lo)) >= hi - lo:
                best = (end - start, name)
        out[best[1]] += hi - lo
    return {k: v for k, v in out.items() if v}


def _print_tables(out, rows, foreign, events, steps, calls, first):
    """For PERF.md, as ``spanread`` prints its tables."""
    n = len(rows)
    ms = lambda ns: ns / n / 1e6
    early = sum(1 for j in range(first, len(steps))
                if steps[j][0] < calls[j - first][0])
    print(f"step gaps: {len(steps)} steps on the device, {len(calls)} "
          f"paired with their dispatch/call, {n} gaps; mean gap "
          f"{out['gap_ms']:.6f} ms = host late {out['late_ms']:.6f} + in "
          f"the call {out['in_call_ms']:.6f}; all gaps {out['gap_s']:.6f} s,"
          f" operations inside them {out['foreign_s']:.6f} s"
          + (f"; {early} STEPS START BEFORE THEIR CALL: the pairing is off"
             if early else ""), file=sys.stderr)
    print("  host late, ms a gap, by the loop's span: " + ", ".join(
        f"{k} {ms(v):.4f}" for k, v in sorted(
            late_by_span(rows, events).items(),
            key=lambda kv: -kv[1])), file=sys.stderr)
    print("  in the call, ms a gap, by the innermost event of the runtime "
          "inside it: " + ", ".join(
              f"{k} {ms(v):.4f}" for k, v in sorted(
                  in_call_by_event(rows, events).items(),
                  key=lambda kv: -kv[1])[:8]), file=sys.stderr)
    by_name = collections.defaultdict(lambda: [0, 0])
    for name, start, end in foreign:
        by_name[name][0] += 1
        by_name[name][1] += end - start
    print("  device operations inside the gaps (count, ms a gap): "
          + (", ".join(f"{k} {c} {ms(v):.4f}" for k, (c, v) in sorted(
              by_name.items(), key=lambda kv: -kv[1][1])[:12]) or "none"),
          file=sys.stderr)
    print("  largest gaps (step on the device, ms, late, in the call): "
          + ", ".join(f"{step} {gap / 1e6:.3f} {late / 1e6:.3f} "
                      f"{(gap - late) / 1e6:.3f}"
                      for gap, late, _, step, _ in sorted(rows)[:-11:-1]),
          file=sys.stderr)

"""What the readers of the trainer's own spans and scopes share (the
``train.feed_*``, ``train.loop_*``, ``train.idle_*``, ``train.fwd_ms`` /
``bwd_ms`` / ``lrn_ms`` / ``pool_ms`` metrics).

Three sources, all written by the program and only read here:

- ``obs["spans"]``: the optimizer's span totals over the window,
  ``{path: (seconds, count)}``.  The loop's thread books ``data-load`` (its
  wait for a batch), ``dispatch``, ``host-wait``, ``flush``, ``bookkeep``,
  ``validate``, ``checkpoint`` and the counter ``loop`` (an iteration's
  wall); the feed's threads book ``data-load/fetch`` with its links
  (``.../source:<DataSet>``, ``.../stage/<i>:<Stage>``) and
  ``h2d/prefetch``, drained into the same tree.
- the profiler's host events of the same names, on the device's clock: the
  idle time of the device is split by what the threads were doing in it.
- the ``jax.named_scope`` of every module kind (and ``optim-update``,
  ``obs-taps``) in the ``op_name`` of the compiled step's instructions.

A program without these spans or scopes (the parent of the PR that added
them) makes every reader here return None.
"""
from __future__ import annotations

import collections
import re
import sys

# the spans of the loop's own thread, which partition an iteration
LOOP_WORK = ("dispatch", "host-wait", "flush", "bookkeep")
MAIN_SPANS = ("data-load",) + LOOP_WORK + ("validate", "checkpoint")
FETCH = "data-load/fetch"
H2D = "h2d/prefetch"
POOL_SCOPES = ("SpatialMaxPooling", "SpatialAveragePooling")
LRN_SCOPE = "SpatialCrossMapLRN"


# -- span totals --------------------------------------------------------------

def ms_per_step(obs, paths):
    """Summed milliseconds per step of ``paths`` over the window, or None
    where the run has no steps or lacks one of them."""
    spans = obs.get("spans")
    if not spans or not obs.get("steps") or \
            any(p not in spans for p in paths):
        return None
    return sum(spans[p][0] for p in paths) / obs["steps"] * 1e3


def ms_per_count(obs, path):
    """Mean milliseconds of one booking of ``path`` (a batch drawn, a
    transfer made)."""
    spans = obs.get("spans") or {}
    seconds, count = spans.get(path, (0.0, 0))
    return seconds / count * 1e3 if count else None


def stage_self_ms(obs):
    """{link of the producer's chain: self ms per batch}, the source and
    every stage under ``data-load/fetch``."""
    spans = obs.get("spans") or {}
    out = {}
    for path, (seconds, count) in spans.items():
        if path.startswith(FETCH + "/") and count:
            out[path[len(FETCH) + 1:]] = seconds / count * 1e3
    return out


def inline_h2d_seconds(spans):
    """The ``h2d`` span holds the loop's own transfers and, credited from
    the background, the transfer thread's; only the first block the loop."""
    return max(0.0, spans.get("h2d", (0.0, 0))[0]
               - spans.get(H2D, (0.0, 0))[0])


def loop_unnamed_ms(obs):
    """The ``loop`` counter minus every span of the loop's thread, per
    step: iteration time that no span names."""
    spans = obs.get("spans")
    if not spans or not obs.get("steps") or "loop" not in spans:
        return None
    named = sum(spans.get(p, (0.0, 0))[0] for p in MAIN_SPANS) \
        + inline_h2d_seconds(spans)
    return (spans["loop"][0] - named) / obs["steps"] * 1e3


# -- interval arithmetic on sorted, disjoint [(start, end)] lists -------------

def merged(intervals):
    out = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def intersect(a, b):
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b):
    """``a`` without ``b``."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, at = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > at:
                out.append((at, b[k][0]))
            at = max(at, b[k][1])
            k += 1
        if at < hi:
            out.append((at, hi))
    return out


def total(intervals):
    return sum(b - a for a, b in intervals)


# -- the device's idle time, by what the host's threads were doing ------------

def idle_partition(obs):
    """{'feed' | 'h2d' | 'loop' | 'unnamed': % of the traced window}: the
    device's idle time split by the host's state, the four summing to
    ``train.device_idle_pct``.  While the device is idle,

    - ``loop``: the loop's thread is in ``dispatch``, ``host-wait``,
      ``flush`` or ``bookkeep``;
    - ``h2d``: it waits in ``data-load`` and an ``h2d/prefetch`` is open;
    - ``feed``: it waits in ``data-load``, no transfer is open and the
      producer is inside a ``data-load/fetch``;
    - ``unnamed``: the rest, the trace's head and tail included.

    None without a device trace or without the feed's spans in it."""
    if "_idle_partition" in obs:
        return obs["_idle_partition"]
    obs["_idle_partition"] = out = _idle_partition(obs)
    if out is not None:
        print("idle partition (% of the traced window): " + ", ".join(
            f"{k} {v:.2f}" for k, v in out.items()), file=sys.stderr)
    return out


def _idle_partition(obs):
    trace = obs.get("trace")
    if trace is None or not trace.device_ops or not obs.get("traced_s"):
        return None
    by_name = collections.defaultdict(list)
    wanted = set(LOOP_WORK) | {"data-load", FETCH, H2D}
    edges = [t for events in trace.device_ops.values()
             for _, start, dur in events for t in (start, start + dur)]
    for name, start, dur, _ in trace.host_spans:
        edges += (start, start + dur)
        if name in wanted:
            by_name[name].append((start, start + dur))
    if FETCH not in by_name or H2D not in by_name:
        return None
    lo, hi = min(edges), max(edges)     # the trace, on its own clock
    waiting = merged(by_name["data-load"])
    working = merged([iv for n in LOOP_WORK for iv in by_name[n]])
    transfer = merged(by_name[H2D])
    fetch = merged(by_name[FETCH])
    sums = collections.Counter()
    for plane in trace.device_ops:
        idle = subtract([(lo, hi)], trace.busy_intervals(plane))
        sums["loop"] += total(intersect(idle, working))
        wait_idle = intersect(idle, waiting)
        under_h2d = intersect(wait_idle, transfer)
        sums["h2d"] += total(under_h2d)
        sums["feed"] += total(intersect(subtract(wait_idle, transfer),
                                        fetch))
    chips = len(trace.device_ops)
    traced_ns = obs["traced_s"] * 1e9
    out = {k: 100.0 * sums[k] / chips / traced_ns
           for k in ("feed", "h2d", "loop")}
    idle_pct = 100.0 * (1.0 - trace.busy_s() / obs["traced_s"])
    out["unnamed"] = idle_pct - sum(out.values())
    return out


# -- the compiled step's operations, by the scope that traced them ------------

_STRONG = ("CONV-FWD", "CONV-BWD", "POOL-FWD", "POOL-BWD", "MATMUL")
_MODULE = re.compile(r"^[A-Z]\w*$")
_STEP_SCOPES = ("optim-update", "obs-taps")
_NOT_MODULES = _STEP_SCOPES + ("unscoped", "unknown")


def scope_of(op_name):
    """(kind, direction) of one instruction's ``op_name``: the innermost
    module scope (a class name, ``jvp(Concat)/Sequential/ReLU/max`` ->
    ``ReLU``), else ``optim-update`` / ``obs-taps``, else None; ``bwd``
    under ``transpose(``, ``fwd`` under ``jvp(`` alone."""
    tokens = [t for t in re.split(r"[/()]", op_name) if t]
    kind = next((t for t in reversed(tokens) if _MODULE.match(t)), None)
    if kind is None:
        kind = next((t for t in tokens if t in _STEP_SCOPES), None)
    direction = ("bwd" if "transpose(" in op_name
                 else "fwd" if "jvp(" in op_name else None)
    return kind, direction


def scope_seconds(obs):
    """{(kind, direction, category): device seconds over the traced
    window}, each operation booked by ``op_scope``.  None without a device
    trace and the program's text, or where no operation has a module
    scope."""
    if "_scope_seconds" in obs:
        return obs["_scope_seconds"]
    obs["_scope_seconds"] = out = _scope_seconds(obs)
    if out is not None:
        _print_tables(obs, out)
    return out


def op_scope(program, name):
    """(kind, direction) of one operation of the entry computation: the
    scope most frequent among its parts, those that hold a convolution, a
    window reduction or a matrix product voting alone where there are any."""
    from benchmark.trace import categorize
    instr = program.by_name.get(name)
    if instr is None:
        return "unknown", None
    parts = list(program._parts(instr))
    strong = [p for p in parts
              if categorize(p["opcode"], p["op_name"]) in _STRONG]
    scopes = [scope_of(p["op_name"]) for p in (strong or parts)]
    named = [sc for sc in scopes if sc[0] is not None]
    kind, direction = collections.Counter(
        named or scopes).most_common(1)[0][0]
    return kind or "unscoped", direction


def _scope_seconds(obs):
    trace, program = obs.get("trace"), obs.get("program_text")
    if trace is None or program is None or not trace.device_ops:
        return None
    out, scoped = collections.Counter(), False
    for name, seconds in trace.op_seconds().items():
        kind, direction = op_scope(program, name)
        scoped = scoped or kind not in _NOT_MODULES
        out[(kind, direction, program.category(name))] += seconds
    return out if scoped else None


def scoped_ms(obs, want):
    """Device ms/step of the operations booked to a module scope that
    ``want(kind, direction)`` picks."""
    table = scope_seconds(obs)
    if table is None or not obs.get("steps"):
        return None
    picked = [s for (kind, direction, _), s in table.items()
              if kind not in _NOT_MODULES and want(kind, direction)]
    return sum(picked) / obs["steps"] * 1e3 if picked else None


def _print_tables(obs, table):
    """For PERF.md: device ms/step by scope, what forward and backward
    leave of the busy time, and the former ELTWISE/OTHER time by kind."""
    steps = obs.get("steps") or 1
    ms = lambda s: s / steps * 1e3
    by_kind, other = collections.Counter(), collections.Counter()
    for (kind, direction, category), s in table.items():
        by_kind[(kind, direction)] += s
        if category == "ELTWISE/OTHER":
            other[kind] += s
    busy = sum(table.values())
    print(f"device ms/step by scope (busy {ms(busy):.3f}):", file=sys.stderr)
    for (kind, direction), s in by_kind.most_common():
        print(f"  scope {kind} {direction or '-'} {ms(s):.3f}",
              file=sys.stderr)
    print(f"ELTWISE/OTHER ms/step by scope (total "
          f"{ms(sum(other.values())):.3f}):", file=sys.stderr)
    for kind, s in other.most_common():
        print(f"  other {kind} {ms(s):.3f}", file=sys.stderr)
    program = obs["program_text"]
    print("largest operations (ms/step, scope, category, first source "
          "file):", file=sys.stderr)
    seconds = obs["trace"].op_seconds()
    for name in sorted(seconds, key=seconds.get, reverse=True)[:40]:
        kind, direction = op_scope(program, name)
        print(f"  op {name} {ms(seconds[name]):.3f} {kind} "
              f"{direction or '-'} {program.category(name)} "
              f"{(program.source_files(name) or ['-'])[0]}", file=sys.stderr)

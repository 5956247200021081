"""What the ``lm_train.*`` readers share.  The language model's step runs
loops on the device (the attention core's block loops, the expert layer's
chunk loop): the profiler reports a loop and, inside its interval, the
operations of its body, and those belong to computations other than the
entry one.  So the join here takes every traced operation's own time (its
duration less that of the operations nested in it) and finds its
instruction in any computation of the compiled step's text.

Scopes read (``jax.named_scope`` in the program): ``WindowAttentionCore``,
``FullAttentionCore``, ``MoeRoute``, ``MoeShared``, ``LmHead``; the
criterion's own class name; and XLA's own name for the grouped-product
kernels that ``lax.ragged_dot`` becomes (``ragged-dot*``: XLA gives them no
``op_name``), booked as ``MoeExperts``.  A program without them makes every
reader return None."""
from __future__ import annotations

import collections
import sys

from benchmark import spanread
from benchmark.trace import categorize, op_short_name

EXPERT_KERNEL = "ragged-dot"
EXPERTS = "MoeExperts"
CORE_SCOPES = {"window": "WindowAttentionCore", "full": "FullAttentionCore"}
HEAD_SCOPES = ("LmHead", "TimeDistributedCriterion", "ClassNLLCriterion")


def self_seconds(events):
    """{operation name: summed own seconds} of one chip's events
    [(name, start_ns, dur_ns)]: an operation that encloses others (a loop,
    a call) keeps what its body does not account for."""
    own = collections.Counter()
    stack = []                          # [end, name, own_ns]
    def close(upto):
        while stack and stack[-1][0] <= upto:
            _, name, ns = stack.pop()
            own[name] += ns / 1e9
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([start + dur, op_short_name(name), dur])
    close(float("inf"))
    return own


def _instructions(program):
    if not hasattr(program, "_all_by_name"):
        program._all_by_name = {
            i["name"]: i for comp in program.comps.values() for i in comp}
    return program._all_by_name


def kind_of(program, name):
    """The scope that an operation's time is booked to."""
    if name.startswith(EXPERT_KERNEL):
        return EXPERTS
    instr = _instructions(program).get(name)
    if instr is None:
        return "unknown"
    parts = list(program._parts(instr))
    strong = [p for p in parts
              if categorize(p["opcode"], p["op_name"]) in spanread._STRONG]
    kinds = [spanread.scope_of(p["op_name"])[0] for p in (strong or parts)]
    named = [k for k in kinds if k is not None]
    if not named:
        return "unscoped"
    return collections.Counter(named).most_common(1)[0][0]


def scope_seconds(obs):
    """{scope: device seconds over the traced window}, averaged over the
    chips; None without a device trace and the program's text, or where
    nothing ran under one of this model's scopes."""
    if "_lm_scope_seconds" in obs:
        return obs["_lm_scope_seconds"]
    obs["_lm_scope_seconds"] = out = _scope_seconds(obs)
    if out is not None:
        steps = obs.get("steps") or 1
        print(f"device ms/step by scope (own time, busy "
              f"{sum(out.values()) / steps * 1e3:.3f}):", file=sys.stderr)
        for kind, s in sorted(out.items(), key=lambda kv: -kv[1]):
            print(f"  scope {kind} {s / steps * 1e3:.3f}", file=sys.stderr)
    return out


def _scope_seconds(obs):
    trace, program = obs.get("trace"), obs.get("program_text")
    if trace is None or program is None or not trace.device_ops:
        return None
    out = collections.Counter()
    for events in trace.device_ops.values():
        for name, seconds in self_seconds(events).items():
            out[kind_of(program, name)] += seconds / len(trace.device_ops)
    ours = set(CORE_SCOPES.values()) | {EXPERTS, "MoeRoute", "LmHead"}
    if not ours & set(out):
        return None
    _print_largest(obs, program)
    return dict(out)


def _print_largest(obs, program, top=30):
    """For PERF.md: the operations with the most own time, each with the
    scope it is booked to."""
    steps = obs.get("steps") or 1
    own = collections.Counter()
    for events in obs["trace"].device_ops.values():
        own.update(self_seconds(events))
    print("largest operations (own ms/step, scope, opcode):",
          file=sys.stderr)
    for name, s in own.most_common(top):
        instr = _instructions(program).get(name) or {}
        print(f"  op {name} {s / steps * 1e3:.3f} {kind_of(program, name)} "
              f"{instr.get('opcode', '-')}", file=sys.stderr)


def scoped_ms(obs, kinds):
    """Device ms per step under the scopes ``kinds``."""
    table = scope_seconds(obs)
    if table is None or not obs.get("steps"):
        return None
    picked = [table[k] for k in kinds if k in table]
    return sum(picked) / obs["steps"] * 1e3 if picked else None


def mean_assignments(obs):
    """Mean assignments held a step, by expert layer, from the counters;
    None where the step events carried none."""
    rows = (obs.get("expert_counters") or {}).get("assignments_held")
    if not rows:
        return None
    return [sum(col) / len(rows) for col in zip(*rows)]

"""From a profiler trace to numbers: device operations, busy union, idle
gaps named by the host span that covers them, and the join of device
operations to the compiled program's text (category and source file).

Adapted from ``tools/profile_step.py`` (``_trace_device_ops``,
``categorize``, the HLO join), reading the ``.xplane.pb`` with
``jax.profiler.ProfileData`` instead of the Chrome JSON.
"""
from __future__ import annotations

import collections
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"


class Trace:
    """What the reduction reads: per chip the device operations
    [(name, start_ns, dur_ns)], and the host's spans
    [(name, start_ns, dur_ns, thread)]."""

    def __init__(self, device_ops, host_spans):
        self.device_ops = device_ops        # {plane name: [(name, t, d)]}
        self.host_spans = host_spans

    @classmethod
    def from_file(cls, path):
        import jax
        data = jax.profiler.ProfileData.from_file(path)
        device_ops, host_spans = {}, []
        for plane in data.planes:
            if plane.name.startswith(DEVICE_PLANE):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        device_ops[plane.name] = [
                            (e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    for e in line.events:
                        host_spans.append((e.name, e.start_ns,
                                           e.duration_ns, line.name))
        return cls(device_ops, host_spans)

    @classmethod
    def from_dir(cls, trace_dir):
        files = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            return None
        return cls.from_file(files[-1])

    # -- device time -------------------------------------------------------
    def busy_intervals(self, plane):
        """Merged [start, end) intervals in which an operation ran."""
        merged = []
        for _, start, dur in sorted(self.device_ops[plane],
                                    key=lambda e: e[1]):
            end = start + dur
            if merged and start <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], end)
            else:
                merged.append([start, end])
        return merged

    def busy_s(self):
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.device_ops:
            return 0.0
        total = sum(end - start for plane in self.device_ops
                    for start, end in self.busy_intervals(plane))
        return total / len(self.device_ops) / 1e9

    def op_seconds(self):
        """{operation name (the text before ' = '): summed seconds},
        averaged over the chips."""
        out = collections.Counter()
        for events in self.device_ops.values():
            for name, _, dur in events:
                out[op_short_name(name)] += dur / 1e9
        n = max(len(self.device_ops), 1)
        return {k: v / n for k, v in out.items()}

    def idle_gaps(self, top=10):
        """The longest gaps between device operations on the first chip,
        each named by the host span that covers most of it:
        [(host span name or 'unattributed', seconds)]."""
        if not self.device_ops:
            return []
        plane = sorted(self.device_ops)[0]
        intervals = self.busy_intervals(plane)
        gaps = [(b[0] - a[1], a[1], b[0])
                for a, b in zip(intervals, intervals[1:]) if b[0] > a[1]]
        gaps.sort(reverse=True)
        spans = [s for s in self.host_spans
                 if not s[0].startswith(("$", "PjitFunction"))]
        named = collections.Counter()
        for dur, start, end in gaps[:100]:
            named[self._cover(start, end, spans)] += dur / 1e9
        return named.most_common(top)

    def _cover(self, start, end, spans=None):
        best, best_overlap = "unattributed", 0.0
        for name, s, d, _ in (self.host_spans if spans is None else spans):
            overlap = min(end, s + d) - max(start, s)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        return best if best_overlap >= 0.5 * (end - start) else \
            "unattributed"


def op_short_name(event_name):
    """'%fusion.25 = f32[...] fusion(...)' -> 'fusion.25'."""
    return event_name.split(" = ", 1)[0].lstrip("%")


# ---------------------------------------------------------------------------
# the compiled program's text: category and source file of every operation
# ---------------------------------------------------------------------------

_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")


def categorize(opcode, op_name):
    """``tools/profile_step.py categorize`` on opcode and jax op_name."""
    o = op_name
    if (opcode == "custom-call" and "tpu_custom_call" in o) \
            or "pallas" in o or "mosaic" in o.lower():
        return "PALLAS-KERNEL"
    if opcode == "select-and-scatter" or "select_and_scatter" in o:
        return "POOL-BWD"
    if "conv_general_dilated" in o or opcode == "convolution":
        return "CONV-BWD" if "transpose(" in o else "CONV-FWD"
    if opcode == "reduce-window" or "reduce_window" in o:
        return "POOL-FWD"
    if opcode == "dot" or "dot_general" in o:
        return "MATMUL"
    if "threefry" in o or "random" in o or "_uniform" in o \
            or "bernoulli" in o:
        return "RNG"
    if opcode in ("copy", "copy-start", "copy-done", "transpose", "bitcast"):
        return "LAYOUT"
    if opcode in ("all-reduce", "all-gather", "reduce-scatter"):
        return "COLLECTIVE"
    return "ELTWISE/OTHER"


class ProgramText:
    """Optimised HLO text, parsed far enough to say of each instruction of
    the entry computation what it is (``categorize``) and which source
    files its parts were traced from (the stack-frame tables at the head
    of the text)."""

    def __init__(self, text):
        self.frames = _frame_files(text)
        self.comps = collections.defaultdict(list)  # comp -> [instr]
        self.entry = None
        cur = None
        for line in text.splitlines():
            stripped = line.strip()
            m = _COMP_RE.match(stripped)
            if m and " = " not in stripped.split("(")[0]:
                cur = m.group(2)
                if m.group(1):
                    self.entry = cur
                continue
            m = _DEF_RE.match(line)
            if not m or cur is None:
                continue
            name, rest = m.groups()
            mo = _OPCODE_RE.search(" " + rest)
            if not mo:
                continue
            op_name = re.search(r'op_name="([^"]*)"', rest)
            frame = re.search(r"stack_frame_id=(\d+)", rest)
            calls = _CALLS_RE.search(rest)
            self.comps[cur].append({
                "name": name, "opcode": mo.group(1),
                "op_name": op_name.group(1) if op_name else "",
                "frame": int(frame.group(1)) if frame else None,
                "calls": calls.group(1) if calls else None})
        self.by_name = {i["name"]: i for i in self.comps.get(self.entry, [])}

    def _parts(self, instr, seen=None):
        """The instruction and, for a fusion or call, what it holds."""
        seen = seen if seen is not None else set()
        yield instr
        comp = instr["calls"]
        if comp and comp not in seen:
            seen.add(comp)
            for inner in self.comps.get(comp, []):
                yield from self._parts(inner, seen)

    def category(self, name):
        instr = self.by_name.get(name)
        if instr is None:
            return "UNKNOWN"
        cats = [categorize(p["opcode"], p["op_name"])
                for p in self._parts(instr)]
        for strong in ("PALLAS-KERNEL", "CONV-BWD", "CONV-FWD", "POOL-BWD",
                       "POOL-FWD", "MATMUL", "COLLECTIVE"):
            if strong in cats:
                return strong
        return cats[0]

    def source_files(self, name):
        """Base names of the source files in the stacks of the
        instruction's parts, most frequent first."""
        instr = self.by_name.get(name)
        if instr is None:
            return []
        count = collections.Counter()
        for p in self._parts(instr):
            for f in self.frames.get(p["frame"], ()):
                count[f] += 1
        return [f for f, _ in count.most_common()]


def _frame_files(text):
    """stack_frame_id -> base names of the files along the stack, from the
    FileNames / FileLocations / StackFrames tables of the HLO text."""
    head = text.split("\n\n%", 1)[0] if "StackFrames" in text[:200000] \
        else ""
    section, names, locs, frames = None, {}, {}, {}
    for line in head.splitlines():
        line = line.strip()
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            section = line
            continue
        m = re.match(r"^(\d+)\s+(.*)$", line)
        if not m or section is None:
            continue
        idx, rest = int(m.group(1)), m.group(2)
        if section == "FileNames":
            names[idx] = os.path.basename(rest.strip('"'))
        elif section == "FileLocations":
            f = re.search(r"file_name_id=(\d+)", rest)
            locs[idx] = int(f.group(1)) if f else None
        elif section == "StackFrames":
            loc = re.search(r"file_location_id=(\d+)", rest)
            parent = re.search(r"parent_frame_id=(\d+)", rest)
            # the printed parent is one more than the parent's id
            frames[idx] = (int(loc.group(1)) if loc else None,
                           int(parent.group(1)) - 1 if parent else 0)
    out = {}
    for idx in frames:
        files, cur, hops = [], idx, 0
        while cur in frames and hops < 64:
            loc, parent = frames[cur]
            name = names.get(locs.get(loc))
            if name and name not in files:
                files.append(name)
            if parent <= 0:
                break
            cur, hops = parent, hops + 1
        out[idx] = tuple(files)
    return out

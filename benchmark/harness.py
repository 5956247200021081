"""What every run shares: the manifest, the device check, the compile
meter, the measured window (with or without the profiler), the per-layer
readers and the result line.

A cell is found by its name in ``BENCHMARK.json``:
``benchmark/workloads/<cell>.json`` names the runner (one per front door)
and holds the traffic mix and the limits of the output check; the
configuration's file is the one the manifest lists; each per-layer metric
``m`` whose ``workloads`` name the cell is read by
``benchmark/metrics/<m>.py:read(obs)``.  Adding a cell, a configuration or
a metric is adding files and manifest entries.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

from benchmark import check
from benchmark import trace as trace_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class BenchmarkError(Exception):
    """The run cannot be made: no chip, unknown cell, unknown device."""


def load_manifest(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def find_cell(manifest, name):
    for entry in manifest["workloads"]:
        if entry["name"] == name:
            config = next(c for c in manifest["configs"]
                          if c["name"] == entry["config"])
            return entry, config
    raise BenchmarkError(
        f"no workload {name!r} in BENCHMARK.json (has: "
        f"{[w['name'] for w in manifest['workloads']]})")


def device_info():
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def require_chip(chips):
    """No accelerator, or fewer chips than the cell asks for, is an error
    and never a CPU run."""
    info = device_info()
    if info["platform"] != "tpu":
        raise BenchmarkError(
            f"JAX runs on {info['platform']!r}, not on a TPU: the benchmark "
            "measures the chip and has no CPU fallback")
    if info["count"] < chips:
        raise BenchmarkError(
            f"the cell needs {chips} chip(s), JAX sees {info['count']}")
    return info


def peaks_for(kind):
    table = load_json("benchmark", "peaks.json")
    known = max((k for k in table if k != "source" and kind.startswith(k)),
                key=len, default=None)
    if known is None:
        raise BenchmarkError(
            f"no datasheet peaks for device kind {kind!r} in "
            "benchmark/peaks.json")
    return table[known]


class CompileMeter:
    """Backend-compile seconds and compile requests, from JAX's own
    monitoring events (``chip_smoke.py CompileMeter``): what set-up
    compiled, and that the window compiled nothing."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def requests(self):
        """Programs asked of the compiler or of its cache so far."""
        return self.compiles + self.cache_hits


class MemoryWatch:
    """Peak HBM held on the fullest chip, over the window.  The TPU's
    allocator counts a running program's temporaries (``bytes_reserved``)
    apart from the arrays (``bytes_in_use``), and keeps a peak of each; the
    two peaks need not fall together, so their sum is only an upper bound.
    A thread reads both from one ``memory_stats()`` call every ``period``
    seconds while the window is open: the largest sum it saw is memory that
    was really held at one moment.  ``memory_peak_bytes`` is that, and never
    under either of the allocator's own peaks."""

    def __init__(self, period=0.1):
        import threading
        self.period = period
        self.seen = 0               # largest in_use + reserved of one read
        self.reads = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name="benchmark-memory-watch")

    @staticmethod
    def _stats():
        import jax
        return [d.memory_stats() or {} for d in jax.local_devices()]

    def _watch(self):
        while not self._stop.wait(self.period):
            self.seen = max([self.seen] + [
                int(s.get("bytes_in_use", 0)) + int(s.get("bytes_reserved", 0))
                for s in self._stats()])
            self.reads += 1

    def start(self):
        self._thread.start()

    def stop(self):
        """Ends the watch; returns what goes into the result's ``device``:
        ``memory_peak_bytes`` and, beside it, the allocator's two peaks."""
        self._stop.set()
        self._thread.join()
        stats = self._stats()
        in_use = max((int(s.get("peak_bytes_in_use", 0)) for s in stats),
                     default=0)
        reserved = max((int(s.get("peak_bytes_reserved", 0)) for s in stats),
                       default=0)
        return {"memory_peak_bytes": max(self.seen, in_use, reserved),
                "peak_bytes_in_use": in_use,
                "peak_bytes_reserved": reserved,
                "memory_reads": self.reads}


def apply_sizes(cell, config, sizes):
    """(traffic, configuration, limits) of a run.  ``sizes`` is a test's:
    a rehearsal at toy sizes overrides keys of the traffic mix and of the
    configuration, and brings under ``check`` the limits that its sizes
    read (the cell's own are set at the cell's own size)."""
    sizes = dict(sizes or {})
    limits = dict(cell["check"], **sizes.pop("check", {}))
    pick = lambda d: dict(d, **{k: v for k, v in sizes.items() if k in d})
    return pick(cell["traffic"]), pick(config), limits


class Context:
    """One run of one cell, as its runner sees it: ``traffic`` (the mix),
    ``config`` (the configuration's file) and ``limits`` (of the output
    check), the seed and the window."""

    def __init__(self, cell, entry, config, seed, seconds, traced, t0,
                 sizes=None):
        self.cell = cell            # the cell's own file
        self.entry = entry          # its manifest entry
        self.seed = int(seed)
        self.traced = bool(traced)
        # a traced run measures a shorter window where the cell's file
        # caps it: a trace of many small steps is large
        cap = cell.get("trace_seconds") if self.traced else None
        self.seconds = min(float(seconds), cap) if cap else float(seconds)
        self.t0 = t0
        self.traffic, self.config, self.limits = apply_sizes(
            cell, config, sizes)
        self.meter = CompileMeter()
        self.parts = {}             # set-up seconds by part
        self._mark = t0
        self.t_open = self.t_close = None
        self.trace_open = self.trace_close = None
        self.trace = None
        self.memory = None          # MemoryWatch.stop()'s readings
        self._watch = MemoryWatch()
        self._compile_mark = None
        self.compiles_in_window = None

    def lap(self, part):
        """Book the time since the last lap to a part of set-up."""
        now = time.perf_counter()
        self.parts[part] = self.parts.get(part, 0.0) + now - self._mark
        self._mark = now

    def open_window(self):
        self.parts["compile_in_setup"] = self.meter.seconds
        if self.traced:
            import jax
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(TRACE_DIR)
            self.trace_open = time.perf_counter()
        self._compile_mark = self.meter.requests()
        self._watch.start()
        self.t_open = time.perf_counter()
        return self.t_open

    def close_window(self):
        """The work of the window is done and waited for."""
        self.t_close = time.perf_counter()
        self.compiles_in_window = self.meter.requests() - self._compile_mark
        if self.traced:
            import jax
            self.trace_close = time.perf_counter()
            jax.profiler.stop_trace()
        self.memory = self._watch.stop()
        if self.traced:
            self.trace = trace_mod.Trace.from_dir(TRACE_DIR)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        return self.t_close

    @property
    def setup_s(self):
        return self.t_open - self.t0


def load_reader(metric_name):
    path = os.path.join(ROOT, "benchmark", "metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + metric_name.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def per_layer_metrics(manifest, cell_name, obs):
    """Every per-layer metric whose ``workloads`` name the cell (or that
    has none and moves a metric the cell reports); a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in manifest["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        value = load_reader(m["name"])(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown(ctx, obs):
    tr = ctx.trace
    if tr is None or not tr.device_ops:
        return None
    program = obs.get("program_text")
    ops = {}
    for name, seconds in tr.op_seconds().items():
        label = program.category(name) if program is not None else name
        if label == "UNKNOWN":
            label = name
        ops[label] = ops.get(label, 0.0) + seconds
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in tr.idle_gaps(10)]}


def run_cell(name, seed, seconds, traced, t0=None, sizes=None,
             manifest=None, cell=None):
    """Drive one run of one cell and return the result object (the last
    line of standard output).  ``sizes`` is for the tests: toy sizes for a
    rehearsal on the CPU, which skips the look for a chip.  So are
    ``manifest`` and ``cell``: a test's own entries and cell, in place of
    ``BENCHMARK.json`` and of the cell's file."""
    t0 = time.perf_counter() if t0 is None else t0
    manifest = manifest or load_manifest()
    entry, config_entry = find_cell(manifest, name)
    cell = cell or load_json("benchmark", "workloads", name + ".json")
    config = load_json(config_entry["file"])
    ctx = Context(cell, entry, config, seed, seconds, traced, t0,
                  sizes=sizes)
    runner = importlib.import_module("benchmark.runners." + cell["runner"])
    out = runner.run(ctx)

    info = device_info()
    device = dict(info, **ctx.memory)
    checks = list(out["checks"])
    checks.append(("compiles_in_window", ctx.compiles_in_window, 0))
    correct, table = check.verdict(checks)
    result = {"correct": correct, "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    reported = {m["name"]: m for m in manifest["end_to_end"]
                if "workloads" not in m or name in m["workloads"]}
    if not traced:
        values = dict(out["end_to_end"], setup_s=ctx.setup_s)
        result["metrics"] = {
            k: {"value": float(values[k]), "unit": m["unit"]}
            for k, m in reported.items()}
    else:
        obs = dict(out["obs"])
        obs.update(trace=ctx.trace, config=ctx.config, cell=cell,
                   window_s=ctx.t_close - ctx.t_open,
                   traced_s=(ctx.trace_close - ctx.trace_open),
                   peaks=(peaks_for(info["kind"])
                          if info["platform"] == "tpu" else None))
        result["metrics"] = per_layer_metrics(manifest, name, obs)
        if ctx.trace is not None and ctx.trace.device_ops:
            device["busy_s"] = ctx.trace.busy_s()
            device["window_s"] = obs["traced_s"]
        bd = breakdown(ctx, obs)
        if bd:
            result["breakdown"] = bd
    result["device"] = device
    result["setup_parts_s"] = {k: round(v, 3) for k, v in ctx.parts.items()}
    result["setup_s"] = ctx.setup_s
    if out.get("detail"):
        result["detail"] = out["detail"]
    result["check"] = table          # each number beside its limit: last
    for key, row in table.items():
        print(f"check {key}: {row['value']:.6g} (limit {row['limit']:.6g})",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    return result

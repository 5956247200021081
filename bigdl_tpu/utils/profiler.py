"""Profiling utilities (SURVEY.md §5.1).

The reference stacks per-module wall-clock timers (AbstractModule
forwardTime/backwardTime), phase metrics (optim/Metrics.scala) and
throughput logs.  Those exist here too (Module.get_times, optim.Metrics);
this module adds the TPU-native layer: ``jax.profiler`` device traces and
annotated host ranges viewable in XProf/TensorBoard.
"""
from __future__ import annotations

from contextlib import contextmanager

import jax


def start_trace(log_dir: str):
    """Begin a device trace (open in xprof / tensorboard-profile)."""
    jax.profiler.start_trace(log_dir)


def stop_trace():
    jax.profiler.stop_trace()


@contextmanager
def trace(log_dir: str):
    start_trace(log_dir)
    try:
        yield
    finally:
        stop_trace()


@contextmanager
def annotation(name: str):
    """Plain named trace range: phase spans (obs/spans.py) and the
    prefetch threads (dataset/prefetch.py) use this so data-load/dispatch/
    validate line up in XProf under the same names as the event log."""
    with jax.profiler.TraceAnnotation(name):
        yield


def device_memory_stats():
    """Per-device HBM usage, when the backend exposes it."""
    stats = {}
    for d in jax.devices():
        try:
            stats[str(d)] = d.memory_stats()
        except Exception:
            stats[str(d)] = None
    return stats


def format_module_times(model, top_n: int = 20) -> str:
    """Pretty per-module forward/backward table
    (ref Container.getTimes Container.scala:71-78)."""
    rows = sorted(model.get_times(), key=lambda r: -(r[1] + r[2]))[:top_n]
    lines = [f"{'module':<40} {'fwd_s':>10} {'bwd_s':>10}"]
    for mod, fwd, bwd in rows:
        lines.append(f"{mod.get_name():<40} {fwd:>10.4f} {bwd:>10.4f}")
    return "\n".join(lines)

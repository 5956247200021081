"""Crash diagnostics: dump a postmortem bundle on the way down
(docs/observability.md).

The failure paths PR 1 built — watchdog peer-death exit, SIGTERM
preemption, the non-finite-gradient abort threshold — all end a run
from code that knows WHY, but until now that knowledge died with the
process (one log line, then ``os._exit``).  ``dump_crash_bundle`` turns
the last moments into a directory an operator (or the next CI run) can
read:

    crash-<reason>-p<proc>-<pid>/
      reason.txt     what tripped, free text
      events.jsonl   the event ring buffer's last N events (obs/events)
      spans.json     the loops' span rings' tails (obs/spans): the last
                     iterations' (path, start, end, step) and the span
                     open when this was dumped
      memory.json    per-device HBM stats (utils/profiler)
      config.json    BIGDL_*/JAX_* env, jax version, process topology
      threads.txt    Python stack of every live thread (where was the
                     main thread blocked? usually: inside a dead
                     collective)
      extra.json     caller-provided context (straggler window, streak)

Every step is individually best-effort: a diagnostics bug must never
mask the real failure, so this function cannot raise.
"""
from __future__ import annotations

import json
import logging
import os
import sys
import tempfile
import time
import traceback

logger = logging.getLogger("bigdl_tpu.obs")


def _resolve_dir(run_dir):
    if run_dir:
        return run_dir
    from bigdl_tpu.obs import events as events_mod
    log = events_mod.get()
    if log is not None and log.run_dir:
        return log.run_dir
    env = os.environ.get(events_mod.ENV_DIR, "").strip()
    if env:
        return env
    return tempfile.mkdtemp(prefix="bigdl_obs_")


def thread_stacks() -> str:
    """Python stack of every live thread — the one artifact that tells a
    hung-collective death from a data-loader deadlock."""
    import threading
    names = {t.ident: t.name for t in threading.enumerate()}
    parts = []
    for ident, frame in sorted(sys._current_frames().items()):
        parts.append(f"--- thread {names.get(ident, '?')} ({ident}) ---")
        parts.append("".join(traceback.format_stack(frame)))
    return "\n".join(parts)


def config_snapshot() -> dict:
    """Env flags + versions + topology: enough to reproduce the run's
    configuration from the bundle alone."""
    snap = {"argv": list(sys.argv),
            "python": sys.version.split()[0],
            "cwd": os.getcwd(),
            "env": {k: v for k, v in sorted(os.environ.items())
                    if k.startswith(("BIGDL_", "JAX_", "XLA_"))}}
    try:
        import jax
        snap["jax"] = jax.__version__
        snap["process_index"] = jax.process_index()
        snap["process_count"] = jax.process_count()
        snap["local_devices"] = [str(d) for d in jax.local_devices()]
    except Exception as e:
        snap["jax"] = f"unavailable: {e!r}"
    return snap


def _write(path, write_fn):
    try:
        with open(path, "w") as f:
            write_fn(f)
    except Exception as e:  # pragma: no cover - disk-full territory
        logger.warning("crash bundle: %s failed: %s", path, e)


def dump_crash_bundle(reason: str, run_dir: str | None = None,
                      extra: dict | None = None,
                      texts: dict | None = None) -> str | None:
    """Write the bundle; returns its path (None only if even the
    directory could not be created).  Safe from signal handlers and
    daemon threads; never raises.  ``texts`` maps extra filenames to
    raw text bodies (e.g. a dead replica's ``stderr.txt`` tail)."""
    try:
        from bigdl_tpu.obs import events as events_mod
        if not events_mod.enabled():
            # BIGDL_OBS=0 is the documented hard-off switch: no stray
            # temp directories from abort/preemption/watchdog paths
            logger.info("crash bundle skipped: obs disabled (%s)", reason)
            return None
        base = _resolve_dir(run_dir)
        log = events_mod.get()
        proc = log.process_index() if log is not None else 0
        slug = "".join(c if c.isalnum() or c in "-_" else "-"
                       for c in reason)[:48]
        path = os.path.join(base, f"crash-{slug}-p{proc}-{os.getpid()}")
        os.makedirs(path, exist_ok=True)
    except Exception as e:
        logger.error("crash bundle: could not create directory: %s", e)
        return None

    # the bundle's own event first, so it rides the ring dump below and
    # the surviving JSONL stream points at the directory
    if log is not None:
        log.emit("crash_bundle", reason=reason, path=path)

    _write(os.path.join(path, "reason.txt"),
           lambda f: f.write(f"{reason}\nat {time.strftime('%Y-%m-%dT%H:%M:%S')}\n"))
    if log is not None:
        _write(os.path.join(path, "events.jsonl"), lambda f: f.writelines(
            json.dumps(e, default=events_mod._jsonable) + "\n"
            for e in log.ring_events()))
    try:
        from bigdl_tpu.obs import spans as spans_mod
        tails = spans_mod.timeline_tails()
    except Exception as e:
        tails = [{"unavailable": repr(e)}]
    if tails:
        _write(os.path.join(path, "spans.json"),
               lambda f: json.dump(tails, f, default=repr))
    _write(os.path.join(path, "threads.txt"),
           lambda f: f.write(thread_stacks()))
    _write(os.path.join(path, "config.json"),
           lambda f: json.dump(config_snapshot(), f, indent=1, default=repr))
    try:
        from bigdl_tpu.utils.profiler import device_memory_stats
        stats = device_memory_stats()
    except Exception as e:
        stats = {"unavailable": repr(e)}
    _write(os.path.join(path, "memory.json"),
           lambda f: json.dump(stats, f, indent=1, default=repr))
    if extra:
        _write(os.path.join(path, "extra.json"),
               lambda f: json.dump(extra, f, indent=1, default=repr))
    for fname, body in (texts or {}).items():
        _write(os.path.join(path, os.path.basename(fname)),
               lambda f, b=body: f.write(b))
    logger.error("crash bundle written: %s (%s)", path, reason)
    return path

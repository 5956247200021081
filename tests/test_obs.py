"""Observability subsystem tests (docs/observability.md, pytest -m obs).

Covers the four obs parts — in-jit taps, structured events, spans,
crash diagnostics — plus the TensorBoard scalar export, the report
tool, and the satellite fixes (Metrics.timer exception safety,
warn_every cache reset/env override, utils/profiler coverage).

The overhead contract (ISSUE 3 acceptance): with taps ON the train
step is still ONE jitted dispatch and the host materializes tap values
only at cadence boundaries — asserted by the jit-count and
materialization-audit tests in TestTapsDispatch.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset import DataSet, Sample
from bigdl_tpu.dataset.transformer import SampleToBatch
from bigdl_tpu.obs import events as obs_events
from bigdl_tpu.obs import taps as obs_taps
from bigdl_tpu.obs.diagnostics import dump_crash_bundle
from bigdl_tpu.obs.events import validate_event
from bigdl_tpu.obs.spans import SpanTracker
from bigdl_tpu.obs.summary import (TrainSummary, ValidationSummary,
                                   read_scalars)
from bigdl_tpu.optim import (DistriOptimizer, LocalOptimizer, Metrics,
                             NonFiniteGradError, Top1Accuracy,
                             max_iteration, several_iteration)
from bigdl_tpu.optim.metrics import Metrics as MetricsClass
from bigdl_tpu.utils.random import set_seed
from bigdl_tpu.utils.table import T

pytestmark = pytest.mark.obs


def _data(n=16, d=6, classes=3, batch=16):
    rng = np.random.RandomState(0)
    w = rng.randn(d, classes)
    xs = rng.randn(n, d).astype(np.float32)
    ys = (xs @ w).argmax(1) + 1.0
    samples = [Sample(x, np.asarray([y])) for x, y in zip(xs, ys)]
    return DataSet.array(samples) >> SampleToBatch(batch)


def _mlp(d=6, classes=3):
    set_seed(7)
    return nn.Sequential(nn.Linear(d, 8), nn.Tanh(),
                         nn.Linear(8, classes), nn.LogSoftMax())


def _opt(model=None, ds=None, distri=False, **kw):
    opt_cls = DistriOptimizer if distri else LocalOptimizer
    opt = opt_cls(model or _mlp(), ds or _data(),
                  nn.ClassNLLCriterion(), **kw)
    opt.set_state(T(learningRate=0.5))
    return opt


# ---------------------------------------------------------------------------
# taps: in-jit scalar computation
# ---------------------------------------------------------------------------

class TestTapsCompute:
    def test_matches_numpy(self):
        grads = {"a": jnp.asarray([3.0, 4.0]),
                 "b": jnp.asarray([[1.0, -2.0]])}
        params = {"a": jnp.asarray([1.0, 1.0]),
                  "b": jnp.asarray([[2.0, 2.0]])}
        newp = {"a": jnp.asarray([1.1, 0.9]),
                "b": jnp.asarray([[2.0, 2.2]])}
        t = obs_taps.compute(grads, params, newp)
        assert set(t) == set(obs_taps.TAP_NAMES)
        np.testing.assert_allclose(float(t["grad_norm"]),
                                   np.sqrt(9 + 16 + 1 + 4), rtol=1e-6)
        pn = np.sqrt(1 + 1 + 4 + 4)
        np.testing.assert_allclose(float(t["param_norm"]), pn, rtol=1e-6)
        dn = np.sqrt(0.01 + 0.01 + 0.04)
        np.testing.assert_allclose(float(t["update_ratio"]), dn / pn,
                                   rtol=1e-5)
        assert float(t["nonfinite_grads"]) == 0.0

    def test_counts_nonfinite_elements(self):
        grads = {"a": jnp.asarray([np.nan, 1.0, np.inf])}
        p = {"a": jnp.asarray([1.0, 1.0, 1.0])}
        t = obs_taps.compute(grads, p, p)
        assert float(t["nonfinite_grads"]) == 2.0
        # skipped step (new == old): the applied update really was zero
        assert float(t["update_ratio"]) == 0.0

    def test_env_gating(self, monkeypatch):
        monkeypatch.setenv(obs_taps.ENV_TAPS, "0")
        assert not obs_taps.enabled()
        assert obs_taps.enabled(True)        # explicit override wins
        monkeypatch.setenv(obs_taps.ENV_CADENCE, "25")
        assert obs_taps.cadence() == 25
        assert obs_taps.cadence(5) == 5


class TestTapsDispatch:
    """The ISSUE 3 overhead contract."""

    def test_step_with_taps_is_single_jit_dispatch(self, monkeypatch):
        calls = []
        real_jit = jax.jit

        def counting_jit(fn, *a, **kw):
            calls.append(fn)
            return real_jit(fn, *a, **kw)

        monkeypatch.setattr(jax, "jit", counting_jit)
        opt = _opt()
        opt.set_taps(enabled=True, cadence=10)
        step = opt._build_step()
        assert len(calls) == 1, \
            "taps must ride the existing jit step, not add a program"
        # distri plain path: also exactly one jit
        calls.clear()
        dopt = _opt(distri=True)
        dopt.set_taps(enabled=True, cadence=10)
        dopt._build_step()
        assert len(calls) == 1

    def test_taps_are_device_values_until_cadence(self):
        """Host materialization happens at cadence boundaries and at the
        final flush — never per step (the audit trail the loop's
        TapsMonitor keeps)."""
        opt = _opt()
        opt.set_taps(enabled=True, cadence=3)
        opt.set_end_when(max_iteration(7))
        opt.optimize()
        mon = opt._taps_monitor
        # boundaries at neval 3 and 6; 7 is the run-end flush
        assert list(mon.materialized_steps) == [3, 6, 7]
        for _, vals in mon.history:
            assert set(vals) == set(obs_taps.TAP_NAMES)
            assert all(np.isfinite(v) for v in vals.values())

    def test_taps_off_is_empty(self):
        opt = _opt()
        opt.set_taps(enabled=False)
        opt.set_end_when(max_iteration(2))
        opt.optimize()
        assert list(opt._taps_monitor.history) == []

    def test_monitor_flush_covers_short_runs(self):
        """Default cadence 10 with a 4-step run: the tail flush still
        logs exactly one sample."""
        opt = _opt()
        opt.set_taps(enabled=True, cadence=10)
        opt.set_end_when(max_iteration(4))
        opt.optimize()
        assert list(opt._taps_monitor.materialized_steps) == [4]


class TestTapsTraining:
    def test_local_taps_see_injected_nan(self, obs_run_dir):
        from bigdl_tpu.resilience import faults
        faults.configure("nan_grad@at=2")
        try:
            opt = _opt()
            opt.set_taps(enabled=True, cadence=1)
            opt.set_nonfinite_policy(0)
            opt.set_end_when(max_iteration(4))
            opt.optimize()
        finally:
            faults.clear()
        hist = dict(opt._taps_monitor.history)
        assert hist[2]["nonfinite_grads"] > 0
        assert hist[2]["update_ratio"] == 0.0      # step was skipped
        assert hist[3]["nonfinite_grads"] == 0.0
        assert hist[3]["update_ratio"] > 0.0
        # ...and the event stream shows the fault then the skip
        ev = obs_events.read_events(obs_events.get().path)
        assert any(e["type"] == "fault" and e["site"] == "nan_grad"
                   for e in ev)
        assert any(e["type"] == "step" and e.get("skips") for e in ev)

    def test_distri_shard_map_taps_match_plain_jit(self):
        """The pmean-merged shard_map taps must agree with the plain-jit
        taps for identical runs (no straggler, no compression loss
        beyond bf16 wire rounding)."""
        a = _opt(model=_mlp(), distri=True)
        a.set_taps(enabled=True, cadence=1)
        a.set_end_when(max_iteration(2))
        a.optimize()
        b = _opt(model=_mlp(), distri=True, gradient_compression="bf16")
        b.set_taps(enabled=True, cadence=1)
        b.set_end_when(max_iteration(2))
        b.optimize()
        ta, tb = a._taps_monitor.last(), b._taps_monitor.last()
        assert ta is not None and tb is not None
        np.testing.assert_allclose(ta["grad_norm"], tb["grad_norm"],
                                   rtol=0.05)
        np.testing.assert_allclose(ta["param_norm"], tb["param_norm"],
                                   rtol=1e-3)

    def test_chunked_dispatch_taps(self):
        opt = _opt()
        opt.set_iterations_per_dispatch(2)
        opt.set_taps(enabled=True, cadence=1)
        opt.set_end_when(max_iteration(4))
        opt.optimize()
        # neval0 = 1, 3 → cadence 1 materializes each dispatch
        assert list(opt._taps_monitor.materialized_steps) == [1, 3]

    def test_chunked_dispatch_cadence_misaligned(self):
        """Chunk starts never land on an exact cadence multiple (neval0
        = 1, 3, 5, ...): the elapsed-iterations gate must still fire
        roughly every cadence steps instead of never (the trigger-style
        chunk-boundary trap)."""
        opt = _opt()
        opt.set_iterations_per_dispatch(2)
        opt.set_taps(enabled=True, cadence=3)
        opt.set_end_when(max_iteration(8))
        opt.optimize()
        # pushes at 1, 3, 5, 7; >=3 iterations elapse at 3 and again at 7
        assert list(opt._taps_monitor.materialized_steps) == [3, 7]

    def test_monitor_gate_never_starves(self):
        """Audit every (n_disp, cadence) pairing the repo uses: the gate
        must fire within 2*cadence pushed steps."""
        for n in (1, 2, 5, 8, 32):
            for cad in (1, 3, 10):
                mon = obs_taps.TapsMonitor(cad, True)
                fired = []
                for step in range(1, 200, n):
                    if mon.push(step, {"grad_norm": jnp.float32(0)}):
                        fired.append(step)
                assert fired, (n, cad)
                gaps = np.diff([0] + fired)
                assert gaps.max() <= 2 * max(cad, n), (n, cad, fired[:5])


# ---------------------------------------------------------------------------
# events: schema + log
# ---------------------------------------------------------------------------

class TestEvents:
    def _env(self, **kw):
        base = {"v": 1, "ts": 1.0, "proc": 0}
        base.update(kw)
        return base

    def test_validate_accepts_known_types(self):
        validate_event(self._env(type="step", step=1, loss=0.5, lr=0.1,
                                 throughput=10.0))
        validate_event(self._env(type="fault", site="nan_grad", step=3))
        validate_event(self._env(type="watchdog", stale=[2]))

    def test_validate_rejects(self):
        with pytest.raises(ValueError, match="missing common"):
            validate_event({"type": "step"})
        with pytest.raises(ValueError, match="unknown event type"):
            validate_event(self._env(type="nope"))
        with pytest.raises(ValueError, match="missing"):
            validate_event(self._env(type="step", step=1))
        with pytest.raises(ValueError, match="newer"):
            validate_event(self._env(type="step", v=99, step=1, loss=0.0,
                                     lr=0.0, throughput=0.0))

    def test_ring_and_file_sink(self, tmp_path):
        log = obs_events.EventLog(run_dir=str(tmp_path), ring=3,
                                  process_index=5)
        for i in range(5):
            log.emit("fault", site="nan_grad", step=i)
        ring = log.ring_events()
        assert [e["step"] for e in ring] == [2, 3, 4]   # maxlen 3
        events = obs_events.read_events(log.path)
        assert len(events) == 5 and all(e["proc"] == 5 for e in events)
        for e in events:
            validate_event(e)
        log.close()

    def test_disabled_by_master_switch(self, monkeypatch):
        monkeypatch.setenv(obs_events.ENV_OBS, "0")
        obs_events.reset()
        try:
            assert obs_events.get() is None
            assert obs_events.emit("fault", site="nan_grad", step=1) is None
        finally:
            obs_events.reset()

    def test_numpy_values_serialize(self, tmp_path):
        log = obs_events.EventLog(run_dir=str(tmp_path), process_index=0)
        log.emit("step", step=np.int64(3), loss=np.float32(0.5),
                 lr=jnp.float32(0.1), throughput=1.0)
        (e,) = obs_events.read_events(log.path)
        assert e["loss"] == pytest.approx(0.5)
        log.close()

    def test_training_stream_validates(self, obs_run_dir):
        opt = _opt(distri=True)
        opt.set_taps(enabled=True, cadence=2)
        opt.set_validation(several_iteration(2), _data(),
                           [Top1Accuracy()])
        opt.set_end_when(max_iteration(4))
        opt.optimize()
        events = obs_events.read_events(obs_events.get().path)
        types = [e["type"] for e in events]
        for e in events:
            validate_event(e)
        assert types[0] == "run_start"
        assert types[-1] == "run_end"
        assert types.count("step") == 4
        assert "validation" in types and "phase" in types
        steps = [e for e in events if e["type"] == "step"]
        assert all({"step", "loss", "lr", "throughput"} <= set(e)
                   for e in steps)
        assert any("taps" in e for e in steps)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class TestSpans:
    def test_nesting_and_rows(self):
        m = MetricsClass()
        tr = SpanTracker(m)
        with tr.span("dispatch"):
            with tr.span("wait"):
                pass
        with tr.span("data-load"):
            pass
        rows = {path: count for path, _, _, _, count in tr.rows()}
        assert rows == {"dispatch": 1, "dispatch/wait": 1, "data-load": 1}
        # tree order, a child at its parent's depth + 1
        assert [(path, depth) for path, depth, _, _, _ in tr.rows()] == [
            ("data-load", 0), ("dispatch", 0), ("dispatch/wait", 1)]
        # nested paths stay local; top-level phases are distributed
        assert "span: dispatch" in m._distributed
        assert "span: dispatch/wait" not in m._distributed

    def test_phase_names_pre_declared_on_every_process(self):
        """The deadlock-safety contract: constructing the tracker alone
        (no spans ever entered) still registers the full phase-name set,
        so collect_per_node walks identical names on every process."""
        m = MetricsClass()
        SpanTracker(m)
        from bigdl_tpu.obs.spans import PHASES
        assert {f"span: {p}" for p in PHASES} <= m._distributed

    def test_per_host_report_single_process(self):
        m = MetricsClass()
        tr = SpanTracker(m)
        with tr.span("dispatch"):
            pass
        rep = tr.per_host_report()
        assert "host0" in rep and "dispatch" in rep and "checkpoint" in rep

    def test_phase_events(self, tmp_path):
        log = obs_events.EventLog(run_dir=str(tmp_path), process_index=0)
        m = MetricsClass()
        tr = SpanTracker(m)
        with tr.span("dispatch"):
            pass
        tr.emit_phase_events(log, step=7)
        (e,) = obs_events.read_events(log.path)
        validate_event(e)
        assert e["name"] == "dispatch" and e["step"] == 7
        assert e["seconds"] >= 0
        log.close()


class TestLoopSpans:
    """The loop's own host time is named: ``flush`` and ``bookkeep`` beside
    ``dispatch``/``host-wait``, and a ``loop`` counter (an iteration's
    wall) so that what no span covers is measured."""

    MAIN = ("data-load", "dispatch", "host-wait", "flush", "bookkeep",
            "validate", "checkpoint")

    def _run(self, monkeypatch, distri, prefetch_on, steps=12):
        import time

        from bigdl_tpu.dataset import prefetch as pf
        from bigdl_tpu.dataset.transformer import FuncTransformer
        monkeypatch.setenv(pf.ENV_PREFETCH, "1" if prefetch_on else "0")

        def slow(batch):            # a feed that binds, as in the cell
            time.sleep(0.03)
            return batch

        opt = _opt(ds=_data(n=64, batch=16) >> FuncTransformer(slow),
                   distri=distri)
        opt.set_validation(several_iteration(5), _data(), [Top1Accuracy()])
        opt.set_end_when(max_iteration(steps))
        opt.optimize()
        return {path: (total, count)
                for path, _, _, total, count in opt.spans.rows()}

    @pytest.mark.parametrize("distri", [False, True])
    @pytest.mark.parametrize("prefetch_on", [True, False])
    def test_unnamed_time_is_under_two_percent_of_the_loop(
            self, monkeypatch, distri, prefetch_on):
        from bigdl_tpu.dataset import prefetch as pf
        spans = self._run(monkeypatch, distri, prefetch_on)
        loop, iterations = spans["loop"]
        assert iterations == 12
        assert spans["flush"][1] >= 1 and spans["flush"][0] > 0
        # two segments an iteration: counters + rollover, trigger probes
        assert spans["bookkeep"][1] == 2 * iterations
        assert spans["validate"][1] == 2
        if not distri:
            # the local loop's dispatch, split where its work divides,
            # and the two counters read between the halves
            for path in ("dispatch/prepare", "dispatch/call",
                         "dispatch/in-flight"):
                assert spans[path][1] == iterations, path
            assert 1 <= spans["dispatch/device-empty"][1] <= iterations
            assert spans["dispatch/prepare"][0] + spans["dispatch/call"][0] \
                <= spans["dispatch"][0]
        inline_h2d = spans["h2d"][0] - spans.get(pf.H2D, (0.0, 0))[0]
        named = sum(spans.get(p, (0.0, 0))[0] for p in self.MAIN) \
            + inline_h2d
        assert 0 <= loop - named < 0.02 * loop, (loop, named, spans)
        if prefetch_on:
            # the transfer thread's wall, under its own path and credited
            # to the top-level phase the per-host table shows
            assert spans[pf.H2D] == pytest.approx(spans["h2d"])
            assert spans[pf.FETCH][1] >= iterations
            assert pf.FETCH + "/stage/1:FuncTransformer" in spans
        else:
            assert pf.H2D not in spans and pf.FETCH not in spans

    def test_duplicate_timers_are_gone(self, monkeypatch):
        """``data-load``/``dispatch`` are the one record of fetch and
        train time; the flat ``Metrics`` entries that repeated them and
        the unused ``step_annotation`` are out."""
        from bigdl_tpu.utils import profiler
        opt = _opt()
        opt.set_end_when(max_iteration(2))
        opt.optimize()
        names = set(opt.metrics._sums)
        assert "data fetch time" not in names and "train time" not in names
        assert "span: data-load" in names and "span: dispatch" in names
        assert not hasattr(profiler, "step_annotation")


# ---------------------------------------------------------------------------
# diagnostics: crash bundles
# ---------------------------------------------------------------------------

class TestDiagnostics:
    def test_bundle_contents(self, obs_run_dir):
        obs_events.emit("fault", site="nan_grad", step=1)
        path = dump_crash_bundle("unit-test", extra={"k": 1})
        assert path and os.path.isdir(path)
        files = sorted(os.listdir(path))
        assert {"reason.txt", "events.jsonl", "config.json",
                "memory.json", "threads.txt", "extra.json"} <= set(files)
        assert "unit-test" in open(os.path.join(path, "reason.txt")).read()
        ring = [json.loads(l) for l in
                open(os.path.join(path, "events.jsonl"))]
        assert any(e["type"] == "fault" for e in ring)
        assert any(e["type"] == "crash_bundle" for e in ring)
        cfg = json.load(open(os.path.join(path, "config.json")))
        assert "env" in cfg and "jax" in cfg
        stacks = open(os.path.join(path, "threads.txt")).read()
        assert "test_bundle_contents" in stacks   # this very frame
        assert json.load(open(os.path.join(path, "extra.json"))) == {"k": 1}

    def test_watchdog_trip_dumps_bundle(self, obs_run_dir, monkeypatch,
                                        tmp_path):
        from bigdl_tpu.resilience.watchdog import Watchdog
        exits = []
        monkeypatch.setattr(os, "_exit", lambda code: exits.append(code))
        dog = Watchdog(str(tmp_path / "hb"), process_index=0,
                       n_processes=2, interval=0.1, timeout=0.3)
        dog._default_on_stale([1])
        assert exits == [43]
        bundles = [f for f in os.listdir(obs_run_dir)
                   if f.startswith("crash-watchdog")]
        assert len(bundles) == 1
        extra = json.load(open(os.path.join(obs_run_dir, bundles[0],
                                            "extra.json")))
        assert extra["stale"] == [1]
        ev = obs_events.get().ring_events()
        assert any(e["type"] == "watchdog" and e["stale"] == [1]
                   for e in ev)

    def test_nonfinite_abort_dumps_bundle(self, obs_run_dir):
        from bigdl_tpu.resilience import faults
        faults.configure("nan_grad@every=1")
        try:
            opt = _opt()
            opt.set_nonfinite_policy(2)
            opt.set_end_when(max_iteration(9))
            with pytest.raises(NonFiniteGradError):
                opt.optimize()
        finally:
            faults.clear()
        bundles = [f for f in os.listdir(obs_run_dir)
                   if f.startswith("crash-nonfinite-abort")]
        assert len(bundles) == 1
        ev = obs_events.read_events(obs_events.get().path)
        assert any(e["type"] == "abort" and e["reason"] == "nonfinite"
                   for e in ev)

    def test_preemption_dumps_bundle_and_event(self, obs_run_dir,
                                               tmp_path):
        from bigdl_tpu.utils.engine import Engine
        ck = tmp_path / "ck"
        ck.mkdir()
        opt = _opt()
        opt.set_checkpoint(str(ck), several_iteration(100))

        def preempt_then_run_long(state):
            # the scheduler's notice lands mid-run; the loop must stop
            # itself at the next iteration boundary
            if state.get("neval", 0) == 3 and not Engine.preempted():
                Engine.request_preemption()
            return state.get("neval", 0) > 9
        opt.set_end_when(preempt_then_run_long)
        opt.optimize()
        assert opt.state["preempted"]
        ev = obs_events.read_events(obs_events.get().path)
        assert any(e["type"] == "preempt" for e in ev)
        assert any(e["type"] == "checkpoint" for e in ev)
        assert any(f.startswith("crash-preemption")
                   for f in os.listdir(obs_run_dir))

    def test_never_raises_without_configuration(self, monkeypatch,
                                                tmp_path):
        # no run dir anywhere: bundle lands in a fresh temp dir
        monkeypatch.delenv(obs_events.ENV_DIR, raising=False)
        obs_events.reset()
        try:
            path = dump_crash_bundle("bare")
            assert path and os.path.isdir(path)
        finally:
            obs_events.reset()

    def test_master_switch_disables_bundles(self, monkeypatch):
        """BIGDL_OBS=0 is the documented hard-off: no stray crash
        directories from abort/preemption/watchdog paths."""
        monkeypatch.setenv(obs_events.ENV_OBS, "0")
        obs_events.reset()
        try:
            assert dump_crash_bundle("off") is None
        finally:
            obs_events.reset()


# ---------------------------------------------------------------------------
# summary: TensorBoard scalar export
# ---------------------------------------------------------------------------

class TestSummary:
    def test_roundtrip_with_crc(self, tmp_path):
        ts = TrainSummary(str(tmp_path), "app")
        for i in range(5):
            ts.add_scalar("Loss", 1.0 / (i + 1), i + 1)
        ts.add_scalar("LearningRate", 0.5, 1)
        ts.close()
        scalars = read_scalars(ts.path)
        losses = [(s, v) for s, tag, v in scalars if tag == "Loss"]
        assert [s for s, _ in losses] == [1, 2, 3, 4, 5]
        np.testing.assert_allclose([v for _, v in losses],
                                   [1, 0.5, 1 / 3, 0.25, 0.2], rtol=1e-6)
        assert ts.path.split(os.sep)[-3:-1] == ["app", "train"]

    def test_negative_step_roundtrips(self, tmp_path):
        """A negative step sentinel must encode as a protobuf int64
        varint (two's complement), not hang the writer."""
        ts = TrainSummary(str(tmp_path), "neg")
        ts.add_scalar("Loss", 2.0, -1)
        ts.close()
        assert read_scalars(ts.path) == [(-1, "Loss", 2.0)]

    def test_corruption_detected(self, tmp_path):
        ts = ValidationSummary(str(tmp_path), "app")
        ts.add_scalar("Top1Accuracy", 0.9, 10)
        ts.close()
        data = bytearray(open(ts.path, "rb").read())
        data[-5] ^= 0xFF
        with open(ts.path, "wb") as f:
            f.write(data)
        with pytest.raises(ValueError, match="crc"):
            read_scalars(ts.path)

    def test_optimizer_wiring(self, tmp_path):
        opt = _opt()
        opt.set_taps(enabled=True, cadence=2)
        ts = TrainSummary(str(tmp_path), "run")
        vs = ValidationSummary(str(tmp_path), "run")
        opt.set_train_summary(ts).set_val_summary(vs)
        opt.set_validation(several_iteration(2), _data(), [Top1Accuracy()])
        opt.set_end_when(max_iteration(4))
        opt.optimize()
        ts.close()
        vs.close()
        train = read_scalars(ts.path)
        tags = {tag for _, tag, _ in train}
        assert {"Loss", "LearningRate", "Throughput",
                "Taps/grad_norm"} <= tags
        assert len([1 for _, tag, _ in train if tag == "Loss"]) == 4
        val = read_scalars(vs.path)
        assert any(tag == "Top1Accuracy" for _, tag, _ in val)


# ---------------------------------------------------------------------------
# report tool
# ---------------------------------------------------------------------------

class TestReport:
    def _load_tool(self):
        import importlib.util
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "obs_report", os.path.join(here, "tools", "obs_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_renders_faulted_run(self, obs_run_dir):
        from bigdl_tpu.resilience import faults
        faults.configure("nan_grad@at=2")
        try:
            opt = _opt()
            opt.set_taps(enabled=True, cadence=2)
            opt.set_nonfinite_policy(0)
            opt.set_end_when(max_iteration(5))
            opt.optimize()
        finally:
            faults.clear()
        dump_crash_bundle("report-test")
        tool = self._load_tool()
        events, bad, bundles = tool.load_run(obs_run_dir)
        assert not bad and events and bundles
        md = tool.render(events, bad, bundles)
        assert "Throughput / loss trajectory" in md
        assert "Incident timeline" in md
        assert "nan_grad" in md
        assert "Crash bundles" in md
        assert "Phase breakdown" in md
        # CLI entry: exit 0, writes the file
        out = os.path.join(obs_run_dir, "report.md")
        assert tool.main([obs_run_dir, "-o", out]) == 0
        assert "# obs report" in open(out).read()

    def test_renders_serving_section_and_trace_waterfall(self, tmp_path):
        """The serve event type is no longer ignored: rollout timeline,
        shed/error counts and a per-hop waterfall for the slowest
        sampled requests all render."""
        from bigdl_tpu.obs.events import SCHEMA_VERSION
        base = {"v": SCHEMA_VERSION, "proc": 0}
        evs = [
            dict(base, ts=1.0, type="serve", kind="start", engine="e0"),
            dict(base, ts=2.0, type="serve", kind="rollout_begin",
                 version=1, replicas=2),
            dict(base, ts=2.5, type="serve", kind="rollout_commit",
                 version=1, replicas=2),
            dict(base, ts=2.6, type="serve", kind="rollout_rollback",
                 version=2, phase="commit", error="OSError: boom"),
            dict(base, ts=3.0, type="serve", kind="error",
                 error="PoisonedRequestError: nan", requests=3),
            dict(base, ts=3.1, type="serve", kind="shed", priority=1),
            dict(base, ts=3.2, type="serve", kind="replica_dead",
                 replica="proc1"),
            dict(base, ts=4.0, type="trace", trace_id="aaaa1111",
                 status="ok", duration_ms=30.0,
                 hops=[["admit", 0.0], ["queue", 0.001],
                       ["dispatch", 0.002], ["compute", 0.025],
                       ["complete", 0.030]]),
            dict(base, ts=4.1, type="trace", trace_id="bbbb2222",
                 status="ok", duration_ms=5.0,
                 hops=[["admit", 0.0], ["complete", 0.005]]),
        ]
        p = tmp_path / "events.p0.jsonl"
        p.write_text("".join(json.dumps(e) + "\n" for e in evs))
        tool = self._load_tool()
        events, bad, bundles = tool.load_run(str(tmp_path))
        assert not bad, bad
        md = tool.render(events, bad, bundles, waterfall=1)
        assert "## Serving" in md
        assert "Rollout timeline" in md
        assert "rollout_rollback" in md and "OSError: boom" in md
        assert "failed requests: **3**" in md
        assert "replica death: **proc1**" in md
        assert "Trace waterfall (slowest 1 of 2" in md
        assert "`aaaa1111`" in md            # the slowest one
        assert "`bbbb2222`" not in md        # cut by waterfall=1
        # waterfall column math: compute hop = 23 ms on the slow trace
        assert "23.00" in md
        md0 = tool.render(events, bad, bundles, waterfall=0)
        assert "Trace waterfall" not in md0

    def test_strict_mode_counts_bad_lines(self, tmp_path):
        p = tmp_path / "events.p0.jsonl"
        good = {"v": 1, "ts": 1.0, "proc": 0, "type": "fault",
                "site": "nan_grad", "step": 1}
        p.write_text(json.dumps(good) + "\nnot json\n"
                     + json.dumps({"type": "step"}) + "\n")
        tool = self._load_tool()
        events, bad, _ = tool.load_run(str(tmp_path))
        assert len(events) == 1 and len(bad) == 2
        assert tool.main([str(tmp_path), "--strict",
                          "-o", str(tmp_path / "r.md")]) == 1


# ---------------------------------------------------------------------------
# satellites: Metrics.timer, warn_every, profiler coverage
# ---------------------------------------------------------------------------

class TestMetricsSatellites:
    def test_timer_records_on_exception(self):
        m = Metrics()
        with pytest.raises(RuntimeError):
            with m.timer("phase"):
                raise RuntimeError("boom")
        total, count = m.get("phase")
        assert count == 1 and total >= 0.0

    def test_declare_registers_without_samples(self):
        m = Metrics()
        m.declare("span: checkpoint")
        assert "span: checkpoint" in m._distributed
        assert m.get("span: checkpoint") == (0.0, 0)
        assert m.mean("span: checkpoint") == 0.0
        # declaring does not disturb later samples
        m.add("span: checkpoint", 2.0, distributed=True)
        assert m.mean("span: checkpoint") == 2.0


class TestWarnEvery:
    def test_reset_warn_cache(self):
        import logging
        from bigdl_tpu.utils.log import reset_warn_cache, warn_every
        lg = logging.getLogger("bigdl_tpu.test")
        assert warn_every(lg, "k1", 3600.0, "x")
        assert not warn_every(lg, "k1", 3600.0, "x")   # rate-limited
        reset_warn_cache()
        assert warn_every(lg, "k1", 3600.0, "x")       # cache cleared

    def test_env_interval_override(self, monkeypatch):
        import logging
        from bigdl_tpu.utils.log import (reset_warn_cache, warn_every,
                                         warn_interval)
        lg = logging.getLogger("bigdl_tpu.optim")
        reset_warn_cache()
        assert warn_every(lg, "k2", 3600.0, "x")
        # global override to 0 disables the rate limit
        monkeypatch.setenv("BIGDL_WARN_INTERVAL", "0")
        assert warn_every(lg, "k2", 3600.0, "x")
        # per-logger override wins over the global one
        monkeypatch.setenv("BIGDL_WARN_INTERVAL_BIGDL_TPU_OPTIM", "3600")
        assert warn_interval(lg, 5.0) == 3600.0
        assert not warn_every(lg, "k2", 0.0, "x")
        other = logging.getLogger("bigdl_tpu.dataset")
        assert warn_interval(other, 5.0) == 0.0        # global applies

    def test_bad_override_ignored(self, monkeypatch):
        import logging
        from bigdl_tpu.utils.log import warn_interval
        monkeypatch.setenv("BIGDL_WARN_INTERVAL", "not-a-number")
        assert warn_interval(logging.getLogger("bigdl_tpu.x"), 7.0) == 7.0


class TestProfiler:
    def test_device_memory_stats_covers_all_devices(self):
        from bigdl_tpu.utils.profiler import device_memory_stats
        stats = device_memory_stats()
        assert set(stats) == {str(d) for d in jax.devices()}
        for v in stats.values():
            assert v is None or isinstance(v, dict)

    def test_format_module_times(self):
        from bigdl_tpu.utils.profiler import format_module_times
        model = _mlp()
        x = np.random.randn(4, 6).astype(np.float32)
        out = model.forward(jnp.asarray(x))          # populates timers
        model.backward(jnp.asarray(x), jnp.zeros_like(out))
        table = format_module_times(model, top_n=3)
        lines = table.splitlines()
        assert lines[0].split() == ["module", "fwd_s", "bwd_s"]
        assert len(lines) == 4                        # header + top 3
        for line in lines[1:]:
            assert len(line.split()) >= 3

    def test_annotations_are_usable(self):
        from bigdl_tpu.utils.profiler import annotation
        with annotation("test-phase"):
            assert float(jnp.square(jnp.float32(2.0))) == 4.0


# ---------------------------------------------------------------------------
# 4-process drill: epoch-end span allgather is deadlock-free
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_four_process_span_gather_no_deadlock(tmp_path):
    """ISSUE 3 satellite: 4 jax.distributed (gloo) processes train with
    the event log + spans on; the per-node span snapshot is collected
    once at the end of optimize() (a collective every process joins) and
    ONLY process 0 renders the per-host report afterwards — from the
    cache, so the asymmetric access cannot deadlock.  All four must exit
    0 with consistent per-node dispatch times and parseable JSONL."""
    from tests.test_multiprocess import free_port, run_workers

    obs = tmp_path / "obs"
    obs.mkdir()
    outs = run_workers(4, free_port(),
                       per_proc_args={i: ["--obs", str(obs)]
                                      for i in range(4)})
    rep = outs[0]["span_report"]
    assert "host0" in rep and "host3" in rep
    for phase in ("data-load", "dispatch", "checkpoint"):
        assert phase in rep
    assert len(outs[0]["dispatch_per_node"]) == 4
    assert all(v > 0 for v in outs[0]["dispatch_per_node"])
    # only process 0 rendered; the others still exited cleanly with a
    # valid event stream on disk
    assert all("span_report" not in o for o in outs[1:])
    for i in range(4):
        events = obs_events.read_events(str(obs / f"events.p{i}.jsonl"))
        assert events, f"no events from process {i}"
        for e in events:
            validate_event(e)
        assert sum(1 for e in events if e["type"] == "step") >= 6
        assert events[-1]["type"] == "run_end"

"""Asynchronous host pipeline tests (ISSUE 4): prefetch-to-device input
path + cadenced host sync.

The contract under test, in order of importance:

1. bit-identical loss trajectory with ``BIGDL_PREFETCH`` on vs off —
   same seed, same per-step losses, same final params — for
   LocalOptimizer and DistriOptimizer, single-step and chunked dispatch,
   including an RNG-bearing pipeline (random crop + flip) across epoch
   boundaries;
2. no per-step device→host sync outside cadence boundaries (the
   ``_HostSyncWindow`` audit trail), and the train step stays ONE jitted
   dispatch with prefetch on;
3. overlap is real: with an artificially slow transform the wall clock
   lands strictly below the serial fetch+train sum;
4. chaos hooks stay keyed by the CONSUMING step, and checkpoint/resume
   replays the serial trajectory (the runner pins the RNG payload to the
   last consumed batch).
"""
import os
import threading
import time

import numpy as np
import pytest

import jax

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset import DataSet, Sample
from bigdl_tpu.dataset import prefetch as pf
from bigdl_tpu.dataset.dataset import AbstractDataSet
from bigdl_tpu.dataset.image import (HFlip, ImgRdmCropper, ImgToBatch,
                                     LabeledImage)
from bigdl_tpu.dataset.transformer import FuncTransformer, SampleToBatch
from bigdl_tpu.obs import events as obs_events
from bigdl_tpu.optim import (DistriOptimizer, LocalOptimizer, Top1Accuracy,
                             max_iteration, several_iteration)
from bigdl_tpu.optim.local_optimizer import validate
from bigdl_tpu.utils.random import RNG, set_seed
from bigdl_tpu.utils.table import T

pytestmark = pytest.mark.perf


@pytest.fixture
def ring_log():
    """Fresh in-memory event ring per test (step events carry the
    per-step losses the trajectory assertions read)."""
    log = obs_events.configure(None)
    yield log
    obs_events.reset()


def _samples(n=24, d=5, seed=0):
    rs = np.random.RandomState(seed)
    xs = rs.randn(n, d).astype(np.float32)
    ys = (rs.randint(0, 3, n) + 1).astype(np.float32)
    return [Sample(x, np.asarray([y])) for x, y in zip(xs, ys)]


def _mlp(d=5):
    return nn.Sequential(nn.Linear(d, 8), nn.Tanh(), nn.Linear(8, 3),
                         nn.LogSoftMax())


def _grey_images(n=16, hw=8, seed=1):
    rs = np.random.RandomState(seed)
    return [LabeledImage(rs.rand(hw, hw).astype(np.float32),
                         float(i % 3 + 1)) for i in range(n)]


class _InOrder(AbstractDataSet):
    """The records in the order given, one pass: which batch holds which
    record is then the test's to say."""

    def __init__(self, records):
        self._records = list(records)

    def size(self):
        return len(self._records)

    def shuffle(self):
        return self

    def data(self, train):
        return iter(self._records)


def _lent_run(ds, depth=None):
    """Drive ``ds`` through a training runner whose ``to_device`` copies
    (as a device does) and holds each batch for a moment.  Returns
    the batches as delivered, whether each lived in one of the runner's
    slots, and the address of each batch's host array."""
    recycled, seen = [], []
    bound = threading.Event()       # the runner's threads start in __init__

    def to_device(x, y):
        assert bound.wait(5)
        recycled.append(any(x is slot.x for slot in runner._slots))
        seen.append(x.ctypes.data)
        before = x.copy(), y.copy()
        time.sleep(0.002)
        # nobody rewrote the host arrays while the transfer had them
        np.testing.assert_array_equal(x, before[0])
        np.testing.assert_array_equal(y, before[1])
        return before

    runner = pf.PipelineRunner(ds, train=True, depth=depth,
                               to_device=to_device)
    bound.set()
    try:
        got = [item.device for item in runner]
    finally:
        runner.close()
    return got, recycled, seen


def _step_events(log):
    return [e for e in log.ring_events() if e["type"] == "step"]


def _losses(log):
    return [e["loss"] for e in _step_events(log)]


def _params_vec(model):
    return np.concatenate([np.asarray(l).reshape(-1) for l in
                           jax.tree_util.tree_leaves(model.params())])


def _train(make_opt, steps, seed=5, dropout=False):
    set_seed(seed)
    opt = make_opt(dropout)
    opt.set_end_when(max_iteration(steps))
    opt.optimize()
    return opt


# ---------------------------------------------------------------------------
# 1. bit-identical trajectories, prefetch on vs off
# ---------------------------------------------------------------------------

class TestTrajectoryParity:
    def _run_mlp(self, monkeypatch, ring_log, prefetch_on, n_disp=1,
                 steps=8, distri=False, dropout=False):
        monkeypatch.setenv(pf.ENV_PREFETCH, "1" if prefetch_on else "0")
        obs_events.configure(None)

        def make(dropout):
            layers = [nn.Linear(5, 8), nn.Tanh()]
            if dropout:
                layers.append(nn.Dropout(0.5))
            layers += [nn.Linear(8, 3), nn.LogSoftMax()]
            model = nn.Sequential(*layers)
            ds = DataSet.array(_samples()) >> SampleToBatch(8)
            cls = DistriOptimizer if distri else LocalOptimizer
            opt = cls(model, ds, nn.ClassNLLCriterion())
            opt.set_state(T(learningRate=0.2, momentum=0.9))
            if n_disp > 1:
                opt.set_iterations_per_dispatch(n_disp)
            return opt

        opt = _train(make, steps, dropout=dropout)
        return _losses(obs_events.get()), _params_vec(opt.model), opt

    @pytest.mark.parametrize("n_disp", [1, 2])
    def test_local(self, monkeypatch, ring_log, n_disp):
        # 8 iterations over a 24-sample epoch (3 steps/epoch): the
        # trajectory crosses epoch shuffles with dropout keys live
        l_on, p_on, _ = self._run_mlp(monkeypatch, ring_log, True,
                                      n_disp=n_disp, dropout=True)
        l_off, p_off, _ = self._run_mlp(monkeypatch, ring_log, False,
                                        n_disp=n_disp, dropout=True)
        assert l_on == l_off
        np.testing.assert_array_equal(p_on, p_off)

    @pytest.mark.parametrize("n_disp", [1, 2])
    def test_distri(self, monkeypatch, ring_log, n_disp):
        l_on, p_on, _ = self._run_mlp(monkeypatch, ring_log, True,
                                      n_disp=n_disp, distri=True,
                                      dropout=True)
        l_off, p_off, _ = self._run_mlp(monkeypatch, ring_log, False,
                                        n_disp=n_disp, distri=True,
                                        dropout=True)
        assert l_on == l_off
        np.testing.assert_array_equal(p_on, p_off)

    def _run_image(self, monkeypatch, prefetch_on, steps=7):
        """RNG-bearing pipeline: random crop + flip draw from the seed
        stream per record — the draws must come off the producer thread
        in the exact serial order (16 images / batch 8 = 2 steps per
        epoch, so 7 steps cross three epoch shuffles)."""
        monkeypatch.setenv(pf.ENV_PREFETCH, "1" if prefetch_on else "0")
        obs_events.configure(None)

        def make(_):
            ds = (DataSet.array(_grey_images())
                  >> ImgRdmCropper(6, 6) >> HFlip() >> ImgToBatch(8))
            model = nn.Sequential(nn.Reshape([36]), nn.Linear(36, 3),
                                  nn.LogSoftMax())
            opt = LocalOptimizer(model, ds, nn.ClassNLLCriterion())
            opt.set_state(T(learningRate=0.1))
            return opt

        opt = _train(make, steps)
        return _losses(obs_events.get()), _params_vec(opt.model)

    def test_rng_bearing_image_pipeline(self, monkeypatch, ring_log):
        l_on, p_on = self._run_image(monkeypatch, True)
        l_off, p_off = self._run_image(monkeypatch, False)
        assert len(l_on) == 7
        assert l_on == l_off
        np.testing.assert_array_equal(p_on, p_off)

    def test_rng_state_after_run_matches_serial(self, monkeypatch,
                                                ring_log):
        """close() must leave the process stream where a serial run
        would: the ahead-draws of merely-prefetched batches are erased,
        so back-to-back optimize() calls stay on the serial trajectory
        (the parity runs above call optimize once per process state)."""
        def end_state(prefetch_on):
            self._run_image(monkeypatch, prefetch_on, steps=5)
            snap = RNG.snapshot()
            return snap["key_counter"], np.asarray(snap["np_state"][1]), \
                snap["np_state"][2]

        kc_on, key_on, pos_on = end_state(True)
        kc_off, key_off, pos_off = end_state(False)
        assert kc_on == kc_off
        assert pos_on == pos_off
        np.testing.assert_array_equal(key_on, key_off)


# ---------------------------------------------------------------------------
# 2. cadenced host sync: no per-step device→host sync, one jit dispatch
# ---------------------------------------------------------------------------

class _StubLoss:
    """A step's device scalar as the window sees it: ``is_ready`` without
    a wait, and a conversion that notes itself (on a device it would block
    until the step has run)."""

    def __init__(self, ready, waited):
        self.ready = ready
        self._waited = waited

    def is_ready(self):
        return self.ready

    def __array__(self, dtype=None, copy=None):
        self._waited.append(self)
        return np.zeros((), np.float32)


def _stub_step(neval0, ready, waited):
    from bigdl_tpu.optim.local_optimizer import _PendingStep
    return _PendingStep(neval0, 1, 0, _StubLoss(ready, waited),
                        _StubLoss(ready, waited), {}, 0.1, 4, 0.0, 0.0, {})


def _stub_window(cadence, ready):
    """A window of ``len(ready)`` pushed steps; returns it and the list
    every conversion of a loss or a finite flag appends itself to."""
    from bigdl_tpu.optim.local_optimizer import _HostSyncWindow
    waited = []
    w = _HostSyncWindow(cadence)
    for i, r in enumerate(ready):
        w.push(_stub_step(i + 1, r, waited))
    return w, waited


class TestCadencedSync:
    def _opt(self, cadence=None, n=64, distri=False):
        ds = DataSet.array(_samples(n=n)) >> SampleToBatch(8)
        cls = DistriOptimizer if distri else LocalOptimizer
        opt = cls(_mlp(), ds, nn.ClassNLLCriterion())
        opt.set_state(T(learningRate=0.2))
        if cadence is not None:
            opt.set_taps(enabled=True, cadence=cadence)
        return opt

    def test_sync_only_at_cadence_boundaries(self, ring_log):
        """The sync-count probe: the window's audit trail shows host
        materializations at cadence boundaries and run end, nowhere else
        (64-sample epoch = 8 steps, so no epoch flush inside 7 steps).  A
        cadence flush covers the cadence's steps and comes one dispatch
        later than they: the step dispatched last stays in flight."""
        set_seed(5)
        opt = self._opt(cadence=3)
        opt.set_end_when(max_iteration(7))
        opt.optimize()
        assert list(opt._window.flush_steps) == [3, 6, 7]
        assert list(opt._window.flush_reasons) == ["cadence", "cadence",
                                                  "run-end"]
        assert list(opt._window.flush_kept) == [4, 7, None]
        # the taps monitor synced at the same boundaries (one host-wait
        # covers both: the step it reads is one the flush materialized,
        # never the one still running), and every step produced its event
        assert list(opt._taps_monitor.materialized_steps) == [3, 6, 7]
        assert [e["step"] for e in _step_events(obs_events.get())] == \
            list(range(1, 8))

    def test_sync_every_step_escape_hatch(self, monkeypatch, ring_log):
        monkeypatch.setenv(pf.ENV_SYNC_EVERY_STEP, "1")
        set_seed(5)
        opt = self._opt(cadence=10)
        opt.set_end_when(max_iteration(4))
        opt.optimize()
        assert list(opt._window.flush_steps) == [1, 2, 3, 4]
        # cadence 1 drains: the host's record is level with the dispatch
        assert list(opt._window.flush_kept) == [None] * 4

    def test_cadenced_losses_match_every_step_sync(self, monkeypatch,
                                                   ring_log):
        def run(sync_env):
            monkeypatch.setenv(pf.ENV_SYNC_EVERY_STEP, sync_env)
            obs_events.configure(None)
            set_seed(5)
            opt = self._opt(cadence=4)
            opt.set_end_when(max_iteration(9))
            opt.optimize()
            return _losses(obs_events.get()), _params_vec(opt.model)

        l_cad, p_cad = run("0")
        l_sync, p_sync = run("1")
        assert len(l_cad) == 9
        assert l_cad == l_sync
        np.testing.assert_array_equal(p_cad, p_sync)

    def test_trigger_and_epoch_boundaries_force_flush(self, ring_log,
                                                      tmp_path):
        set_seed(5)
        ds = DataSet.array(_samples(n=24)) >> SampleToBatch(8)
        opt = LocalOptimizer(_mlp(), ds, nn.ClassNLLCriterion())
        opt.set_state(T(learningRate=0.2))
        opt.set_taps(enabled=True, cadence=100)   # cadence never fires
        opt.set_checkpoint(str(tmp_path), several_iteration(5))
        opt.set_end_when(max_iteration(7))
        opt.optimize()
        # 24-sample epoch = 3 steps: the epoch flushes after dispatches 3
        # and 6 bring the record up to steps 2 and 5 and leave 3 and 6
        # running; the checkpoint trigger fires once neval reaches 5
        # (after step 4 — neval is the NEXT iteration index, the
        # historical semantics) and drains, as run-end does
        assert list(opt._window.flush_steps) == [2, 4, 5, 7]
        assert list(opt._window.flush_reasons) == ["epoch", "trigger",
                                                   "epoch", "run-end"]
        assert list(opt._window.flush_kept) == [3, None, 6, None]
        assert os.path.exists(tmp_path / "model.5")

    def test_unwind_flushes_pending_steps(self, ring_log):
        """A crash between cadence boundaries must not lose the already-
        dispatched steps: the unwind flush emits their events (the
        postmortem needs the steps nearest the failure)."""
        def boom(batch):
            boom.n += 1
            if boom.n > 4:
                raise RuntimeError("source died")
            return batch
        boom.n = 0

        set_seed(5)
        ds = (DataSet.array(_samples(n=64)) >> SampleToBatch(8)
              >> FuncTransformer(boom))
        opt = LocalOptimizer(_mlp(), ds, nn.ClassNLLCriterion())
        opt.set_state(T(learningRate=0.2))
        opt.set_taps(enabled=True, cadence=100)  # cadence never fires
        opt.set_end_when(max_iteration(50))
        with pytest.raises(RuntimeError, match="source died"):
            opt.optimize()
        assert [e["step"] for e in _step_events(obs_events.get())] == \
            [1, 2, 3, 4]
        assert list(opt._window.flush_reasons) == ["exception"]
        assert list(opt._window.flush_kept) == [None]
        assert not opt._window.pending

    # -- the newest step stays in flight (PR 38) ---------------------------

    @pytest.mark.parametrize("reason, cadence, left", [
        ("cadence", 10, 1), ("epoch", 10, 1),
        ("trigger", 10, 0), ("preempt", 10, 0), ("run-end", 10, 0),
        ("exception", 10, 0), ("cadence", 1, 0), ("epoch", 1, 0)])
    def test_only_a_record_flush_leaves_the_newest_step(self, reason,
                                                        cadence, left):
        """The step dispatched last reports its loss not ready: a flush
        that only brings the record up to date must not convert (wait
        for) it, every other flush and every flush at cadence 1 must."""
        w, waited = _stub_window(cadence, [True, True, False])
        newest = w.pending[-1]
        entries, losses, finites, _ = w.flush(reason)
        assert len(entries) == len(losses) == len(finites) == 3 - left
        assert [e.neval0 for e in w.pending] == ([3] if left else [])
        assert ((newest.loss in waited) and (newest.finite in waited)) \
            == (not left)
        assert len(waited) == 2 * (3 - left)
        assert list(w.flush_steps) == [3 - left]
        assert list(w.flush_kept) == ([3] if left else [None])

    def test_a_window_holding_only_the_kept_step_books_no_flush(self):
        w, waited = _stub_window(2, [True, True, False])
        assert w.due()
        w.flush("cadence")
        assert len(w.pending) == 1 and not w.due()
        for reason in ("cadence", "epoch"):
            assert w.flushable(reason) == 0 and w.flush(reason) is None
        assert list(w.flush_steps) == [2] and len(waited) == 4
        # the gate counts from the last step really materialized (2), over
        # the steps a flush would materialize: [3] is one, [3, 4] two
        for neval0, due in ((4, False), (5, True)):
            w.push(_stub_step(neval0, False, waited))
            assert w.due() is due
        entries, *_ = w.flush("cadence")
        assert [e.neval0 for e in entries] == [3, 4]
        assert w.flushable("run-end") == 1

    def test_the_window_clock_is_armed_again_where_a_step_stays(self):
        w, _ = _stub_window(10, [True, True])
        *_, wall = w.flush("epoch")
        assert wall > 0 and w._t0 is not None     # the kept step's window
        *_, wall = w.flush("run-end")
        assert wall >= 0 and w._t0 is None        # drained: the next arm()

    @pytest.mark.parametrize("distri", [False, True],
                             ids=["local", "distri"])
    @pytest.mark.parametrize("cadence, epoch_steps, end", [
        (3, 8, 7),      # cadence flushes only
        (4, 3, 9),      # epoch flushes inside a cadence
        (2, 1, 5),      # an epoch of one step: lags by one and terminates
        (10, 4, 11),    # the benchmark cell's shape
        (2, 5, 10),     # the last dispatch ends a cadence and an epoch
        (1, 3, 5)])     # cadence 1 drains at every step
    def test_every_step_gets_one_event_whatever_the_flushes(
            self, monkeypatch, ring_log, distri, cadence, epoch_steps, end):
        def run(sync_env):
            monkeypatch.setenv(pf.ENV_SYNC_EVERY_STEP, sync_env)
            obs_events.configure(None)
            set_seed(5)
            opt = self._opt(cadence=cadence, n=8 * epoch_steps,
                            distri=distri)
            opt.set_end_when(max_iteration(end))
            opt.optimize()
            return opt, _step_events(obs_events.get())

        opt, events = run("0")
        w = opt._window
        # exactly one event a step, in order, and nothing left behind
        assert [e["step"] for e in events] == list(range(1, end + 1))
        assert not w.pending
        steps, reasons, kept = (list(w.flush_steps), list(w.flush_reasons),
                                list(w.flush_kept))
        assert steps == sorted(set(steps)) and steps[-1] == end
        # only a flush that kept a step leaves run-end anything to do
        assert set(reasons) <= {"cadence", "epoch", "run-end"}
        assert (reasons[-1] == "run-end") == (cadence > 1)
        for step, reason, left in zip(steps, reasons, kept):
            if cadence > 1 and reason in ("cadence", "epoch"):
                assert left == step + 1, (steps, reasons, kept)
            else:
                assert left is None, (steps, reasons, kept)
        # a flush that materializes nothing books nothing
        assert opt.metrics.get("span: host-wait")[1] == len(steps)
        assert opt.metrics.get("span: flush")[1] == len(steps)
        if cadence > 1:
            n_kept = sum(left is not None for left in kept)
            assert opt.metrics.get("span: flush/kept")[1] == n_kept > 0
            # an epoch flush brings the record up to the step before the
            # epoch's last; a cadence flush covers the cadence's steps
            for step, reason in zip(steps, reasons):
                if reason == "epoch":
                    assert (step + 1) % epoch_steps == 0
        if (cadence, epoch_steps) == (2, 1):
            assert steps == [1, 2, 3, 4, 5] and kept == [2, 3, 4, 5, None]
        # same arithmetic, same keys, same records, same order
        ref, ref_events = run("1")
        assert list(ref._window.flush_steps) == list(range(1, end + 1))
        assert [e["loss"] for e in events] == \
            [e["loss"] for e in ref_events]
        np.testing.assert_array_equal(_params_vec(opt.model),
                                      _params_vec(ref.model))

    @pytest.mark.parametrize("distri", [False, True],
                             ids=["local", "distri"])
    def test_a_trigger_and_a_preemption_drain(self, ring_log, tmp_path,
                                              distri):
        from bigdl_tpu.utils.engine import Engine
        set_seed(5)
        opt = self._opt(cadence=100, distri=distri)
        opt.set_checkpoint(str(tmp_path), several_iteration(3))

        def end(state):
            if state.get("neval", 0) == 6 and not Engine.preempted():
                Engine.request_preemption()
            return state.get("neval", 0) > 50
        opt.set_end_when(end)
        try:
            opt.optimize()
        finally:
            Engine.clear_preemption()
        # checkpoints after steps 2 and 5 (neval 3 and 6); the notice
        # lands before step 6, whose iteration honours it
        assert list(opt._window.flush_reasons) == ["trigger", "trigger",
                                                   "preempt"]
        assert list(opt._window.flush_steps) == [2, 5, 6]
        assert list(opt._window.flush_kept) == [None] * 3
        assert opt.state["preempted"] and not opt._window.pending
        assert [e["step"] for e in _step_events(obs_events.get())] == \
            list(range(1, 7))

    @pytest.mark.parametrize("distri", [False, True],
                             ids=["local", "distri"])
    def test_nonfinite_abort_fires_a_step_later_with_every_event_out(
            self, ring_log, distri):
        """Every step poisoned, abort after 3 consecutive skips, cadence
        2: the flush after dispatch 3 reads steps 1-2, the one after
        dispatch 5 reads step 3 and aborts (a drained window would have
        at dispatch 4); steps 4 and 5 were dispatched by then and their
        events go out before the raise, the kept step's by a second
        flush, and the ledger is not asked again."""
        from bigdl_tpu.optim import NonFiniteGradError
        from bigdl_tpu.resilience import faults
        faults.configure("nan_grad@every=1")
        try:
            set_seed(5)
            opt = self._opt(cadence=2, distri=distri)
            opt.set_nonfinite_policy(3)
            opt.set_end_when(max_iteration(20))
            with pytest.raises(NonFiniteGradError, match="3 consecutive"):
                opt.optimize()
        finally:
            faults.clear()
        events = obs_events.get().ring_events()
        assert [e["step"] for e in events if e["type"] == "step"] == \
            [1, 2, 3, 4, 5]
        assert len([e for e in events if e["type"] == "abort"]) == 1
        assert list(opt._window.flush_reasons) == ["cadence", "cadence",
                                                  "exception"]
        assert list(opt._window.flush_steps) == [2, 4, 5]
        assert not opt._window.pending

    def test_single_jit_dispatch_with_prefetch(self, monkeypatch,
                                               ring_log):
        """The jit-count invariant extended to the prefetch path: the
        whole optimize() run — prefetcher, H2D thread, cadence window —
        builds exactly ONE jitted program."""
        calls = []
        real_jit = jax.jit

        def counting_jit(fn, *a, **kw):
            calls.append(fn)
            return real_jit(fn, *a, **kw)

        monkeypatch.setattr(jax, "jit", counting_jit)
        set_seed(5)
        opt = self._opt()
        opt.set_end_when(max_iteration(5))
        opt.optimize()
        assert len(calls) == 1

    def test_queue_depth_in_step_events(self, ring_log):
        set_seed(5)
        opt = self._opt(cadence=2)
        opt.set_end_when(max_iteration(5))
        opt.optimize()
        steps = _step_events(obs_events.get())
        assert steps and all("queue_depth" in e for e in steps)


# ---------------------------------------------------------------------------
# 3. overlap: wall clock strictly below the serial fetch+train sum
# ---------------------------------------------------------------------------

class TestOverlap:
    DELAY = 0.05
    STEPS = 8

    def _run(self, monkeypatch, prefetch_on, steps=None):
        from bigdl_tpu.resilience import faults
        monkeypatch.setenv(pf.ENV_PREFETCH, "1" if prefetch_on else "0")

        def slow(batch):                      # producer-side stall
            time.sleep(self.DELAY)            # per BATCH (after assembly)
            return batch

        set_seed(5)
        ds = (DataSet.array(_samples(n=64)) >> SampleToBatch(8)
              >> FuncTransformer(slow))
        opt = LocalOptimizer(_mlp(), ds, nn.ClassNLLCriterion())
        opt.set_state(T(learningRate=0.2))
        opt.set_end_when(max_iteration(steps or self.STEPS))
        # consumer-side work the producer can hide behind: the
        # slow_worker chaos site sleeps at CONSUME time every step
        faults.configure(f"slow_worker@every=1,delay={self.DELAY}")
        t0 = time.perf_counter()
        try:
            opt.optimize()
        finally:
            faults.clear()
        return time.perf_counter() - t0, opt

    def test_stall_injection_overlap(self, monkeypatch, ring_log):
        # warm the persistent XLA cache so both timed runs pay the same
        # (small) compile cost — the sleeps dominate, not the compiler
        self._run(monkeypatch, False, steps=2)
        wall_on, opt_on = self._run(monkeypatch, True)
        wall_off, _ = self._run(monkeypatch, False)
        # serial pays DELAY (producer) + DELAY (consumer) per step; the
        # pipeline hides the producer sleep behind the consumer's work,
        # so at least ~STEPS*DELAY of wall must disappear
        assert wall_on < wall_off - 0.15, (wall_on, wall_off)
        assert wall_on < 0.85 * wall_off, (wall_on, wall_off)
        # the spans tell the same story from the prefetch run alone: the
        # producer paid the transform wall (data-load/fetch), the
        # consumer's data-load wait stayed a fraction of it
        fetch_total, fetch_n = opt_on.metrics.get("span: data-load/fetch")
        wait_total, _ = opt_on.metrics.get("span: data-load")
        assert fetch_n >= self.STEPS
        assert wait_total < 0.6 * fetch_total, (wait_total, fetch_total)
        # wall < this same run's serial fetch+train sum (the components
        # it would have paid back-to-back without overlap)
        disp_total, _ = opt_on.metrics.get("span: dispatch")
        hw_total, _ = opt_on.metrics.get("span: host-wait")
        chaos_total = self.STEPS * self.DELAY
        assert wall_on < fetch_total + disp_total + hw_total \
            + chaos_total, (wall_on, fetch_total, disp_total, hw_total)

    def test_stall_events_emitted(self, monkeypatch, ring_log):
        """A producer slower than the consumer must surface as
        prefetch_stall events keyed by the waiting step."""
        def slow(batch):
            time.sleep(0.1)
            return batch

        monkeypatch.setenv(pf.ENV_PREFETCH, "1")
        set_seed(5)
        ds = (DataSet.array(_samples(n=64)) >> SampleToBatch(8)
              >> FuncTransformer(slow))
        opt = LocalOptimizer(_mlp(), ds, nn.ClassNLLCriterion())
        opt.set_state(T(learningRate=0.2))
        opt.set_end_when(max_iteration(6))
        opt.optimize()
        stalls = [e for e in obs_events.get().ring_events()
                  if e["type"] == "prefetch_stall"]
        assert stalls
        assert all(e["seconds"] > 0 and e["step"] >= 1 for e in stalls)


# ---------------------------------------------------------------------------
# 4. chaos keyed by consuming step + checkpoint/resume
# ---------------------------------------------------------------------------

class TestChaosAndResume:
    def test_fault_keyed_by_consuming_step(self, ring_log):
        """nan_grad@at=3 must poison the batch CONSUMED at iteration 3,
        not the batch fetched third — with prefetch on, those differ by
        the queue depth.  The taps ledger pins it."""
        from bigdl_tpu.resilience import faults
        faults.configure("nan_grad@at=3")
        try:
            set_seed(5)
            ds = DataSet.array(_samples(n=64)) >> SampleToBatch(8)
            opt = LocalOptimizer(_mlp(), ds, nn.ClassNLLCriterion())
            opt.set_state(T(learningRate=0.2))
            opt.set_taps(enabled=True, cadence=1)
            opt.set_nonfinite_policy(0)
            opt.set_end_when(max_iteration(5))
            opt.optimize()
        finally:
            faults.clear()
        hist = dict(opt._taps_monitor.history)
        assert hist[3]["nonfinite_grads"] > 0
        assert hist[3]["update_ratio"] == 0.0
        assert hist[2]["nonfinite_grads"] == 0.0
        assert hist[4]["nonfinite_grads"] == 0.0
        ev = obs_events.get().ring_events()
        assert any(e["type"] == "fault" and e["site"] == "nan_grad"
                   and e["step"] == 3 for e in ev)

    def test_resume_replays_serial_trajectory(self, tmp_path, ring_log):
        """The checkpoint RNG payload is pinned to the last CONSUMED
        batch (not the prefetch head): resuming replays the exact
        uninterrupted trajectory — crop/flip draws and dropout keys
        included.  Scenario shape follows the resilience resume test:
        the pipeline decodes fresh records from bytes each epoch, all
        records are identical (the dataset's shuffled list order is not
        part of a checkpoint), and batch == dataset so every checkpoint
        lands on an epoch boundary (a mid-epoch permutation is not
        replayable, with or without prefetch)."""
        from bigdl_tpu.dataset import ByteRecord
        from bigdl_tpu.dataset.image import BytesToGreyImg, ImgNormalizer
        raw = np.random.RandomState(2).randint(
            0, 255, 64, dtype=np.uint8).tobytes()
        records = [ByteRecord(raw, 1.0) for _ in range(16)]

        def make_ds():
            return (DataSet.array(list(records)) >> BytesToGreyImg(8, 8)
                    >> ImgNormalizer(128.0, 128.0)
                    >> ImgRdmCropper(6, 6) >> HFlip() >> ImgToBatch(16))

        def build(seed):
            set_seed(seed)
            model = nn.Sequential(nn.Reshape([36]), nn.Dropout(0.5),
                                  nn.Linear(36, 3), nn.LogSoftMax())
            opt = LocalOptimizer(model, make_ds(), nn.ClassNLLCriterion())
            opt.set_state(T(learningRate=0.05))
            return opt

        opt_a = build(7)
        opt_a.set_checkpoint(str(tmp_path), several_iteration(2))
        opt_a.set_end_when(max_iteration(5))
        opt_a.optimize()
        assert opt_a.state["loss"] > 0    # gradients stayed live
        final = _params_vec(opt_a.model)

        from bigdl_tpu.optim import load_latest_checkpoint
        # corrupt the newer snapshots (several_iteration(2) fired at
        # neval 2, 4 and 6) so resume falls back to neval 2 — mid-run,
        # where the prefetch head had drawn past the consumed batches
        (tmp_path / "model.4").write_bytes(b"rot")
        (tmp_path / "model.6").write_bytes(b"rot")

        def resume(restore_rng):
            set_seed(12345 if restore_rng else 999)
            module, blob, neval = load_latest_checkpoint(
                str(tmp_path), restore_rng=restore_rng)
            assert neval == 2
            opt_b = LocalOptimizer(module, make_ds(),
                                   nn.ClassNLLCriterion())
            opt_b.set_state(blob["state"])
            opt_b.set_optim_state(blob["opt_state"])
            opt_b.set_end_when(max_iteration(5))
            opt_b.optimize()
            return _params_vec(opt_b.model)

        np.testing.assert_array_equal(resume(restore_rng=True), final)
        # negative control: without the rng payload the crops/flips and
        # dropout masks of steps 2-5 differ and the trajectory forks
        assert not np.array_equal(resume(restore_rng=False), final)


# ---------------------------------------------------------------------------
# PipelineRunner / satellite units
# ---------------------------------------------------------------------------

class TestPipelineRunner:
    def test_matches_serial_iterator(self):
        # no epoch_size: compares against the RAW looped iterator (the
        # rollover-shuffle parity is covered by the trajectory tests)
        ds = DataSet.array(_samples(n=32)) >> SampleToBatch(8)
        set_seed(11)
        serial = [np.array(b.data) for b, _ in
                  zip(ds.data(train=True), range(6))]
        set_seed(11)
        runner = pf.PipelineRunner(ds, train=True)
        got = [np.array(runner.get()[0].x) for _ in range(6)]
        runner.close()
        for a, b in zip(serial, got):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("n_workers", [0, 2])
    def test_wrapped_chain_keeps_batch_order_across_epochs(self, n_workers):
        """The producer builds the chain link by link, each link under a
        clock: the batches, epoch shuffles included, are those of
        ``dataset.data()`` driven by the serial loop's arithmetic — for a
        nested chain (``a >> (b >> c)``) and with the pure prefix fanned
        out as one link."""
        from bigdl_tpu.dataset.image import ImgNormalizer

        def make_ds():
            tail = ImgRdmCropper(6, 6) >> (HFlip() >> ImgToBatch(8))
            return (DataSet.array(_grey_images(n=16))
                    >> ImgNormalizer(0.5, 2.0) >> tail)

        set_seed(19)
        ds, serial = make_ds(), []
        it = ds.data(train=True)
        for k in range(6):              # 2 batches an epoch: 3 shuffles
            serial.append(np.array(next(it).data))
            if k % 2 == 1:
                ds.shuffle()
                it = ds.data(train=True)
        set_seed(19)
        runner = pf.PipelineRunner(make_ds(), train=True, epoch_size=16,
                                   n_workers=n_workers)
        got = [np.array(runner.get()[0].x) for _ in range(6)]
        spans = runner.take_spans()
        runner.close()
        for a, b in zip(serial, got):
            np.testing.assert_array_equal(a, b)
        links = [p for p in spans if p.startswith(pf.FETCH + "/")]
        assert pf.FETCH + "/source:LocalArrayDataSet" in links
        assert pf.FETCH + "/stage/3:ImgToBatch" in links
        # fanned out or not, the pure prefix is the link after the source
        assert pf.FETCH + "/stage/0:ImgNormalizer" in links
        assert len(links) == 5
        assert spans[pf.FETCH][1] >= 6 and pf.H2D not in spans

    def test_stage_self_times_sum_to_the_draw_and_name_the_stage(self):
        """Each link's self time is the time inside its ``next()`` minus
        the time inside its upstream's; over a run they sum to the draws'
        wall (what is left is the epoch rollover and the RNG snapshot),
        and on a chain whose cost is the stacking of records they name
        ``SampleToBatch``."""
        rs = np.random.RandomState(3)
        samples = [Sample(rs.rand(3, 128, 128).astype(np.float32),
                          np.asarray([1.0], np.float32))
                   for _ in range(128)]
        # a 25 MB batch: the draw's fixed costs are ~0.1 ms of several
        ds = (DataSet.array(samples) >> FuncTransformer(lambda s: s)
              >> SampleToBatch(128))
        runner = pf.PipelineRunner(ds, train=True, epoch_size=128)
        for _ in range(12):
            runner.get()
        spans = runner.take_spans()
        runner.close()
        wall, draws = spans.pop(pf.FETCH)
        assert draws >= 12
        assert all(n == draws for _, n in spans.values())
        assert sorted(spans) == [
            pf.FETCH + "/source:LocalArrayDataSet",
            pf.FETCH + "/stage/0:FuncTransformer",
            pf.FETCH + "/stage/1:SampleToBatch"]
        self_total = sum(sec for sec, _ in spans.values())
        assert self_total == pytest.approx(wall, rel=0.05)
        assert max(spans, key=lambda p: spans[p][0]).endswith(
            ":SampleToBatch")
        assert all(sec >= 0 for sec, _ in spans.values())

    def test_take_spans_drains_and_books_the_transfer(self):
        ds = DataSet.array(_samples(n=32)) >> SampleToBatch(8)
        runner = pf.PipelineRunner(
            ds, train=True, epoch_size=32,
            to_device=lambda x, y: (np.asarray(x), np.asarray(y)))
        for _ in range(4):
            runner.get()
        first = runner.take_spans()
        assert first[pf.H2D][1] >= 4 and first[pf.H2D][0] > 0
        assert first[pf.FETCH][1] >= 4
        runner.close()
        # drained: what the threads booked before the drain is gone
        later = runner.take_spans()
        assert later.get(pf.H2D, (0, 0))[1] <= 3

    def test_close_restores_consumed_rng_state(self):
        def make_ds():
            # fresh images per pass: the croppers mutate records in
            # place, and a reused (already-cropped) image changes the
            # randint RANGES and with them the words-per-draw
            return (DataSet.array(_grey_images(n=16))
                    >> ImgRdmCropper(6, 6) >> HFlip() >> ImgToBatch(8))

        set_seed(13)
        it = make_ds().data(train=True)
        for _ in range(3):      # exactly 3 batches (zip would pull a 4th)
            next(it)
        serial_state = RNG.snapshot()["np_state"]
        set_seed(13)
        runner = pf.PipelineRunner(make_ds(), train=True,
                                   epoch_size=10 ** 9)
        for _ in range(3):
            runner.get()
        runner.close()          # producer drew ahead; close rewinds
        got_state = RNG.snapshot()["np_state"]
        np.testing.assert_array_equal(np.asarray(serial_state[1]),
                                      np.asarray(got_state[1]))
        assert serial_state[2] == got_state[2]
        assert RNG.seed_stream_owner() is not None

    def test_producer_error_propagates(self):
        def boom(sample):
            raise RuntimeError("decode failed")

        ds = DataSet.array(_samples()) >> FuncTransformer(boom) \
            >> SampleToBatch(8)
        runner = pf.PipelineRunner(ds, train=True, epoch_size=24)
        with pytest.raises(RuntimeError, match="decode failed"):
            runner.get()
        runner.close()

    def test_worker_fanout_preserves_order_and_trajectory(self):
        """Pure per-record stages fan out across workers; the record
        order and the stochastic stages' draw sequence are unchanged."""
        from bigdl_tpu.dataset.image import ImgNormalizer

        def run(n_workers):
            ds = (DataSet.array(_grey_images(n=16))
                  >> ImgNormalizer(0.5, 2.0)      # pure: fans out
                  >> ImgRdmCropper(6, 6) >> HFlip()   # stochastic: stays
                  >> ImgToBatch(8))
            set_seed(17)
            runner = pf.PipelineRunner(ds, train=True, epoch_size=16,
                                       n_workers=n_workers)
            out = [np.array(runner.get()[0].x) for _ in range(5)]
            runner.close()
            return out

        fanout = run(4)
        serial = run(0)
        for a, b in zip(serial, fanout):
            np.testing.assert_array_equal(a, b)

    def test_eval_background_prefetch_one_pass(self):
        ds = DataSet.array(_samples(n=20)) >> SampleToBatch(8)
        serial = [np.array(b.data) for b in ds.data(train=False)]
        got = [np.array(b.data) for b in
               pf.background(ds.data(train=False), 2)]
        assert len(got) == len(serial) == 3   # 8 + 8 + 4 tail
        for a, b in zip(serial, got):
            np.testing.assert_array_equal(a, b)

    def test_validate_results_match_serial(self, monkeypatch):
        ds = DataSet.array(_samples(n=40)) >> SampleToBatch(8)
        set_seed(3)
        model = _mlp()

        def run(on):
            monkeypatch.setenv(pf.ENV_PREFETCH, "1" if on else "0")
            res = validate(model, model.params(), model.state(), ds,
                           [Top1Accuracy()])
            return res[0][1]

        assert run(True) == run(False)


class TestLentSlots:
    """A training runner that copies to the device owns the host batch
    buffers: it lends ``SampleToBatch`` a recycled slot per draw and takes
    it back when the transfer is over.  Everyone else gets fresh arrays."""

    def test_no_slot_is_rewritten_before_its_release(self):
        """Two slots, a transfer that holds every batch for 10 ms and
        checks it again, a producer that could run far ahead: every batch
        arrives as the serial path draws it, over three epochs with their
        shuffles."""
        def make_ds():
            return DataSet.array(_samples(n=32)) >> SampleToBatch(8)

        set_seed(23)
        ds, serial = make_ds(), []
        it = ds.data(train=True)
        for k in range(12):             # 4 batches an epoch
            b = next(it)
            serial.append((np.array(b.data), np.array(b.labels)))
            if k % 4 == 3:
                ds.shuffle()
                it = ds.data(train=True)
        set_seed(23)
        held = []

        def to_device(x, y):
            before = x.copy(), y.copy()
            time.sleep(0.01)
            held.append(np.array_equal(x, before[0])
                        and np.array_equal(y, before[1]))
            return before

        runner = pf.PipelineRunner(make_ds(), train=True, epoch_size=32,
                                   depth=1, to_device=to_device)
        runner._free.get(timeout=5)     # depth + 2 = 3 slots: leave two
        items = [runner.get()[0] for _ in range(12)]
        spans = runner.take_spans()
        runner.close()
        assert all(held) and len(held) >= 12
        for (x, y), item in zip(serial, items):
            np.testing.assert_array_equal(x, item.device[0])
            np.testing.assert_array_equal(y, item.device[1])
            # the host arrays went back with the slot
            assert item.x is None and item.y is None and item.slot is None
        assert spans[pf.SLOT_WAIT][1] >= 12

    def test_direct_iteration_yields_distinct_arrays(self):
        """``dataset.data()`` iterated directly (what the serial loop
        under ``BIGDL_PREFETCH=0`` does too) gives every batch its own
        arrays, also while a runner has the stage's slots."""
        tb = SampleToBatch(8)
        ds = DataSet.array(_samples(n=32)) >> tb
        runner = pf.PipelineRunner(ds, train=True,
                                   to_device=lambda x, y: (x.copy(),
                                                           y.copy()))
        try:
            runner.get()
            batches = [b for b, _ in zip(ds.data(train=True), range(6))]
            kept = [(b.data.copy(), b.labels.copy()) for b in batches]
            for _ in range(6):
                runner.get()            # the slots turn over meanwhile
        finally:
            runner.close()
        for i, a in enumerate(batches):
            for b in batches[i + 1:]:
                assert not np.shares_memory(a.data, b.data)
                assert not np.shares_memory(a.labels, b.labels)
        for b, (x, y) in zip(batches, kept):
            np.testing.assert_array_equal(b.data, x)
            np.testing.assert_array_equal(b.labels, y)

    def test_serial_loop_leaves_the_slots_alone(self, monkeypatch, ring_log):
        monkeypatch.setenv(pf.ENV_PREFETCH, "0")
        tb = SampleToBatch(8)
        opt = LocalOptimizer(_mlp(), DataSet.array(_samples()) >> tb,
                             nn.ClassNLLCriterion())
        opt.set_end_when(max_iteration(4))
        opt.optimize()
        assert tb._shelf == [[]]
        monkeypatch.setenv(pf.ENV_PREFETCH, "1")
        opt.set_end_when(max_iteration(8))
        opt.optimize()                  # the front door borrows them ...
        (slots,) = tb._shelf            # ... and close() gave them back
        assert len(slots) == pf.DEFAULT_DEPTH + 2

    @pytest.mark.parametrize("case", ["padded", "pinned", "shape", "dtype"])
    def test_batches_that_do_not_fit_are_assembled_fresh(self, case):
        rs = np.random.RandomState(2)
        label = np.asarray([1.0], np.float32)
        kw = {}
        if case in ("padded", "pinned"):
            # variable-length rows: without fixed_length the batch's width
            # is data-dependent, so no slot can hold it
            samples = [Sample(rs.rand(int(n), 3).astype(np.float32), label)
                       for n in rs.randint(2, 7, 16)]
            kw = dict(feature_padding=0.0,
                      fixed_length=6 if case == "pinned" else None)
            want = [case == "pinned"] * 4
        elif case == "shape":
            # the third batch's rows are wider than the slots'
            samples = [Sample(rs.rand(6 if 8 <= i < 12 else 5)
                              .astype(np.float32), label) for i in range(16)]
            want = [True, True, False, True]
        else:
            # one float64 row: ``np.stack`` promotes its batch, a copy
            # into the float32 slot would have rounded it
            samples = [Sample(rs.rand(5).astype(
                np.float64 if i == 9 else np.float32), label)
                for i in range(16)]
            want = [True, True, False, True]
        plain = list(SampleToBatch(4, **kw)(iter(samples)))
        got, recycled, _ = _lent_run(
            _InOrder(samples) >> SampleToBatch(4, **kw))
        assert recycled == want
        for a, (x, y) in zip(plain, got):
            assert x.dtype == a.data.dtype and x.shape == a.data.shape
            np.testing.assert_array_equal(a.data, x)
            np.testing.assert_array_equal(a.labels, y)

    def test_host_side_and_eval_runners_assemble_fresh(self):
        """Without ``to_device`` (the optimizers' mode under a
        ``FaultInjector``) the consumer reads ``item.x`` whenever it
        likes; a validation pass borrows the dataset for a moment.  Both
        get fresh arrays, and neither touches the slots."""
        tb = SampleToBatch(8)
        ds = DataSet.array(_samples(n=32)) >> tb
        runner = pf.PipelineRunner(ds, train=True)
        items = [runner.get()[0] for _ in range(6)]
        spans = runner.take_spans()
        runner.close()
        assert pf.SLOT_WAIT not in spans
        for i, a in enumerate(items):
            assert a.slot is None
            for b in items[i + 1:]:
                assert not np.shares_memory(a.x, b.x)
        evalr = pf.PipelineRunner(ds, train=False,
                                  to_device=lambda x, y: (x, y))
        assert len(list(evalr)) == 4
        assert pf.SLOT_WAIT not in evalr.take_spans()
        evalr.close()
        assert tb._shelf == [[]]

    def test_second_runner_over_one_dataset_assembles_fresh(self):
        ds = DataSet.array(_samples(n=32)) >> SampleToBatch(8)
        copy = lambda x, y: (x.copy(), y.copy())
        first = pf.PipelineRunner(ds, train=True, to_device=copy)
        second = pf.PipelineRunner(ds, train=True, to_device=copy,
                                   own_rng=False)
        try:
            for _ in range(4):
                first.get(), second.get()
            assert pf.SLOT_WAIT in first.take_spans()
            assert pf.SLOT_WAIT not in second.take_spans()
        finally:
            first.close()
            second.close()
        # the slots are back: the next runner has them
        third = pf.PipelineRunner(ds, train=True, to_device=copy)
        third.get()
        third.close()
        assert pf.SLOT_WAIT in third.take_spans()

    def test_device_arrays_that_are_the_host_memory_keep_it(self):
        """The CPU backend adopts an aligned host array without a copy,
        and a stub may return the host arrays: the slot then lets go of
        its buffers, so no delivered batch is ever rewritten."""
        samples = _samples(n=64)
        plain = list(SampleToBatch(8)(iter(samples)))
        runner = pf.PipelineRunner(
            _InOrder(samples) >> SampleToBatch(8), train=True, depth=1,
            to_device=lambda x, y: (x, y))
        items = list(runner)
        runner.close()
        assert len(items) == 8
        for a, item in zip(plain, items):
            np.testing.assert_array_equal(a.data, item.device[0])
            np.testing.assert_array_equal(a.labels, item.device[1])

    def test_slot_wait_is_booked_outside_the_draw(self):
        """Slots scarcer than the queues (two, and a transfer that holds
        each for 30 ms): the producer's wait for a free one shows under
        ``feed/slot-wait``, outside ``data-load/fetch/`` (whose sub-paths
        are the chain's links), and the draw's wall and its links do not
        grow by it."""
        ds = DataSet.array(_samples(n=32)) >> SampleToBatch(8)

        def to_device(x, y):
            time.sleep(0.03)
            return x.copy(), y.copy()

        runner = pf.PipelineRunner(ds, train=True, epoch_size=32, depth=1,
                                   to_device=to_device)
        runner._free.get(timeout=5)     # depth + 2 = 3 slots: leave two
        for _ in range(10):
            runner.get()
        spans = runner.take_spans()
        runner.close()
        assert not pf.SLOT_WAIT.startswith(pf.FETCH)
        wait, recycled = spans[pf.SLOT_WAIT]
        fetch, draws = spans[pf.FETCH]
        assert recycled == draws >= 10      # reuse share 100%
        assert wait > 0.15                  # ~8 draws waited ~30 ms each
        links = sum(sec for path, (sec, _) in spans.items()
                    if path.startswith(pf.FETCH + "/"))
        assert fetch < 0.25 * wait and links <= fetch * 1.001


class TestSatellites:
    def test_stack_chunk_converts_once_and_checks_shapes(self):
        from bigdl_tpu.dataset.sample import MiniBatch
        a = MiniBatch(np.ones((4, 3), np.float32), np.ones((4,)))
        b = MiniBatch(np.zeros((4, 3), np.float32), np.zeros((4,)))
        xs, ys = pf.stack_chunk([a, b])
        assert xs.shape == (2, 4, 3) and ys.shape == (2, 4)
        bad = MiniBatch(np.ones((5, 3), np.float32), np.ones((5,)))
        with pytest.raises(ValueError, match="uniform batch shapes"):
            pf.stack_chunk([a, bad])

    def test_eval_iteration_is_snapshot_free(self):
        from bigdl_tpu.dataset.dataset import (LocalArrayDataSet,
                                               ShardedDataSet)
        for cls in (LocalArrayDataSet,
                    lambda d: ShardedDataSet(d, n_shards=1, shard_index=0)):
            ds = cls(list(range(10)))
            assert list(ds.data(train=False)) == list(range(10))
            # the view is lazy: a shuffle between passes is visible to
            # the NEXT iterator without any per-call list copy
            it = ds.data(train=False)
            assert not isinstance(it, list)
            set_seed(4)
            ds.shuffle()
            assert sorted(ds.data(train=False)) == list(range(10))

    def test_lent_slots_recycle_in_turn(self):
        """The runner's slots really recycle (3 of them carry 12 batches)
        and every batch reads as the plain path's."""
        samples = _samples(n=96)
        plain = list(SampleToBatch(8)(iter(samples)))
        got, recycled, seen = _lent_run(
            _InOrder(samples) >> SampleToBatch(8), depth=1)
        assert len(got) == len(plain) == 12 and all(recycled)
        for a, (x, y) in zip(plain, got):
            np.testing.assert_array_equal(a.data, x)
            np.testing.assert_array_equal(a.labels, y)
        assert len(set(seen)) == 3          # depth + 2 buffers, no more

    def test_lent_slots_tail_falls_back(self):
        samples = _samples(n=20)               # 8 + 8 + 4 tail
        got, recycled, _ = _lent_run(_InOrder(samples) >> SampleToBatch(8))
        assert [x.shape[0] for x, _ in got] == [8, 8, 4]
        assert recycled == [True, True, False]
        np.testing.assert_array_equal(
            got[2][0], np.stack([s.feature for s in samples[16:]]))

    def test_transformer_purity_attrs(self):
        from bigdl_tpu.dataset.image import (BytesToImg, ColorJitter,
                                             ImgCropper, ImgNormalizer,
                                             Lighting)
        assert BytesToImg().pure_per_record
        assert ImgNormalizer(0.0, 1.0).pure_per_record
        assert not ImgNormalizer(0.0, 1.0).stochastic
        for t in (HFlip(), ColorJitter(), Lighting(),
                  ImgRdmCropper(2, 2), ImgCropper(2, 2, "random")):
            assert t.stochastic, type(t).__name__
        assert ImgCropper(2, 2, "center").pure_per_record

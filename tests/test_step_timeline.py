"""The loop's step timeline (PR 37): ``SpanTracker`` keeps a bounded ring of
``(path, start, end, step)`` beside its totals, nested spans are annotated
by their path, ``dispatch`` is split into ``dispatch/prepare`` and
``dispatch/call`` with a count of the steps in flight between them, and
every ``optimize()`` call of the local loop leaves one ``step_timeline``
event and one log line behind; since PR 38 a cadence or epoch flush leaves
the newest step in flight, the event counts the flushes that did
(``flushes``, counter ``flush/kept``) and ``device_empty`` stays right with
a step pending after a flush.  All on the CPU: no number here is a device
metric."""
import collections
import contextlib
import json
import logging
import os
import sys
import types

import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset import DataSet, Sample
from bigdl_tpu.dataset.transformer import SampleToBatch
from bigdl_tpu.obs import events as obs_events
from bigdl_tpu.obs import spans as spans_mod
from bigdl_tpu.obs.diagnostics import dump_crash_bundle
from bigdl_tpu.obs.events import validate_event
from bigdl_tpu.obs.spans import SpanTracker, render_timeline
from bigdl_tpu.optim.local_optimizer import (LocalOptimizer, _HostSyncWindow,
                                             _PendingStep)
from bigdl_tpu.optim.metrics import Metrics
from bigdl_tpu.optim.trigger import max_iteration
from bigdl_tpu.utils.random import set_seed
from bigdl_tpu.utils.table import T

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
LEAVES = ("data-load", "dispatch/prepare", "dispatch/call", "bookkeep")


@pytest.fixture
def event_log():
    log = obs_events.configure(None, ring=4096)
    yield log
    obs_events.reset()


def _opt(steps, batch=4, n=16):
    rng = np.random.RandomState(0)
    xs = rng.randn(n, 6).astype(np.float32)
    samples = [Sample(x, np.asarray([1.0 + i % 3])) for i, x in enumerate(xs)]
    set_seed(7)
    model = nn.Sequential(nn.Linear(6, 8), nn.Tanh(), nn.Linear(8, 3),
                          nn.LogSoftMax())
    opt = LocalOptimizer(model, DataSet.array(samples) >> SampleToBatch(batch),
                         nn.ClassNLLCriterion())
    opt.set_state(T(learningRate=0.1))
    opt.set_end_when(max_iteration(steps))
    return opt


@pytest.fixture(scope="module")
def run():
    """One call of the local loop: 11 iterations, epochs of 4, cadence 10."""
    log = obs_events.configure(None, ring=4096)
    opt = _opt(11)
    opt.optimize()
    events = log.ring_events()
    obs_events.reset()
    return opt, events


def _by_step(records):
    out = collections.defaultdict(list)
    for rec in records:
        out[rec[3]].append(rec)
    return out


# -- the ring ---------------------------------------------------------------

def test_ring_records_carry_path_times_and_one_step_an_iteration(run):
    opt, _ = run
    records = opt.spans.records_since(0)
    assert all(isinstance(path, str) and t0 <= t1
               for path, t0, t1, _ in records)
    by_step = _by_step(records)
    assert sorted(by_step) == list(range(1, 12))      # neval0 of each
    for step, recs in by_step.items():
        paths = [r[0] for r in recs]
        assert paths.count("loop") == 1, (step, paths)
        for leaf in LEAVES[:3]:
            assert paths.count(leaf) == 1, (step, paths)
        assert paths.count("bookkeep") == 2
        loop = next(r for r in recs if r[0] == "loop")
        # the iteration's own spans lie inside its wall
        inside = [r for r in recs if r[2] <= loop[2]]
        assert all(loop[1] <= r[1] for r in inside)
    # the run-end flush comes after the last iteration and carries its step
    last = by_step[11]
    assert [r[0] for r in last][-2:] == ["host-wait", "flush"]
    assert last[-1][1] >= next(r for r in last if r[0] == "loop")[2]


def test_dispatch_is_split_and_keeps_its_total(run):
    opt, _ = run
    totals = {path: (total, count)
              for path, _, _, total, count in opt.spans.rows()}
    in_ring = collections.Counter()
    for step, recs in _by_step(opt.spans.records_since(0)).items():
        at = {r[0]: r for r in recs}
        d, p, c = at["dispatch"], at["dispatch/prepare"], at["dispatch/call"]
        assert d[1] <= p[1] <= p[2] <= c[1] <= c[2] <= d[2]
        for r in recs:
            in_ring[r[0]] += r[2] - r[1]
    # the totals are the ring's records summed: ``dispatch`` still times
    # the whole of it, the count of steps in flight included
    for path in ("dispatch", "dispatch/prepare", "dispatch/call"):
        assert totals[path][1] == 11
        assert totals[path][0] == pytest.approx(in_ring[path], rel=1e-9)
    # the counter ``loop`` holds the closing flush besides (count 0)
    assert totals["loop"][1] == 11 and totals["loop"][0] > in_ring["loop"]
    assert totals["dispatch/prepare"][0] + totals["dispatch/call"][0] \
        <= totals["dispatch"][0]


def test_nested_spans_are_annotated_by_their_path(monkeypatch):
    from bigdl_tpu.utils import profiler
    seen = []

    @contextlib.contextmanager
    def annotation(name):
        seen.append(name)
        yield

    monkeypatch.setattr(profiler, "annotation", annotation)
    tr = SpanTracker(Metrics())
    with tr.span("dispatch"):
        with tr.span("prepare"):
            pass
        with tr.span("call"):
            pass
    with tr.span("flush"):
        pass
    assert seen == ["dispatch", "dispatch/prepare", "dispatch/call", "flush"]
    assert [r[0] for r in tr.ring] == ["dispatch/prepare", "dispatch/call",
                                       "dispatch", "flush"]


def test_the_ring_is_bounded(monkeypatch):
    monkeypatch.setattr(spans_mod, "RING_RECORDS", 8)
    tr = SpanTracker(Metrics())
    mark = tr.appended
    for step in range(1, 6):
        tr.begin_step(step)
        with tr.span("dispatch"):
            pass
        tr.end_step()
    assert tr.appended == 10 and len(tr.ring) == 8
    kept = tr.records_since(mark)
    assert len(kept) == 8 and kept[0][3] == 2 and kept[-1] == tr.ring[-1]
    assert tr.records_since(tr.appended - 3) == kept[-3:]
    assert tr.records_since(tr.appended) == []
    # the totals are not the ring's: nothing of them is dropped
    assert tr.metrics.get("span: loop")[1] == 5
    # the distribution is over the iterations the ring still holds
    assert tr.step_timeline(mark)["sampled"] == 4


# -- steps in flight --------------------------------------------------------

class _Loss:
    def __init__(self, ready):
        self.ready = ready

    def is_ready(self):
        return self.ready


class _Array(_Loss):
    """One the window can materialize."""

    def __array__(self, dtype=None, copy=None):
        return np.zeros((), np.float32)


def _pending(neval0, ready):
    return _PendingStep(neval0, 1, 0, _Array(ready), np.True_, {}, 0.1, 4,
                        0.0, 0.0, {})


@pytest.mark.parametrize("ready, want", [
    ([], 0), ([True], 0), ([False], 1), ([True, False], 1),
    ([True, False, False], 2), ([True, True, True], 0),
    # the device runs in order: nothing older than a ready step is asked
    ([False, True, False], 1)])
def test_in_flight_counts_the_unready_pending_steps(ready, want):
    w = _HostSyncWindow(10)
    for i, r in enumerate(ready):
        w.push(_pending(i + 1, r))
    assert w.in_flight() == want


def test_in_flight_stops_at_the_first_ready_step():
    asked = []

    class Loss(_Loss):
        def is_ready(self):
            asked.append(self)
            return self.ready

    w = _HostSyncWindow(10)
    for i, r in enumerate([True] * 8 + [False]):
        p = _pending(i + 1, r)
        p.loss = Loss(r)
        w.push(p)
    assert w.in_flight() == 1 and len(asked) == 2


def test_device_empty_books_exactly_the_zeros_by_what_preceded_them():
    opt = _opt(1)
    opt._start_obs_run()
    w = opt._window = _HostSyncWindow(10)
    assert opt._note_in_flight() == 0               # the call's start
    w.push(_pending(1, False))
    assert opt._note_in_flight() == 1
    w.push(_pending(2, False))
    assert opt._note_in_flight() == 2
    for p in w.pending:
        p.loss.ready = True
    assert opt._note_in_flight() == 0               # ran dry, no flush
    # a cadence flush leaves step 2 pending: while it runs the device is
    # not empty, and nothing is booked
    w.pending[-1].loss.ready = False
    assert [e.neval0 for e in w.flush("cadence")[0]] == [1]
    assert opt._note_in_flight() == 1
    # it ended before the host came back: the zero is the flush's, not
    # ``none``, though ``pending`` is not empty
    w.pending[0].loss.ready = True
    assert opt._note_in_flight() == 0
    w.push(_pending(3, True))
    assert opt._note_in_flight() == 0               # dispatched since: none
    w.flush("trigger")                              # drains
    assert not w.pending
    assert opt._note_in_flight() == 0               # after the drain
    w.push(_pending(4, False))
    assert opt._note_in_flight() == 1
    rows = {path: (total, count)
            for path, _, _, total, count in opt.spans.rows()}
    assert rows["dispatch/in-flight"] == (5.0, 9)
    assert rows["dispatch/device-empty"] == (0.0, 5)
    assert opt._in_flight_hist == {0: 5, 1: 3, 2: 1}
    assert opt._empty_after == {"start": 1, "none": 2, "cadence": 1,
                                "trigger": 1}


def test_the_count_rides_the_step_events(run):
    opt, events = run
    steps = [e for e in events if e["type"] == "step"]
    assert len(steps) == 11
    assert all(isinstance(e["in_flight"], int) and e["in_flight"] >= 0
               for e in steps)
    # the first dispatch of a call finds nothing in flight; the one after
    # an epoch's flush (epochs of 4 steps, cadence 10) at most the step
    # the flush left running
    assert steps[0]["in_flight"] == 0
    for e in steps:
        if e["step"] in (5, 9):
            assert e["in_flight"] <= 1, e
    total, count = opt.metrics.get("span: dispatch/in-flight")
    assert count == 11 and total == sum(e["in_flight"] for e in steps)


# -- the event and the log line ---------------------------------------------

def test_an_optimize_call_emits_one_valid_step_timeline(run):
    opt, events = run
    (t,) = [e for e in events if e["type"] == "step_timeline"]
    validate_event(json.loads(json.dumps(t)))
    assert t["steps"] == t["sampled"] == 11
    assert sum(t["in_flight"].values()) == 11
    assert set(t["in_flight"]) <= {"0", "1", "2", "3"}
    # the call's start; the two epoch flushes each left a step running,
    # which a CPU step this small may end before the host is back (booked
    # under ``epoch``), as any other may (``none``)
    assert t["device_empty"]["start"] == 1
    assert t["device_empty"].get("epoch", 0) <= 2
    assert sum(t["device_empty"].values()) == t["in_flight"]["0"]
    assert t["flushes"] == {"epoch": {"count": 2, "kept": 2},
                            "run-end": {"count": 1, "kept": 0}}
    assert opt.metrics.get("span: flush/kept") == (0.0, 2)
    assert list(opt._window.flush_steps) == [3, 7, 11]
    for key in ("iter_ms", "call_ms", "between_calls_ms"):
        d = t[key]
        assert 0 <= d["p50"] <= d["p95"] <= d["max"]
    assert t["call_ms"]["max"] <= t["iter_ms"]["max"]
    assert len(t["slowest"]) == 5
    assert all(s["span"] in LEAVES + ("host-wait", "flush")
               for s in t["slowest"])
    # the event comes before the call's ``run_end``
    types = [e["type"] for e in events]
    assert types.index("step_timeline") < types.index("run_end")
    with pytest.raises(ValueError, match="missing"):
        validate_event({k: v for k, v in t.items() if k != "slowest"})
    # ``flushes`` came with schema v11: an older event reads without it
    assert t["v"] == obs_events.SCHEMA_VERSION == 11
    older = {k: v for k, v in t.items() if k != "flushes"}
    with pytest.raises(ValueError, match="flushes"):
        validate_event(older)
    validate_event(dict(older, v=10))


def test_a_second_call_summarises_its_own_iterations(event_log):
    opt = _opt(3)
    opt.optimize()
    opt.set_end_when(max_iteration(8))
    opt.optimize()
    first, second = [e for e in event_log.ring_events()
                     if e["type"] == "step_timeline"]
    assert first["steps"] == 3 and second["steps"] == 5
    assert {s["step"] for s in second["slowest"]} == {4, 5, 6, 7, 8}


def test_the_five_slowest_are_the_five_slowest(monkeypatch):
    """A hand-driven clock: iteration ``i`` lasts ``walls[i]`` ms, of which
    the call takes all but 2 (1 of ``data-load`` before it, 1 of
    ``bookkeep`` after)."""
    clock = [100.0]
    monkeypatch.setattr(spans_mod, "time", types.SimpleNamespace(
        perf_counter=lambda: clock[0]))
    walls = [10, 50, 12, 11, 90, 13, 70, 14, 30, 60, 15, 16]
    tr = SpanTracker(Metrics())

    def spend(name, ms):
        with tr.span(name):
            clock[0] += ms / 1e3

    for step, wall in enumerate(walls, start=1):
        tr.begin_step(step)
        spend("data-load", 1)
        with tr.span("dispatch"):
            spend("call", wall - 2)
        spend("bookkeep", 1)
        tr.end_step()
    spend("flush", 500)                 # after the last iteration: not its
    t = tr.step_timeline()
    assert t["sampled"] == 12
    assert [(s["step"], s["ms"]) for s in t["slowest"]] == [
        (5, 90.0), (7, 70.0), (10, 60.0), (2, 50.0), (9, 30.0)]
    # the longest span is a leaf: ``dispatch/call``, not its parent
    assert all(s["span"] == "dispatch/call" and s["span_ms"] == s["ms"] - 2
               for s in t["slowest"])
    assert t["iter_ms"] == {"p50": 15.0, "p95": 90.0, "max": 90.0}
    assert t["call_ms"] == {"p50": 13.0, "p95": 88.0, "max": 88.0}
    assert t["between_calls_ms"] == {"p50": 2.0, "p95": 2.0, "max": 2.0}
    assert tr.step_timeline(since=tr.appended) is None
    # the log line (``SpanTracker.report()``'s successor) says all of it
    line = render_timeline(dict(
        t, steps=12, in_flight={"0": 2, "1": 10},
        device_empty={"start": 1, "cadence": 1},
        flushes={"cadence": {"count": 1, "kept": 1},
                 "run-end": {"count": 1, "kept": 0}}))
    assert "\n" not in line
    for piece in ("12 iterations (12 in the ring)",
                  "iteration p50 15.000 p95 90.000 max 90.000 ms",
                  "dispatch/call p50 13.000", "between two calls p50 2.000",
                  "{0: 2, 1: 10}", "{start: 1, cadence: 1}",
                  "left a step in flight {cadence: 1 of 1, run-end: 0 of 1}",
                  "step 5 90.000 ms (dispatch/call 88.000)"):
        assert piece in line, (piece, line)


def test_the_loop_logs_the_line(caplog, event_log):
    with caplog.at_level(logging.INFO, logger="bigdl_tpu.optim"):
        _opt(2).optimize()
    (line,) = [r.getMessage() for r in caplog.records
               if r.getMessage().startswith("step timeline")]
    assert "2 iterations (2 in the ring)" in line


def test_a_run_of_the_lm_toy_sizes_emits_it():
    """The benchmark's LM runner at toy sizes, untraced: each of its three
    ``optimize()`` calls leaves its timeline in the event ring."""
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
    from benchmark import harness
    from test_lm_rehearsal import CELL, TOY
    result = harness.run_cell(CELL, 13, 0.5, False, sizes=TOY)
    assert result["correct"] is True, result["check"]
    timelines = [e for e in obs_events.get().ring_events()
                 if e["type"] == "step_timeline"]
    obs_events.reset()
    assert [t["steps"] for t in timelines[:2]] == [1, 2]
    window = timelines[2]
    validate_event(json.loads(json.dumps(window)))
    assert window["steps"] >= result["attempted"] >= 1
    assert sum(window["in_flight"].values()) == window["steps"]


# -- the crash bundle -------------------------------------------------------

def test_crash_bundle_takes_the_rings_tail_and_the_open_span(tmp_path,
                                                             event_log):
    tr = SpanTracker(Metrics())
    for step in range(1, spans_mod.TAIL_ITERATIONS + 9):
        tr.begin_step(step)
        with tr.span("dispatch"):
            with tr.span("call"):
                pass
        tr.end_step()
    tr.begin_step(99)
    with tr.span("dispatch"):
        with tr.span("call"):
            path = dump_crash_bundle("unit-test", run_dir=str(tmp_path))
    tails = json.load(open(os.path.join(path, "spans.json")))
    (tail,) = [t for t in tails if t["step"] == 99]
    assert [s[0] for s in tail["open"]] == ["dispatch", "dispatch/call"]
    assert tail["open"][1][1] <= tail["now"]
    steps = [r[3] for r in tail["records"] if r[0] == "loop"]
    assert steps == list(range(9, spans_mod.TAIL_ITERATIONS + 9))
    assert tail["records"][0][3] == 9       # whole iterations only
    # a loop that closes no iteration (DistriOptimizer's) is cut by count
    other = SpanTracker(Metrics())
    for _ in range(20 * spans_mod.TAIL_ITERATIONS):
        with other.span("dispatch"):
            pass
    assert len(other.tail()["records"]) == 16 * spans_mod.TAIL_ITERATIONS

"""Request-forensics suite (docs/observability.md "Request
forensics", marker ``forensic``).

The PR tentpole contracts:

- the always-on :class:`FlightRecorder` assembles one record per
  request from the hooks that already exist at every seam — router
  admission/shed/requeue, engine compute, continuous-decoder
  admit/boundary/retire — bounded by the ``BIGDL_OBS_RECORDER_N`` ring;
- tail-based retention: with head sampling at 0, healthy traffic emits
  ZERO trace events while every anomalous request (error, shed,
  requeue, SLO miss, tail latency) emits its full hop chain PLUS a
  schema-v7 ``forensic`` bundle carrying the record and the ring's
  neighboring-request context, counted by
  ``forensic_requests_total{kind=...}``;
- the recorder is free at the device: zero new compiled programs and
  zero added host syncs with the recorder on vs off (the PR-13
  jit-trap/xcache/sync-accounting audit pattern);
- deterministic replay: ``tools/request_replay.py`` re-executes a
  recorded request (same seed, flags, quant recipe, weight version) on
  a fresh decoder and the greedy token stream is identical across the
  paged × prefix × spec × int8-KV matrix; a rolled weight version
  produces a NON-empty diff with the version mismatch reported.
"""
import importlib.util
import os
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.obs import events as obs_events
from bigdl_tpu.obs import metrics as obs_metrics
from bigdl_tpu.obs import recorder
from bigdl_tpu.obs.trace import Trace
from bigdl_tpu.serve import (DeadReplicaError, Router, SheddedError,
                             WeightStore, xcache)
from bigdl_tpu.serve.decode import ContinuousDecoder
from bigdl_tpu.utils.random import set_seed

pytestmark = [pytest.mark.obs, pytest.mark.forensic]


def _tool(name):
    path = os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _lm(seed=1):
    set_seed(seed)
    return TransformerLM(vocab_size=11, d_model=16, n_heads=2,
                        n_layers=2, hidden=32)


class FakeReplica:
    """Deterministic router replica: resolves each submit on a worker
    thread after ``service_s``; output = 2x the input row."""

    transport = "inproc"

    def __init__(self, name="fake", service_s=0.0, exc=None):
        self.name = name
        self.service_s = service_s
        self.exc = exc
        self.submitted = 0
        self._alive = True

    def submit(self, x):
        self.submitted += 1
        fut = Future()

        def work():
            if self.service_s:
                time.sleep(self.service_s)
            if self.exc is not None:
                fut.set_exception(self.exc)
            elif not self._alive:
                fut.set_exception(DeadReplicaError(self.name))
            else:
                fut.set_result(np.asarray(x) * 2)

        threading.Thread(target=work, daemon=True).start()
        return fut

    def inflight(self):
        return 0

    def alive(self):
        return self._alive

    def stats(self):
        return {"submitted": self.submitted}

    def close(self, drain=True):
        self._alive = False


class DyingReplica(FakeReplica):
    """Accepts ``die_after`` submits, then fails everything with
    DeadReplicaError and reports dead."""

    def __init__(self, name="dying", die_after=2):
        super().__init__(name)
        self.die_after = die_after

    def submit(self, x):
        if self.submitted >= self.die_after:
            self._alive = False
        if not self._alive:
            self.submitted += 1
            fut = Future()
            fut.set_exception(DeadReplicaError(self.name))
            return fut
        return super().submit(x)


def _events_of(etype):
    return [e for e in obs_events.get().ring_events()
            if e["type"] == etype]


# ---------------------------------------------------------------------------
# schema v7: the forensic event type
# ---------------------------------------------------------------------------

class TestSchemaV7:
    def test_forensic_roundtrip_validates(self):
        from bigdl_tpu.obs.events import validate_event
        obs_events.configure(None)
        e = obs_events.emit("forensic", kind="shed", stage="admission",
                            trace_id="t1", record={"outcome": "shed"},
                            context=[])
        assert validate_event(e) is e
        assert e["v"] >= 7

    @pytest.mark.parametrize("kind,fields", [
        ("error", {"error": "ValueError: boom"}),
        ("shed", {"stage": "replica"}),
        ("requeue", {"attempts": 2}),
        ("slo_miss", {"slo": "deadline"}),
        ("slow", {"e2e_ms": 9.0, "bound_ms": 3.0}),
        ("replica_death", {"replica": "r0"}),
        ("partition", {"replica": "r1"}),
    ])
    def test_every_kind_has_required_fields(self, kind, fields):
        from bigdl_tpu.obs.events import (FORENSIC_KINDS, validate_event)
        assert kind in FORENSIC_KINDS
        e = {"v": 7, "ts": 0.0, "proc": 0, "type": "forensic",
             "kind": kind, "trace_id": "t", "record": {}, **fields}
        validate_event(e)
        # dropping any required per-kind field must fail validation
        for missing in FORENSIC_KINDS[kind]:
            bad = {k: v for k, v in e.items() if k != missing}
            with pytest.raises(ValueError, match=missing):
                validate_event(bad)

    def test_unknown_kind_errors(self):
        from bigdl_tpu.obs.events import validate_event
        e = {"v": 7, "ts": 0.0, "proc": 0, "type": "forensic",
             "kind": "gremlin", "trace_id": "t", "record": {}}
        with pytest.raises(ValueError, match="gremlin"):
            validate_event(e)


# ---------------------------------------------------------------------------
# FlightRecorder unit behavior
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_bounded_evicts_oldest(self):
        fr = recorder.FlightRecorder(ring=3)
        for i in range(5):
            fr.open(f"t{i}", priority=i)
        recs = fr.records()
        assert len(recs) == 3
        assert [r["trace_id"] for r in recs] == ["t2", "t3", "t4"]

    def test_note_creates_on_miss_and_export_pops(self):
        fr = recorder.FlightRecorder()
        fr.note("t0", rid="d0/1", flags={"paged": True})
        fr.note("t0", tokens=[1, 2, 3], skipped=None)
        rec = fr.export_notes("t0")
        assert rec == {"rid": "d0/1", "flags": {"paged": True},
                       "tokens": [1, 2, 3]}
        assert fr.export_notes("t0") is None      # popped

    def test_classify_precedence(self):
        fr = recorder.FlightRecorder(tail_ms=5.0)
        cases = [
            ({"outcome": "failed", "death_replica": "r0",
              "error": "x"}, "replica_death"),
            ({"outcome": "failed", "error": "ValueError: x"}, "error"),
            ({"outcome": "shed", "shed_stage": "replica",
              "requeues": 2}, "shed"),
            ({"outcome": "ok", "blip_replica": "r1",
              "requeues": 1}, "partition"),
            ({"outcome": "ok", "requeues": 1,
              "slo_miss": "deadline"}, "requeue"),
            ({"outcome": "ok", "slo_miss": "ttft",
              "e2e_ms": 100.0}, "slo_miss"),
            ({"outcome": "ok", "e2e_ms": 100.0}, "slow"),
            ({"outcome": "ok", "e2e_ms": 1.0}, None),
        ]
        for rec, want in cases:
            kind, _ = fr.classify(rec)
            assert kind == want, (rec, kind, want)

    def test_windowed_p99_multiplier(self):
        fr = recorder.FlightRecorder(tail_ms=0.0, tail_p99x=3.0)
        assert fr._p99_bound() is None            # window too thin
        for _ in range(30):
            fr._lat.append(2.0)
        bound = fr._p99_bound()
        assert bound == pytest.approx(6.0)
        assert fr.classify({"outcome": "ok", "e2e_ms": 7.0})[0] == "slow"
        assert fr.classify({"outcome": "ok", "e2e_ms": 5.0})[0] is None

    def test_finalize_emits_bundle_only_when_anomalous(self):
        obs_events.configure(None)
        fr = recorder.FlightRecorder()
        # healthy, not head-sampled: retained in the ring, no events
        fr.open("ok1", priority=0)
        assert fr.finalize("ok1", "ok", e2e_ms=1.0) is False
        # healthy but head-sampled: trace emission stays on
        fr.open("ok2")
        assert fr.finalize("ok2", "ok", head_sampled=True) is True
        assert _events_of("forensic") == []
        # anomalous: forensic bundle + counter + emit=True
        for i in range(3):
            fr.open(f"n{i}", priority=i, e2e_ms=1.0)
            fr.finalize(f"n{i}", "ok")
        fr.open("bad", replica="r0")
        assert fr.finalize("bad", "failed",
                           error="ValueError: boom") is True
        (e,) = _events_of("forensic")
        assert e["kind"] == "error" and e["trace_id"] == "bad"
        assert e["record"]["outcome"] == "failed"
        assert e["record"]["anomaly"] == "error"
        # neighboring-request context rides the bundle
        assert {c["trace_id"] for c in e["context"]} <= {"ok1", "ok2",
                                                         "n0", "n1", "n2"}
        assert len(e["context"]) >= 1
        snap = obs_metrics.get().snapshot()
        assert obs_metrics.family_total(
            snap, "forensic_requests_total", kind="error") == 1

    def test_disabled_recorder_is_inert(self, monkeypatch):
        monkeypatch.setenv(recorder.ENV_RECORDER, "0")
        recorder.reset()
        assert recorder.get() is None
        recorder.note("t", rid="x")               # all no-ops
        assert recorder.export_notes("t") is None
        assert recorder.finalize("t", "failed") is False
        assert recorder.finalize("t", "failed", head_sampled=True)


# ---------------------------------------------------------------------------
# tail-based retention through the router (end to end)
# ---------------------------------------------------------------------------

class TestTailRetention:
    def test_healthy_sample0_zero_events_full_records(self):
        """THE retention contract: head sampling at 0 + healthy traffic
        = zero trace events, yet EVERY request has a complete record
        with a monotone hop timeline in the ring."""
        obs_events.configure(None)
        with Router([FakeReplica("a")], shed=False,
                    trace_sample=0.0) as router:
            futs = [router.submit(np.ones((2,), np.float32),
                                  priority=1) for _ in range(8)]
            for f in futs:
                f.result(timeout=10)
        assert _events_of("trace") == []
        assert _events_of("forensic") == []
        recs = [r for r in recorder.get().records()
                if r.get("outcome") is not None]
        assert len(recs) == 8
        for r in recs:
            assert r["outcome"] == "ok"
            assert r["replica"] == "a"
            assert r["transport"] == "inproc"
            assert r["priority"] == 1
            assert r["e2e_ms"] >= 0.0
            phases = [h[0] for h in r["hops"]]
            it = iter(phases)
            assert all(p in it for p in
                       ("admit", "queue", "dispatch", "complete"))
            stamps = [h[1] for h in r["hops"]]
            assert stamps == sorted(stamps)

    def test_error_request_emits_trace_and_forensic(self):
        obs_events.configure(None)
        bad = FakeReplica("bad", exc=ValueError("boom"))
        with Router([bad], shed=False, trace_sample=0.0) as router:
            fut = router.submit(np.ones((2,), np.float32))
            with pytest.raises(ValueError):
                fut.result(timeout=10)
        (tr,) = _events_of("trace")
        assert tr["status"] == "failed"
        (fo,) = _events_of("forensic")
        assert fo["kind"] == "error"
        assert fo["error"] == "ValueError: boom"
        assert fo["record"]["hops"]

    def test_shed_requests_bundle_and_healthy_stay_silent(self):
        obs_events.configure(None)
        with Router([FakeReplica("a", service_s=0.05)], shed=True,
                    est_ms=50.0, trace_sample=0.0) as router:
            futs = [router.submit(np.ones((2,), np.float32),
                                  priority=1, slo_ms=60)
                    for _ in range(12)]
            shed = 0
            for f in futs:
                try:
                    f.result(timeout=10)
                except SheddedError:
                    shed += 1
        assert shed > 0
        forensics = _events_of("forensic")
        assert len(forensics) == shed
        assert all(e["kind"] == "shed" for e in forensics)
        assert all(e["stage"] == "admission" for e in forensics)
        # tail retention: exactly the shed chains were emitted
        assert len(_events_of("trace")) == shed
        snap = obs_metrics.get().snapshot()
        assert obs_metrics.family_total(
            snap, "forensic_requests_total", kind="shed") == shed

    def test_requeued_request_keeps_death_involvement(self):
        obs_events.configure(None)
        dying = DyingReplica("dying", die_after=2)
        with Router([dying, FakeReplica("ok")], shed=False,
                    trace_sample=0.0) as router:
            futs = [router.submit(np.ones((2,), np.float32))
                    for _ in range(8)]
            for f in futs:
                f.result(timeout=10)           # zero lost futures
        forensics = _events_of("forensic")
        assert forensics
        for e in forensics:
            assert e["kind"] in ("requeue", "replica_death")
            rec = e["record"]
            assert rec["outcome"] == "ok"
            assert rec.get("requeues", 0) >= 1 \
                or rec.get("death_replica") == "dying"
            assert "requeue" in [h[0] for h in rec["hops"]]

    def test_slo_miss_completed_late_is_bundled(self):
        obs_events.configure(None)
        with Router([FakeReplica("a", service_s=0.05)], shed=False,
                    trace_sample=0.0) as router:
            fut = router.submit(np.ones((2,), np.float32), slo_ms=1)
            fut.result(timeout=10)
        (e,) = _events_of("forensic")
        assert e["kind"] == "slo_miss" and e["slo"] == "deadline"
        assert e["record"]["outcome"] == "ok"

    def test_head_sampling_composes_with_tail(self):
        """sample=1.0 + healthy traffic: every trace emitted (head),
        zero forensic bundles (no anomalies)."""
        obs_events.configure(None)
        with Router([FakeReplica("a")], shed=False,
                    trace_sample=1.0) as router:
            futs = [router.submit(np.ones((2,), np.float32))
                    for _ in range(4)]
            for f in futs:
                f.result(timeout=10)
        assert len(_events_of("trace")) == 4
        assert _events_of("forensic") == []


# ---------------------------------------------------------------------------
# decode-side record assembly + the zero-cost audit
# ---------------------------------------------------------------------------

class TestDecodeRecord:
    def test_record_carries_the_replay_recipe(self):
        lm = _lm()
        store = WeightStore()
        dec = ContinuousDecoder(lm, max_slots=2, n_pos=16,
                                page_size=4, sync_interval=2)
        dec.weights_version = store.put_model(lm)
        tr = Trace()
        fut = dec.submit([1, 2, 3], 5, trace=tr)
        dec.run()
        row = fut.result()
        rec = recorder.get().get(tr.trace_id)
        assert rec["tokens"] == row
        assert rec["seed_len"] == 3 and rec["n_words"] == 5
        assert rec["seed_hash"] == recorder.seed_hash([1, 2, 3])
        assert rec["flags"] == dec.decode_flags()
        assert rec["flags"]["paged"] and rec["flags"]["page_size"] == 4
        assert rec["weights_version"] == 1
        assert rec["decoder"] == dec.name
        assert rec["rid"].startswith(dec.name)
        assert rec["kv_pages"] >= 1 and rec["start_pos"] == 0

    def test_recorder_adds_zero_programs_and_zero_syncs(self,
                                                        monkeypatch):
        """The PR-13 audit: same decode load with the recorder OFF
        (warm) then ON — zero new executable-cache compiles, identical
        host-sync count."""
        lm = _lm()

        def drive():
            dec = ContinuousDecoder(lm, max_slots=2, n_pos=16,
                                    page_size=4, sync_interval=2)
            futs = [dec.submit(s, 4, trace=Trace())
                    for s in ([1, 2, 3], [4, 5], [6, 7, 8])]
            dec.run()
            rows = [f.result() for f in futs]
            return rows, dec.stats()["host_syncs"]

        monkeypatch.setenv(recorder.ENV_RECORDER, "0")
        recorder.reset()
        rows_off, syncs_off = drive()

        monkeypatch.delenv(recorder.ENV_RECORDER, raising=False)
        recorder.reset()
        compiles0 = xcache.get().stats()["compiles"]
        rows_on, syncs_on = drive()
        assert rows_on == rows_off
        assert syncs_on == syncs_off
        assert xcache.get().stats()["compiles"] == compiles0
        # and the records really were assembled on the ON pass
        recs = [r for r in recorder.get().records() if "tokens" in r]
        assert len(recs) == 3


# ---------------------------------------------------------------------------
# deterministic replay
# ---------------------------------------------------------------------------

REPLAY_MATRIX = [
    pytest.param({}, id="paged"),
    pytest.param({"prefix_cache": True}, id="prefix"),
    pytest.param({"spec_k": 2}, id="spec"),
    pytest.param({"kv_quant": "int8"}, id="int8kv"),
]


class TestReplay:
    def _record_one(self, cfg, store):
        lm = _lm(seed=1)
        dec = ContinuousDecoder(lm, max_slots=2, n_pos=16,
                                page_size=4, sync_interval=2, **cfg)
        dec.weights_version = store.put_model(lm)
        tr = Trace()
        fut = dec.submit([1, 2, 3, 4], 5, trace=tr)
        dec.run()
        fut.result()
        return recorder.get().get(tr.trace_id)

    @pytest.mark.parametrize("cfg", REPLAY_MATRIX)
    def test_replay_token_identical(self, cfg):
        """A fresh decoder + the pinned weight version reproduce the
        committed stream exactly — even when the replay model was
        initialized with DIFFERENT weights (the store restores v1)."""
        rr = _tool("request_replay")
        store = WeightStore()
        record = self._record_one(cfg, store)
        report = rr.replay_request(record, _lm(seed=9), store=store)
        assert report["version_mismatch"] is None
        assert report["seed_hash_ok"]
        assert report["match"], report
        assert report["replayed"] == record["tokens"]

    def test_rolled_version_reports_mismatch_and_diff(self):
        rr = _tool("request_replay")
        store = WeightStore(keep=2)
        record = self._record_one({}, store)
        # roll the fleet twice: v1 falls out of the retained window
        store.put_model(_lm(seed=5))
        store.put_model(_lm(seed=6))
        report = rr.replay_request(record, _lm(seed=9), store=store)
        assert report["version_mismatch"] is not None
        assert "weight version 1" in report["version_mismatch"]
        assert not report["match"]
        assert report["diverge_at"] is not None

    def test_unreplayable_record_is_a_typed_error(self):
        rr = _tool("request_replay")
        with pytest.raises(ValueError, match="not replayable"):
            rr.replay_request({"outcome": "ok"}, _lm())


# ---------------------------------------------------------------------------
# tools: report section + serve_top line
# ---------------------------------------------------------------------------

class TestForensicTools:
    def _anomalize(self):
        fr = recorder.get()
        fr.open("aaaa1111", priority=1, replica="r0")
        fr.finalize("aaaa1111", "failed", error="ValueError: boom",
                    trace=None, e2e_ms=12.5,
                    hops=[["admit", 0.0], ["queue", 0.001],
                          ["dispatch", 0.002], ["complete", 0.0125]])

    def test_obs_report_renders_forensics_section(self, obs_run_dir):
        self._anomalize()
        rep = _tool("obs_report")
        events, bad, bundles = rep.load_run(obs_run_dir)
        assert not bad
        out = rep.render(events, bad, bundles, obs_run_dir)
        assert "## Forensics" in out
        assert "error=1" in out
        assert "aaaa1111"[:8] in out

    def test_obs_report_strict_accepts_v7(self, obs_run_dir, capsys):
        self._anomalize()
        rep = _tool("obs_report")
        assert rep.main([obs_run_dir, "--strict"]) == 0
        assert "Forensics" in capsys.readouterr().out

    def test_serve_top_anomalies_line(self):
        st = _tool("serve_top")
        reg = obs_metrics.get()
        assert st.anomalies_line({}, None, 1.0) is None
        reg.counter("forensic_requests_total", kind="error").inc()
        reg.counter("forensic_requests_total", kind="slow").inc(2)
        reg.gauge("forensic_worst_e2e_ms", agg="max").set(42.0)
        cur = reg.snapshot()
        line = st.anomalies_line(cur, None, 1.0)
        assert "error=1" in line and "slow=2" in line
        assert "worst e2e 42.0 ms" in line
        # an idle window with history reports quiet, not stale totals
        assert st.anomalies_line(cur, cur, 1.0) == "anomalies: none"
        reg.counter("forensic_requests_total", kind="error").inc()
        line = st.anomalies_line(reg.snapshot(), cur, 1.0)
        assert "error=1" in line and "slow" not in line

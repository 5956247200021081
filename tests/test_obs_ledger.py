"""Performance-observatory ledger tests (docs/observability.md
"Performance observatory", pytest -m obs).

Load-bearing contracts:

- ledger capture happens at COMPILE TIME only: the capture counter
  tracks the executable cache's compile counter and never the dispatch
  counter (the warm-path audit, ISSUE 13 acceptance), and the live
  gauges are set only at flush/sync cadence boundaries;
- AOT captures (``ExecutableCache.get_or_compile``) carry the full
  cost AND memory analysis keyed by the SAME xcache keys; tracked-jit
  captures carry flops/bytes from the lowering alone;
- the cost normalizer accepts both the dict and the list forms of
  ``cost_analysis()`` (the list form is what this container's jax
  returns — indexing it used to silently nan bench MFU);
- ``bench.py`` MFU and the ledger-derived MFU agree within 1% (they
  resolve flops AND peak through one code path, so divergence means a
  second probe crept back in);
- the train loop publishes finite windowed ``train_mfu``; the decoder
  publishes ``decode_model_flops_util``; both through ledger flops;
- the device-memory sampler joins on close and watermarks correctly;
  HBM tenants appear/disappear with their owners;
- a 2-replica pool drill shows ledger gauges over ``merged_registry()``
  with a jit-trap proving the serving/ledger path costs no new
  compiles (the subprocess variant rides the slow marker);
- ``EventLog`` rotates at ``BIGDL_OBS_MAX_MB`` with keep-last
  semantics; schema v3 ``ledger`` events round-trip validation.
"""
import json
import math
import os
import time

import numpy as np
import pytest

import jax

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset import DataSet, Sample
from bigdl_tpu.dataset.transformer import SampleToBatch
from bigdl_tpu.obs import events as obs_events
from bigdl_tpu.obs import ledger as obs_ledger
from bigdl_tpu.obs import metrics as obs_metrics
from bigdl_tpu.obs.events import validate_event
from bigdl_tpu.optim import LocalOptimizer, max_iteration
from bigdl_tpu.serve import xcache
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


def _opt(steps=5, **kw):
    opt = LocalOptimizer(_mlp(), _data(), nn.ClassNLLCriterion(), **kw)
    opt.set_state(T(learningRate=0.5))
    opt.set_end_when(max_iteration(steps))
    return opt


# ---------------------------------------------------------------------------
# capture plumbing
# ---------------------------------------------------------------------------

class TestCapture:
    def test_aot_capture_keyed_by_xcache_key(self):
        """get_or_compile ledgers the compiled executable under the
        cache's own key, with cost AND memory analysis fields."""
        import jax.numpy as jnp

        f = jax.jit(lambda x: (x @ x).sum())
        x = jnp.ones((16, 16), jnp.float32)
        cache = xcache.get()
        exe, fresh = cache.get_or_compile(f, "probe", (x,))
        assert fresh
        key = cache.key_for("probe", (x,))
        led = obs_ledger.get()
        entry = led.newest("probe")
        assert entry is not None and entry.key == key
        assert entry.source == "aot"
        assert entry.flops > 0 and entry.bytes_accessed > 0
        assert entry.peak_bytes is not None and entry.peak_bytes > 0
        assert entry.argument_bytes == x.size * 4

    def test_aot_hit_does_not_recapture(self):
        import jax.numpy as jnp

        f = jax.jit(lambda x: x * 2.0)
        x = jnp.ones((4,), jnp.float32)
        cache = xcache.get()
        cache.get_or_compile(f, "probe2", (x,))
        n = obs_ledger.get().captures
        cache.get_or_compile(f, "probe2", (x,))   # warm hit
        assert obs_ledger.get().captures == n

    def test_tracked_jit_captures_once_per_key(self):
        import jax.numpy as jnp

        fn = xcache.tracked_jit(lambda x: x @ x, "tj_probe")
        x = jnp.ones((8, 8), jnp.float32)
        led = obs_ledger.get()
        n0 = led.captures
        fn(x)
        assert led.captures == n0 + 1
        for _ in range(3):          # warm dispatches: ledger untouched
            fn(x)
        assert led.captures == n0 + 1
        entry = led.newest("tj_probe")
        assert entry.source == "jit"
        assert entry.flops > 0
        assert entry.peak_bytes is None   # lowering-only capture

    def test_master_switch_disables_capture(self, monkeypatch):
        import jax.numpy as jnp

        monkeypatch.setenv(obs_ledger.ENV_LEDGER, "0")
        fn = xcache.tracked_jit(lambda x: x + 1, "tj_off")
        fn(jnp.ones((4,), jnp.float32))
        assert obs_ledger.get().newest("tj_off") is None

    def test_exec_event_emitted_and_validates(self, obs_run_dir):
        import jax.numpy as jnp

        f = jax.jit(lambda x: x.sum())
        xcache.get().get_or_compile(f, "probe_ev",
                                    (jnp.ones((4,), jnp.float32),))
        evs = [e for e in obs_events.get().ring_events()
               if e["type"] == "ledger" and e["kind"] == "exec"]
        assert evs, "AOT capture must emit a ledger/exec event"
        for e in evs:
            validate_event(e)
        assert evs[-1]["fn"] == "probe_ev"

    def test_gauges_ride_registry(self):
        import jax.numpy as jnp

        f = jax.jit(lambda x: (x @ x).sum())
        xcache.get().get_or_compile(f, "probe_g",
                                    (jnp.ones((8, 8), jnp.float32),))
        snap = obs_metrics.get().snapshot()
        for fam in ("ledger_flops", "ledger_bytes_accessed",
                    "ledger_peak_hbm_bytes"):
            rows = [r for r in snap[fam]["series"]
                    if r["labels"].get("fn") == "probe_g"]
            assert rows and rows[0]["value"] > 0, fam
            assert snap[fam]["agg"] == "max"   # fleet merge dedupes


# ---------------------------------------------------------------------------
# live train MFU + the warm-path/cadence audit
# ---------------------------------------------------------------------------

@pytest.fixture
def v5e_peak(monkeypatch):
    """The CPU publishes no MFU (no datasheet peak), so the gauge tests
    run as if the step were on a v5e: same code path, a real peak."""
    monkeypatch.setattr(obs_ledger, "device_peak_flops",
                        lambda device=None: obs_ledger.PEAK_FLOPS["TPU v5e"])


class TestTrainMFU:
    def test_no_mfu_gauge_on_cpu(self):
        """A CPU run has a step wall time but no utilization."""
        _opt(steps=5).optimize()
        snap = obs_metrics.get().snapshot()
        assert not snap.get("train_mfu", {}).get("series")
        assert obs_metrics.family_total(
            snap, "train_step_wall_seconds", optimizer="local") > 0

    def test_windowed_gauges_finite_after_run(self, v5e_peak):
        _opt(steps=5).optimize()
        snap = obs_metrics.get().snapshot()
        mfu = obs_metrics.family_total(snap, "train_mfu",
                                       optimizer="local")
        wall = obs_metrics.family_total(snap, "train_step_wall_seconds",
                                        optimizer="local")
        assert math.isfinite(mfu) and mfu > 0
        assert math.isfinite(wall) and wall > 0

    def test_capture_only_at_compile_time(self):
        """The warm-path audit (TestTapsDispatch's sibling): over a
        10-step run the ledger captures exactly as many entries as the
        xcache registers compiles — dispatches 2..10 add nothing."""
        xcache.reset()
        obs_ledger.get().clear()
        _opt(steps=10).optimize()
        led = obs_ledger.get().stats()
        xs = xcache.get().stats()
        assert led["captures"] == xs["compiles"] > 0
        assert xs["hits"] >= 8      # the warm dispatches that captured 0

    def test_mfu_gauge_set_at_flush_cadence_only(self, monkeypatch,
                                                 v5e_peak):
        """Cadence audit: the train_mfu gauge is written once per host-
        sync window flush, never per step."""
        reg = obs_metrics.get()
        gauge = reg.gauge("train_mfu", "", agg="max", optimizer="local")
        sets = []
        orig = obs_metrics.Gauge.set

        def counting_set(self, v):
            if self is gauge:
                sets.append(v)
            return orig(self, v)

        monkeypatch.setattr(obs_metrics.Gauge, "set", counting_set)
        opt = _opt(steps=8)
        opt.optimize()
        flushes = len(opt._window.flush_steps)
        assert 0 < len(sets) <= flushes
        assert all(math.isfinite(v) and v > 0 for v in sets)


# ---------------------------------------------------------------------------
# bench <-> ledger cross-check (one cost code path)
# ---------------------------------------------------------------------------

_CROSSCHECK_SCRIPT = """
import json
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import bench as b
from bigdl_tpu.obs import ledger as obs_ledger
from bigdl_tpu.utils.random import set_seed

set_seed(1)
name, build, recs, unit, aflops, n_disp = next(
    c for c in b.configs() if c[0].startswith("LeNet"))
rate, step_ms, mfu, flops, loss, band, fetch = b.bench_config(
    build, recs, warmup=1, iters=1, windows=1, steps_per_dispatch=2)
entry = obs_ledger.get().newest(("bench_chunk", recs, 2))
print(json.dumps({"mfu": mfu, "flops": flops, "step_ms": step_ms,
                  "entry_flops": entry.flops if entry else None}))
"""


class TestBenchCrossCheck:
    def test_bench_flops_are_the_ledger_entry_and_cpu_has_no_mfu(self):
        """ISSUE 13 acceptance, on a machine without a chip: bench.py's
        per-step flops ARE the ledger entry it captured (one cost code
        path — a divergence means a second cost probe crept back in),
        and on the CPU, which has no datasheet peak, bench reports no
        MFU instead of dividing by another device's.  Runs in a
        subprocess like the real bench CLI — bench_config's
        donated-buffer warmup is not safe inside the suite's
        persistent-compile-cache process."""
        import subprocess
        import sys

        root = os.path.join(os.path.dirname(__file__), "..")
        out = subprocess.run(
            [sys.executable, "-c", _CROSSCHECK_SCRIPT], cwd=root,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        res = json.loads(out.stdout.strip().splitlines()[-1])
        assert res["entry_flops"] == res["flops"] > 0
        assert res["step_ms"] > 0
        assert res["mfu"] is None


# ---------------------------------------------------------------------------
# HBM: sampler + tenants
# ---------------------------------------------------------------------------

class TestDeviceMemorySampler:
    def _fake(self, seq):
        it = iter(seq)
        last = {"state": None}

        def fn():
            try:
                last["state"] = next(it)
            except StopIteration:
                pass
            return last["state"]
        return fn

    def test_publishes_and_watermarks(self):
        s = obs_ledger.DeviceMemorySampler(
            interval=0.005,
            stats_fn=self._fake([
                {"d0": {"bytes_in_use": 100, "bytes_limit": 1000}},
                {"d0": {"bytes_in_use": 400, "bytes_limit": 1000}},
                {"d0": {"bytes_in_use": 50, "bytes_limit": 1000}},
            ]))
        s.start()
        deadline = time.time() + 5.0
        while s.samples < 3 and time.time() < deadline:
            time.sleep(0.01)
        s.close()
        assert s.samples >= 3
        snap = obs_metrics.get().snapshot()
        assert obs_metrics.family_total(snap, "hbm_bytes_in_use",
                                        device="d0") == 50
        assert obs_metrics.family_total(snap, "hbm_bytes_peak",
                                        device="d0") == 400
        assert obs_metrics.family_total(snap, "hbm_bytes_limit",
                                        device="d0") == 1000

    def test_close_joins_thread(self):
        s = obs_ledger.DeviceMemorySampler(
            interval=0.005, stats_fn=lambda: {})
        s.start()
        t = s._thread
        s.close()
        assert s._thread is None and not t.is_alive()
        s.close()   # idempotent

    def test_hbm_events_validate(self, obs_run_dir):
        s = obs_ledger.DeviceMemorySampler(
            interval=0.005,
            stats_fn=lambda: {"d0": {"bytes_in_use": 7}})
        s.sample_once()
        evs = [e for e in obs_events.get().ring_events()
               if e["type"] == "ledger" and e["kind"] == "hbm"]
        assert evs and evs[-1]["in_use"] == 7
        for e in evs:
            validate_event(e)

    def test_cpu_backend_samples_to_nothing(self):
        # the real stats fn: CPU PJRT exposes no memory stats — the
        # sampler must tick cleanly and publish nothing
        s = obs_ledger.DeviceMemorySampler(interval=0.005)
        assert s.sample_once() == {}

    def test_env_autostart_and_reset_stops(self, monkeypatch):
        monkeypatch.setenv(obs_ledger.ENV_HBM_SAMPLE, "30")
        s = obs_ledger.maybe_start_sampler_from_env()
        assert s is not None and s._thread.is_alive()
        assert obs_ledger.maybe_start_sampler_from_env() is s  # once
        obs_ledger.reset()
        assert not s._stop.is_set() or s._thread is None


class TestTenants:
    def test_decoder_kv_pool_tenant_dropped_at_close(self):
        from bigdl_tpu.models.transformer import TransformerLM
        from bigdl_tpu.serve.decode import ContinuousDecoder
        set_seed(1)
        lm = TransformerLM(vocab_size=11, d_model=16, n_heads=2,
                           n_layers=2, hidden=32)
        dec = ContinuousDecoder(lm, max_slots=2, n_pos=16)
        snap = obs_metrics.get().snapshot()
        rows = [r for r in snap["hbm_tenant_bytes"]["series"]
                if r["labels"].get("tenant") == "kv_pool"
                and r["labels"].get("decoder") == dec.name]
        expected = sum(obs_ledger.tree_nbytes(c) for c in dec._caches)
        assert rows and rows[0]["value"] == expected > 0
        dec.close()
        snap = obs_metrics.get().snapshot()
        assert not [r for r in snap.get("hbm_tenant_bytes",
                                        {"series": []})["series"]
                    if r["labels"].get("decoder") == dec.name]

    def test_engine_weights_and_staged_tenants(self):
        from bigdl_tpu.serve import ServeEngine
        model = _mlp()
        eng = ServeEngine(model, max_batch=4, max_wait_ms=1,
                          name="tenant0")

        def tenant(name):
            snap = obs_metrics.get().snapshot()
            return obs_metrics.family_total(
                snap, "hbm_tenant_bytes", tenant=name, engine="tenant0")

        assert tenant("serve_weights") == \
            obs_ledger.tree_nbytes(eng._weights) > 0
        eng.stage_weights(model.params(), model.state())
        assert tenant("staged_weights") > 0
        eng.commit_weights()
        assert tenant("staged_weights") == 0
        eng.stage_weights(model.params(), model.state())
        eng.rollback_weights()
        assert tenant("staged_weights") == 0

    def test_weight_store_host_tenant_tracks_retention(self):
        from bigdl_tpu.serve.cluster import WeightStore
        model = _mlp()
        store = WeightStore(keep=2)
        one = None
        for _ in range(3):
            store.put(model.params(), model.state())
            snap = obs_metrics.get().snapshot()
            got = obs_metrics.family_total(snap, "hbm_tenant_bytes",
                                           tenant="weight_store_host")
            if one is None:
                one = got
        # keep=2: the third put retains two snapshots, not three
        assert got == 2 * one > 0

    def test_tenant_events_validate(self, obs_run_dir):
        obs_ledger.note_tenant("unit_test", 123, owner="t")
        evs = [e for e in obs_events.get().ring_events()
               if e["type"] == "ledger" and e["kind"] == "tenant"]
        assert evs and evs[-1]["bytes"] == 123
        for e in evs:
            validate_event(e)


# ---------------------------------------------------------------------------
# decode utilization
# ---------------------------------------------------------------------------

class TestDecodeUtilization:
    def test_util_gauges_published_per_boundary(self, v5e_peak):
        from bigdl_tpu.models.transformer import TransformerLM, lm_decode
        from bigdl_tpu.serve.decode import ContinuousDecoder
        set_seed(1)
        lm = TransformerLM(vocab_size=11, d_model=16, n_heads=2,
                           n_layers=2, hidden=32)
        dec = ContinuousDecoder(lm, max_slots=2, n_pos=16)
        try:
            assert dec._step_flops and dec._step_flops > 0
            futs = [dec.submit([1, 2, 3], 4), dec.submit([4, 5], 4)]
            dec.run()
            assert futs[0].result() == lm_decode(lm, [1, 2, 3], 4,
                                                 greedy=True)
            snap = obs_metrics.get().snapshot()
            util = obs_metrics.family_total(
                snap, "decode_model_flops_util", decoder=dec.name)
            toks = obs_metrics.family_total(
                snap, "decode_tokens_per_s", decoder=dec.name)
            assert math.isfinite(util) and util > 0
            assert toks > 0
        finally:
            dec.close()


# ---------------------------------------------------------------------------
# fleet drill: ledger truth over merged_registry + jit trap
# ---------------------------------------------------------------------------

class TestFleetLedgerDrill:
    def _drill(self, pool, run_dir):
        from bigdl_tpu.obs import alerts as obs_alerts

        rng = np.random.RandomState(0)
        for _ in range(6):
            pool.submit(rng.rand(6).astype(np.float32)).result(
                timeout=60)
        merged = pool.merged_registry()
        # ledger gauges carry fleet cost truth through the merge
        assert "ledger_flops" in merged
        assert obs_metrics.family_total(merged, "ledger_flops") > 0
        # a firing alert evaluated over merged_registry()
        eng = obs_alerts.AlertEngine(
            pool.merged_registry,
            [obs_alerts.Rule("queue_depth", "threshold",
                             metric="serve_queue_depth", threshold=8)])
        assert eng.evaluate_once() == []
        spike = obs_metrics.get().gauge("serve_queue_depth", "",
                                        engine="drill")
        spike.set(99)
        assert eng.evaluate_once() == [("queue_depth", "firing", 99.0)]
        spike.set(0)
        assert eng.evaluate_once() == [("queue_depth", "resolved", 0.0)]

        # the alerts: line renders live from the merged snapshot
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "serve_top", os.path.join(os.path.dirname(__file__), "..",
                                      "tools", "serve_top.py"))
        st = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(st)
        assert st.alerts_line(pool.merged_registry()) == "alerts: none"
        spike.set(99)
        eng.evaluate_once()
        line = st.alerts_line(pool.merged_registry())
        assert line == "alerts: FIRING queue_depth"

        # obs_report renders the alert timeline from the event stream
        spec = importlib.util.spec_from_file_location(
            "obs_report", os.path.join(os.path.dirname(__file__), "..",
                                       "tools", "obs_report.py"))
        rep = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(rep)
        events_, bad, bundles = rep.load_run(run_dir)
        assert not bad
        md = rep.render(events_, bad, bundles)
        assert "## Alert timeline" in md
        assert "queue_depth" in md
        assert "## Performance ledger" in md

    def test_local_pool_drill_with_jit_trap(self, obs_run_dir,
                                            monkeypatch):
        """2 local replicas: warm the pool, then prove the WHOLE drill
        — submits, ledger lookups, alert evaluation, merges — creates
        zero new jit programs (the no-new-cold-compiles audit)."""
        from bigdl_tpu.serve import ReplicaPool
        model = _mlp()
        with ReplicaPool(model, n_replicas=2, max_batch=8,
                         max_wait_ms=5, shed=False) as pool:
            # first submit warms engines through xcache (compiles ok)
            pool.submit(np.random.RandomState(1)
                        .rand(6).astype(np.float32)).result(timeout=60)
            compiles0 = xcache.get().stats()["compiles"]
            real_jit = jax.jit
            trapped = []

            def trapping_jit(fn, *a, **kw):
                trapped.append(fn)
                return real_jit(fn, *a, **kw)

            monkeypatch.setattr(jax, "jit", trapping_jit)
            self._drill(pool, obs_run_dir)
            monkeypatch.setattr(jax, "jit", real_jit)
            assert trapped == [], "serve drill must not build new jit " \
                                  "programs"
            assert xcache.get().stats()["compiles"] == compiles0

    @pytest.mark.slow
    def test_one_local_one_subprocess_drill(self, obs_run_dir):
        """ISSUE 13 acceptance: 1 local + 1 subprocess replica — the
        child's ledger gauges ride its registry snapshot into
        merged_registry(), and the alert/report/serve_top surfaces all
        render from the fleet truth."""
        from bigdl_tpu.serve import (LocalReplica, ProcessReplica,
                                     ReplicaPool, ServeEngine)
        model = _mlp()
        replicas = [
            LocalReplica(ServeEngine(model, name="local0", max_batch=8,
                                     max_wait_ms=5), name="local0"),
            ProcessReplica(model, name="proc0", max_batch=8,
                           max_wait_ms=5),
        ]
        with ReplicaPool(replicas=replicas, shed=False) as pool:
            # warm the SUBPROCESS side explicitly (least-loaded serial
            # traffic would otherwise stay on the local replica), so
            # the child compiles and its ledger entries exist
            replicas[1].submit(np.random.RandomState(2)
                               .rand(6).astype(np.float32)).result(
                                   timeout=120)
            self._drill(pool, obs_run_dir)
            # per-replica cost truth: the child's ledger gauges ride
            # its registry snapshot into the merge
            child = replicas[1].registry_snapshot()
            assert obs_metrics.family_total(child, "ledger_flops") > 0
            merged = pool.merged_registry()
            # both sides compiled through their own xcache: the child's
            # compile counter is visible next to the parent's
            assert obs_metrics.family_total(
                merged, "xcache_compiles_total") > \
                obs_metrics.family_total(
                    obs_metrics.get().snapshot(),
                    "xcache_compiles_total")


# ---------------------------------------------------------------------------
# EventLog rotation (BIGDL_OBS_MAX_MB)
# ---------------------------------------------------------------------------

class TestEventLogRotation:
    def test_rotates_with_keep_last_semantics(self, tmp_path):
        log = obs_events.EventLog(run_dir=str(tmp_path),
                                  max_mb=2e-4, keep=2)   # ~200 bytes
        try:
            for i in range(200):
                log.emit("phase", name="x", seconds=0.1, i=i)
            assert log.rotations >= 3
            assert os.path.getsize(log.path) <= 400
            assert os.path.exists(log.path + ".1")
            assert os.path.exists(log.path + ".2")
            assert not os.path.exists(log.path + ".3")   # keep-last 2
            # the newest events live in the current file + ring
            tail = obs_events.read_events(log.path) or \
                obs_events.read_events(log.path + ".1")
            assert tail[-1]["i"] == 199
            assert log.ring_events()[-1]["i"] == 199
        finally:
            log.close()

    def test_ring_unaffected_by_rotation(self, tmp_path):
        log = obs_events.EventLog(run_dir=str(tmp_path), ring=64,
                                  max_mb=2e-4, keep=1)
        try:
            for i in range(100):
                log.emit("phase", name="x", seconds=0.1, i=i)
            ring = log.ring_events()
            assert len(ring) == 64 and ring[-1]["i"] == 99
        finally:
            log.close()

    def test_unlimited_by_default(self, tmp_path, monkeypatch):
        monkeypatch.delenv(obs_events.ENV_MAX_MB, raising=False)
        log = obs_events.EventLog(run_dir=str(tmp_path))
        try:
            assert log._max_bytes == 0
            for i in range(50):
                log.emit("phase", name="x", seconds=0.1)
            assert log.rotations == 0
        finally:
            log.close()

    def test_obs_report_reads_rotated_segments(self, tmp_path):
        """Rotation must not blind the postmortem tool: events that
        landed in rotated segments (run_start, early ledger captures)
        still render in the report."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "obs_report_rot", os.path.join(os.path.dirname(__file__),
                                           "..", "tools",
                                           "obs_report.py"))
        rep = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(rep)
        log = obs_events.EventLog(run_dir=str(tmp_path), max_mb=1e-3,
                                  keep=16)   # ~1 KiB cap, keep all
        try:
            log.emit("run_start", flags={"drill": 1})
            for i in range(60):
                log.emit("phase", name="x", seconds=0.1, i=i)
            log.emit("run_end", steps=60, wall=1.0)
            assert log.rotations >= 1
        finally:
            log.close()
        events_, bad, _ = rep.load_run(str(tmp_path))
        assert not bad
        assert [e["type"] for e in events_].count("phase") == 60
        md = rep.render(events_, bad, [])
        assert "run_start" in md and "run_end" in md

    def test_env_configures_cap(self, tmp_path, monkeypatch):
        monkeypatch.setenv(obs_events.ENV_MAX_MB, "1.5")
        monkeypatch.setenv(obs_events.ENV_KEEP, "5")
        log = obs_events.EventLog(run_dir=str(tmp_path))
        try:
            assert log._max_bytes == int(1.5 * (1 << 20))
            assert log._keep == 5
        finally:
            log.close()


# ---------------------------------------------------------------------------
# schema v3: ledger/alert kinds
# ---------------------------------------------------------------------------

class TestSchemaV3:
    def _ev(self, etype, **fields):
        e = {"v": obs_events.SCHEMA_VERSION, "ts": 0.0, "proc": 0,
             "type": etype}
        e.update(fields)
        return e

    @pytest.mark.parametrize("kind,required", [
        ("exec", {"fn": "f", "flops": 1.0, "bytes_accessed": 2.0}),
        ("tenant", {"tenant": "kv_pool", "bytes": 8}),
        ("hbm", {"in_use": 100}),
    ])
    def test_ledger_kinds_roundtrip(self, kind, required):
        e = self._ev("ledger", kind=kind, **required)
        assert validate_event(json.loads(json.dumps(e))) == e
        for missing in required:
            bad = {k: v for k, v in e.items() if k != missing}
            with pytest.raises(ValueError, match=missing):
                validate_event(bad)

    @pytest.mark.parametrize("kind", ["firing", "resolved"])
    def test_alert_kinds_roundtrip(self, kind):
        e = self._ev("alert", kind=kind, rule="r", value=1.0,
                     threshold=2.0)
        assert validate_event(json.loads(json.dumps(e))) == e
        with pytest.raises(ValueError, match="value"):
            validate_event(self._ev("alert", kind=kind, rule="r",
                                    threshold=2.0))

    def test_unknown_kinds_rejected(self):
        with pytest.raises(ValueError, match="unknown ledger kind"):
            validate_event(self._ev("ledger", kind="bogus"))
        with pytest.raises(ValueError, match="unknown alert kind"):
            validate_event(self._ev("alert", kind="bogus", rule="r"))

    def test_alert_requires_rule(self):
        with pytest.raises(ValueError, match="rule"):
            validate_event(self._ev("alert", kind="firing", value=1.0,
                                    threshold=2.0))

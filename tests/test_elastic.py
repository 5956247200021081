"""Elastic training + async sharded checkpointing (ISSUE 8,
docs/resilience.md "Elastic training").

Fast, in-process coverage of every recovery building block:

- watchdog policy hook: ``on_peer_death="recover"`` hands the trip to
  the elastic layer and keeps beating; the ``"exit"`` default keeps the
  historical fail-fast contract (exit-43 back-compat)
- the reform protocol's file handshake (join/plan/quorum/abort) — pure
  files + callbacks, no jax.distributed needed
- the host AnchorKeeper (background snapshot-to-host) and guarded_sync
  (abandonable host syncs)
- the ``recover`` obs event schema and the obs_report recovery timeline
- async sharded checkpointing: shard split/assemble round trip, the
  background writer, keep-last-N retention with a corrupt-newest layout,
  and the corrupt-shard fallback in ``load_latest_checkpoint``
- world-size-agnostic zero1 restore: save under dp=4, restore under
  dp=2 and dp=1, post-restore trajectory matches a never-killed oracle
- dataset world re-keying: ``ShardedDataSet.reshard`` and
  ``SampleToBatch(global_batch_size=...)``

The 4-process kill→recover→converge drill lives in
``tests/test_multiprocess.py`` (slow + chaos + elastic);
``scripts/chaos_drill.sh`` runs the full matrix.
"""
import json
import os
import time

import numpy as np
import pytest
import jax

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset import DataSet, Sample
from bigdl_tpu.dataset.dataset import ShardedDataSet
from bigdl_tpu.dataset.transformer import SampleToBatch
from bigdl_tpu.obs.events import validate_event
from bigdl_tpu.optim import (DistriOptimizer, load_latest_checkpoint,
                             max_iteration, several_iteration)
from bigdl_tpu.optim.optimizer import (list_checkpoints, prune_checkpoints,
                                       snapshot_files, snapshot_valid)
from bigdl_tpu.parallel.mesh import make_mesh
from bigdl_tpu.resilience import Watchdog, elastic
from bigdl_tpu.resilience import checkpoint as ckpt_mod
from bigdl_tpu.resilience.checkpoint import (AsyncCheckpointWriter,
                                             ShardRef,
                                             assemble_sharded_state,
                                             shard_file,
                                             split_sharded_state)
from bigdl_tpu.utils import file as File
from bigdl_tpu.utils.random import set_seed
from bigdl_tpu.utils.table import T

pytestmark = pytest.mark.elastic


@pytest.fixture(autouse=True)
def _clean_elastic():
    elastic.reset()
    yield
    elastic.reset()


def _data(n=16, d=6, classes=3, seed=0):
    rng = np.random.RandomState(seed)
    w = rng.randn(d, classes) * 2
    xs = rng.randn(n, d).astype(np.float32)
    ys = (xs @ w).argmax(1) + 1.0
    return [Sample(x, np.asarray([y])) for x, y in zip(xs, ys)]


def _model(d=6, classes=3):
    return nn.Sequential(nn.Linear(d, 8), nn.Tanh(),
                         nn.Linear(8, classes), nn.LogSoftMax())


def _params_vec(model):
    return np.concatenate([np.asarray(p).ravel()
                           for p in jax.tree_util.tree_leaves(
                               model.params())])


# ---------------------------------------------------------------------------
# Watchdog policy hook
# ---------------------------------------------------------------------------

class TestWatchdogPolicy:
    def test_invalid_policy_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="on_peer_death"):
            Watchdog(str(tmp_path), 0, 2, on_peer_death="retry")

    def test_default_policy_is_exit(self, tmp_path):
        dog = Watchdog(str(tmp_path), 0, 2)
        assert dog.on_peer_death == "exit"
        assert dog.on_stale == dog._default_on_stale

    def test_explicit_on_stale_overrides_policy(self, tmp_path):
        def custom(stale):
            pass

        dog = Watchdog(str(tmp_path), 0, 2, on_stale=custom,
                       on_peer_death="recover")
        assert dog.on_stale is custom

    def test_recover_policy_defers_and_keeps_beating(self, tmp_path):
        dog = Watchdog(str(tmp_path), process_index=0, n_processes=2,
                       interval=0.05, timeout=0.2,
                       on_peer_death="recover")
        # the heartbeat dir doubles as the reform dir
        assert elastic.runtime().reform_dir == str(tmp_path)
        assert elastic.runtime().watchdog is dog
        dog.start()
        try:
            deadline = time.time() + 5.0
            while elastic.tripped() is None and time.time() < deadline:
                time.sleep(0.02)
            # peer 1 never beat: trip recorded, process still alive
            assert elastic.tripped() == frozenset([1])
            assert elastic.trip_age() is not None
            # this process's OWN heartbeat keeps advancing (survivors'
            # monitors must not read a recovering peer as dead)
            hb = os.path.join(str(tmp_path), "hb.0")
            m0 = os.path.getmtime(hb)
            time.sleep(0.15)
            assert os.path.getmtime(hb) > m0
        finally:
            dog.stop()

    def test_rebind_narrows_the_monitored_peers(self, tmp_path):
        dog = Watchdog(str(tmp_path), process_index=0, n_processes=3,
                       interval=0.05, timeout=0.1)
        for i in range(3):
            open(os.path.join(str(tmp_path), f"hb.{i}"), "w").close()
        time.sleep(0.25)
        assert sorted(dog.stale_peers()) == [1, 2]
        dog.rebind(peers=[0, 1])
        assert sorted(dog.stale_peers()) == [1]

    def test_check_raises_recovery_signal(self):
        elastic.note_trip([2])
        with pytest.raises(elastic.PeerLossRecovery) as ei:
            elastic.check()
        assert ei.value.stale == frozenset([2])
        elastic.clear_trip()
        elastic.check()   # no trip pending: no raise


# ---------------------------------------------------------------------------
# Reform protocol (files + callbacks; no jax.distributed)
# ---------------------------------------------------------------------------

class TestReformProtocol:
    def _join(self, d, gen, orig):
        open(os.path.join(str(d), f"rf.{gen}.join.{orig}"), "w").close()

    def test_plan_round_trip(self, tmp_path):
        d = str(tmp_path)
        for o in (0, 2, 3):
            self._join(d, 1, o)
        plan = elastic.publish_plan(d, 1, stale=[1], orig_index=0,
                                    n_orig=4, settle=0.1, timeout=5.0)
        assert plan["survivors"] == [0, 2, 3]
        assert plan["gen"] == 1
        host, port = plan["addr"].rsplit(":", 1)
        assert int(port) > 0
        # non-coordinators read the identical plan back
        assert elastic.await_plan(d, 1, timeout=2.0) == plan

    def test_plan_waits_for_joiners_to_settle(self, tmp_path):
        import threading
        d = str(tmp_path)
        self._join(d, 1, 0)

        def late_join():
            time.sleep(0.2)
            self._join(d, 1, 1)

        t = threading.Thread(target=late_join)
        t.start()
        plan = elastic.publish_plan(d, 1, stale=[2], orig_index=0,
                                    n_orig=3, settle=0.6, timeout=10.0)
        t.join()
        assert plan["survivors"] == [0, 1]

    def test_quorum_floor_aborts(self, tmp_path):
        d = str(tmp_path)
        self._join(d, 1, 0)
        with pytest.raises(elastic.ReformAbort, match="quorum"):
            elastic.publish_plan(d, 1, stale=[1, 2, 3], orig_index=0,
                                 n_orig=4, settle=0.1, timeout=5.0,
                                 min_survivors=2)

    def test_live_probe_excludes_freshly_dead(self, tmp_path):
        d = str(tmp_path)
        for o in (0, 1, 2):
            self._join(d, 1, o)
        # peer 2 joined, then went silent before the plan was cut
        plan = elastic.publish_plan(d, 1, stale=[3], orig_index=0,
                                    n_orig=4, settle=0.1, timeout=5.0,
                                    live_probe=lambda: [2])
        assert plan["survivors"] == [0, 1]

    def test_await_plan_times_out(self, tmp_path):
        with pytest.raises(elastic.ReformAbort, match="no plan"):
            elastic.await_plan(str(tmp_path), 1, timeout=0.3)

    def test_reform_unarmed_aborts(self):
        with pytest.raises(elastic.ReformAbort, match="not armed"):
            elastic.reform([1])

    def test_coordinator_death_is_unrecoverable(self, tmp_path):
        rt = elastic.runtime()
        rt.armed = True
        rt.reform_dir = str(tmp_path)
        rt.orig_index, rt.n_orig = 1, 4
        with pytest.raises(elastic.ReformAbort, match="process 0"):
            elastic.reform([0])

    def test_finalize_is_noop_without_recovery(self):
        elastic.finalize(0)   # must return, not exit


# ---------------------------------------------------------------------------
# AnchorKeeper + guarded_sync
# ---------------------------------------------------------------------------

def _payload(neval=3, count=8):
    return {"state": T(neval=neval), "neval": neval, "epoch": 1,
            "count": count, "rng": {"seed": 1}}


class TestAnchorKeeper:
    def test_offer_then_latest(self):
        k = elastic.AnchorKeeper()
        trees = ({"w": np.ones((2, 2))}, {}, {"v": np.zeros(3)})
        k.offer(trees, _payload(neval=5))
        a = k.latest(grace=5.0)
        assert a.neval == 5 and a.count == 8
        np.testing.assert_array_equal(a.params["w"], np.ones((2, 2)))

    def test_latest_returns_newest_complete(self):
        k = elastic.AnchorKeeper()
        for ne in (1, 2, 3):
            k.offer(({"w": np.full(2, ne)}, {}, {}), _payload(neval=ne))
            k.latest(grace=5.0)   # let each land before the next offer
        a = k.latest(grace=5.0)
        assert a.neval == 3
        np.testing.assert_array_equal(a.params["w"], np.full(2, 3))

    def test_no_anchor_aborts(self):
        k = elastic.AnchorKeeper()
        with pytest.raises(elastic.ReformAbort, match="no complete"):
            k.latest(grace=0.1)

    def test_capture_sync_seeds_immediately(self):
        k = elastic.AnchorKeeper()
        k.capture_sync(({"w": np.ones(1)}, {}, {}), _payload(neval=9))
        assert k.latest(grace=0.0).neval == 9

    def test_device_trees_materialize_to_host(self):
        import jax.numpy as jnp
        k = elastic.AnchorKeeper()
        k.offer(({"w": jnp.arange(4.0)}, {}, {}), _payload())
        a = k.latest(grace=5.0)
        assert isinstance(a.params["w"], np.ndarray)


class TestGuardedSync:
    def test_passthrough_value_and_error(self):
        assert elastic.guarded_sync(lambda: 42) == 42
        with pytest.raises(KeyError):
            elastic.guarded_sync(lambda: {}["missing"])

    def test_pending_trip_raises_before_running(self):
        elastic.note_trip([1])
        ran = []
        with pytest.raises(elastic.PeerLossRecovery):
            elastic.guarded_sync(lambda: ran.append(1))
        assert not ran

    def test_trip_mid_sync_abandons_the_block(self):
        import threading

        release = threading.Event()

        def blocked():
            release.wait(timeout=30.0)
            return "late"

        def trip_soon():
            time.sleep(0.2)
            elastic.note_trip([2])

        t = threading.Thread(target=trip_soon)
        t.start()
        t0 = time.time()
        with pytest.raises(elastic.PeerLossRecovery):
            elastic.guarded_sync(blocked, poll=0.05)
        assert time.time() - t0 < 5.0
        t.join()
        release.set()


# ---------------------------------------------------------------------------
# recover obs events + report section
# ---------------------------------------------------------------------------

class TestRecoverEvents:
    def _env(self, **kw):
        e = {"v": 2, "ts": 0.0, "proc": 0, "type": "recover"}
        e.update(kw)
        return e

    def test_kinds_validate(self):
        validate_event(self._env(kind="trip", stale=[1]))
        validate_event(self._env(kind="quiesce", step=7))
        validate_event(self._env(kind="reform", world_before=4,
                                 world_after=3))
        validate_event(self._env(kind="reshard", world_after=3))
        validate_event(self._env(kind="resume", step=7, world_before=4,
                                 world_after=3, pause_s=1.25))
        validate_event(self._env(kind="abort", reason="below quorum"))

    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError, match="unknown recover kind"):
            validate_event(self._env(kind="reboot"))

    def test_missing_fields_rejected(self):
        with pytest.raises(ValueError, match="missing"):
            validate_event(self._env(kind="resume", step=7))

    def test_obs_report_renders_recovery_timeline(self, obs_run_dir):
        from bigdl_tpu.obs import events
        from tools.obs_report import load_run, render
        events.emit("recover", kind="trip", stale=[2])
        events.emit("recover", kind="quiesce", step=11, stale=[2])
        events.emit("recover", kind="reform", world_before=4,
                    world_after=3, generation=1)
        events.emit("recover", kind="reshard", world_after=3, step=11)
        events.emit("recover", kind="resume", step=11, world_before=4,
                    world_after=3, pause_s=2.5)
        evs, bad, bundles = load_run(obs_run_dir)
        assert not bad
        md = render(evs, bad, bundles)
        assert "Recovery timeline" in md
        assert "4 → 3" in md
        assert "2.50s" in md
        assert "resume" in md


# ---------------------------------------------------------------------------
# Async sharded checkpointing: split/assemble, writer, retention
# ---------------------------------------------------------------------------

class TestShardedState:
    def test_single_process_state_has_no_cross_process_shards(self):
        # everything addressable on one process: the classic whole-tree
        # path stays in charge (and split returns no slices)
        tree = {"v": jax.numpy.zeros((8, 3)), "step": jax.numpy.int32(4)}
        marked, slices = split_sharded_state(tree)
        assert slices == {}
        assert not any(isinstance(l, ShardRef)
                       for l in jax.tree_util.tree_leaves(marked))

    def test_assemble_round_trip(self):
        full = np.arange(24, dtype=np.float32).reshape(8, 3)
        marked = {"v": ShardRef("['v']", (8, 3), "float32"),
                  "step": np.int32(4)}
        blobs = [{"rank": 0, "world": 2,
                  "slices": {"['v']": [(((0, 4), (0, 3)), full[:4])]}},
                 {"rank": 1, "world": 2,
                  "slices": {"['v']": [(((4, 8), (0, 3)), full[4:])]}}]
        out = assemble_sharded_state(marked, blobs)
        np.testing.assert_array_equal(out["v"], full)
        assert out["step"] == 4

    def test_assemble_dedups_replicated_rows(self):
        # two processes covering the same rows (within-process replication)
        full = np.arange(8, dtype=np.float32).reshape(4, 2)
        spec = lambda r0, r1: ((r0, r1), (0, 2))
        marked = {"v": ShardRef("['v']", (4, 2), "float32")}
        blobs = [{"slices": {"['v']": [(spec(0, 2), full[:2]),
                                       (spec(2, 4), full[2:])]}},
                 {"slices": {"['v']": [(spec(2, 4), full[2:])]}}]
        np.testing.assert_array_equal(
            assemble_sharded_state(marked, blobs)["v"], full)

    def test_assemble_non_dim0_sharding(self):
        # zero1_tp_rule shards TP'd leaves over dim 1 (P(model, data)):
        # the spec round-trips ANY layout, not just row blocks
        full = np.arange(24, dtype=np.float32).reshape(4, 6)
        marked = {"w": ShardRef("['w']", (4, 6), "float32")}
        blobs = [{"slices": {"['w']": [(((0, 2), (0, 3)), full[:2, :3]),
                                       (((2, 4), (0, 3)), full[2:, :3])]}},
                 {"slices": {"['w']": [(((0, 2), (3, 6)), full[:2, 3:]),
                                       (((2, 4), (3, 6)), full[2:, 3:])]}}]
        np.testing.assert_array_equal(
            assemble_sharded_state(marked, blobs)["w"], full)

    def test_missing_rows_fail_loudly(self):
        marked = {"v": ShardRef("['v']", (8, 3), "float32")}
        blobs = [{"slices": {"['v']": [(((0, 4), (0, 3)),
                                        np.zeros((4, 3), np.float32))]}}]
        with pytest.raises(ValueError, match="cover only"):
            assemble_sharded_state(marked, blobs)
        with pytest.raises(ValueError, match="missing"):
            assemble_sharded_state(marked, [{"slices": {}}])

    def test_shardref_survives_file_save(self, tmp_path):
        # File.save's numpy duck test must not flatten the placeholder
        p = str(tmp_path / "state.1")
        File.save({"opt_state": {"v": ShardRef("['v']", (4,), "float32")},
                   "opt_shards": 2}, p)
        back = File.load(p)
        ref = back["opt_state"]["v"]
        assert isinstance(ref, ShardRef)
        assert ref.shape == (4,) and ref.path == "['v']"


class TestAsyncWriter:
    def test_writes_files_with_sidecars(self, tmp_path):
        w = AsyncCheckpointWriter()
        files = [(str(tmp_path / "state.2"), {"neval": 2}),
                 (str(tmp_path / "state.2.shard0of1"), {"slices": {}})]
        w.submit(files)
        assert w.flush(timeout=30.0)
        assert w.written == 1 and w.failed == 0
        for p, _ in files:
            assert os.path.exists(p) and os.path.exists(p + ".crc32")
            assert File.verify(p)
        assert File.load(str(tmp_path / "state.2"))["neval"] == 2

    def test_failure_is_contained(self, tmp_path):
        w = AsyncCheckpointWriter()
        # unpicklable blob: the write fails, the writer survives
        w.submit([(str(tmp_path / "state.0"), {"fn": lambda: None})])
        w.submit([(str(tmp_path / "state.1"), {"ok": 1})])
        assert w.flush(timeout=30.0)
        assert w.failed == 1 and w.written == 1
        assert File.load(str(tmp_path / "state.1"))["ok"] == 1

    def test_emits_checkpoint_event_and_prunes(self, tmp_path,
                                               obs_run_dir):
        from bigdl_tpu.obs import events
        d = tmp_path / "ckpt"
        d.mkdir()
        for n in (1, 2):
            File.save({"n": n}, str(d / f"model.{n}"))
            File.save({"n": n}, str(d / f"state.{n}"))
        w = AsyncCheckpointWriter()
        w.submit([(str(d / "model.3"), {"n": 3}),
                  (str(d / "state.3"), {"n": 3})],
                 meta={"event_path": str(d / "model.3"), "step": 3,
                       "shards": 0, "keep": 1, "ckpt_dir": str(d)})
        assert w.flush(timeout=30.0)
        assert events.get() is not None
        assert list_checkpoints(str(d)) == [3]
        with open(os.path.join(obs_run_dir,
                               "events.p0.jsonl")) as fh:
            evs = [json.loads(l) for l in fh if l.strip()]
        ck = [e for e in evs if e["type"] == "checkpoint"
              and e.get("mode") == "async"]
        assert ck and ck[0]["step"] == 3


class TestRetention:
    def _snap(self, d, n, shards=0):
        File.save({"n": n}, str(d / f"model.{n}"))
        File.save({"n": n}, str(d / f"state.{n}"))
        for r in range(shards):
            File.save({"r": r}, shard_file(str(d), n, r, shards))

    def test_keep_last_n(self, tmp_path):
        for n in (2, 4, 6):
            self._snap(tmp_path, n)
        prune_checkpoints(str(tmp_path), keep=2)
        assert list_checkpoints(str(tmp_path)) == [6, 4]
        assert not os.path.exists(str(tmp_path / "model.2.crc32"))

    def test_zero_keep_is_unlimited(self, tmp_path):
        for n in (1, 2, 3):
            self._snap(tmp_path, n)
        assert prune_checkpoints(str(tmp_path), keep=0) == []
        assert list_checkpoints(str(tmp_path)) == [3, 2, 1]

    def test_never_deletes_newest_valid_with_corrupt_newest(self,
                                                            tmp_path):
        for n in (2, 4, 6):
            self._snap(tmp_path, n, shards=2)
        # corrupt the NEWEST snapshot's payload (sidecar now disagrees)
        p = str(tmp_path / "state.6")
        with open(p, "r+b") as fh:
            fh.seek(0)
            fh.write(b"\xde\xad\xbe\xef")
        assert not snapshot_valid(str(tmp_path), 6)
        assert snapshot_valid(str(tmp_path), 4)
        prune_checkpoints(str(tmp_path), keep=1)
        # 6 is in the keep window (corrupt, but retention is not repair);
        # 4 is the newest CRC-valid snapshot and MUST survive the prune
        labels = list_checkpoints(str(tmp_path))
        assert 4 in labels and 6 in labels and 2 not in labels
        # the resume scan lands on 4, skipping the corrupt 6
        got = load_latest_checkpoint(str(tmp_path))
        assert got is None   # these stubs are not real module blobs

    def test_shard_files_ride_their_snapshot(self, tmp_path):
        self._snap(tmp_path, 1, shards=2)
        self._snap(tmp_path, 2, shards=2)
        files = snapshot_files(str(tmp_path), 1)
        assert f"state.1.shard0of2" in files
        prune_checkpoints(str(tmp_path), keep=1)
        left = sorted(os.listdir(str(tmp_path)))
        assert not any(f.startswith(("model.1", "state.1")) for f in left)
        assert any(f.startswith("state.2.shard") for f in left)


class TestShardedResumeScan:
    def _write_sharded_snapshot(self, d, neval, nshards, value):
        model = _model()
        File.save_module(model, str(d / f"model.{neval}"))
        full = np.full((8, 3), value, np.float32)
        rows = 8 // nshards
        for r in range(nshards):
            spec = ((r * rows, (r + 1) * rows), (0, 3))
            File.save({"rank": r, "world": nshards,
                       "slices": {"['v']": [
                           (spec, full[r * rows:(r + 1) * rows])]}},
                      shard_file(str(d), neval, r, nshards))
        File.save({"state": T(neval=neval), "neval": neval,
                   "opt_state": {"v": ShardRef("['v']", (8, 3),
                                               "float32")},
                   "opt_shards": nshards, "rng": None},
                  str(d / f"state.{neval}"))

    def test_reassembles_full_tree(self, tmp_path):
        self._write_sharded_snapshot(tmp_path, 3, 4, 7.0)
        module, blob, neval = load_latest_checkpoint(str(tmp_path))
        assert neval == 3
        v = blob["opt_state"]["v"]
        assert not isinstance(v, ShardRef)
        np.testing.assert_array_equal(np.asarray(v),
                                      np.full((8, 3), 7.0))

    def test_corrupt_shard_falls_back_to_older_pair(self, tmp_path):
        self._write_sharded_snapshot(tmp_path, 2, 2, 1.0)
        self._write_sharded_snapshot(tmp_path, 5, 2, 9.0)
        p = shard_file(str(tmp_path), 5, 1, 2)
        with open(p, "r+b") as fh:
            fh.write(b"\x00\x00\x00\x00")
        module, blob, neval = load_latest_checkpoint(str(tmp_path))
        assert neval == 2
        np.testing.assert_array_equal(np.asarray(blob["opt_state"]["v"]),
                                      np.full((8, 3), 1.0))

    def test_missing_shard_falls_back(self, tmp_path):
        self._write_sharded_snapshot(tmp_path, 2, 2, 1.0)
        self._write_sharded_snapshot(tmp_path, 5, 2, 9.0)
        os.remove(shard_file(str(tmp_path), 5, 0, 2))
        os.remove(shard_file(str(tmp_path), 5, 0, 2) + ".crc32")
        module, blob, neval = load_latest_checkpoint(str(tmp_path))
        assert neval == 2


# ---------------------------------------------------------------------------
# World-size-agnostic zero1 restore (dp=4 save -> dp=2 / dp=1 restore)
# ---------------------------------------------------------------------------

def _zero1_run(dp, iters, ckpt=None, ckpt_every=None, resume=None,
               compression=None, seed=7):
    """Full-batch zero1 training on a ``dp``-device mesh; momentum makes
    the optimizer state matter.  ``resume=(module, blob)`` continues a
    checkpointed run (neval rides the state)."""
    samples = _data()
    set_seed(seed)
    if resume is None:
        model = _model()
    else:
        model = resume[0]
    ds = DataSet.array(samples) >> SampleToBatch(len(samples))
    mesh = make_mesh({"data": dp}, jax.devices()[:dp])
    opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), mesh=mesh,
                          zero1=True, gradient_compression=compression)
    st = T(learningRate=0.2, momentum=0.9)
    if resume is not None:
        st.update(resume[1]["state"])
    opt.set_state(st)
    if resume is not None and resume[1].get("opt_state") is not None:
        opt.set_optim_state(resume[1]["opt_state"])
    opt.set_end_when(max_iteration(iters))
    if ckpt:
        opt.set_checkpoint(str(ckpt), several_iteration(ckpt_every))
    opt.optimize()
    return opt, model


@pytest.mark.serial
class TestWorldSizeAgnosticRestore:
    @pytest.mark.parametrize("dp_restore", [2, 1])
    def test_zero1_dp4_checkpoint_restores_at_smaller_dp(self, tmp_path,
                                                         dp_restore):
        # oracle: 6 uninterrupted steps at dp=4
        _, oracle = _zero1_run(4, 6)
        ref = _params_vec(oracle)
        # killed run: checkpoint at step 3, then restore at dp_restore
        _zero1_run(4, 3, ckpt=tmp_path, ckpt_every=3)
        got = load_latest_checkpoint(str(tmp_path), restore_rng=True)
        assert got is not None
        module, blob, neval = got
        assert neval == 3
        # the snapshot's optimizer state is the FULL logical tree
        for leaf in jax.tree_util.tree_leaves(blob["opt_state"]):
            assert not isinstance(leaf, ShardRef)
        opt2, m2 = _zero1_run(dp_restore, 6, resume=(module, blob))
        # post-restore trajectory matches the never-killed oracle: the
        # restored state re-partitioned over the smaller mesh is the
        # same math (mesh layout is data placement, not semantics)
        np.testing.assert_allclose(_params_vec(m2), ref,
                                   rtol=1e-4, atol=1e-5)
        assert int(opt2.state["neval"]) == 7

    def test_z1c_flat_state_restores_at_smaller_dp(self, tmp_path):
        # the compressed-ZeRO-1 flat mirrors carry dp=4 padding; restore
        # at dp=2 must trim + re-pad (bf16 wire: loose tolerance)
        _, oracle = _zero1_run(4, 6, compression="bf16")
        ref_loss = None
        _zero1_run(4, 3, ckpt=tmp_path, ckpt_every=3,
                   compression="bf16")
        module, blob, neval = load_latest_checkpoint(str(tmp_path),
                                                     restore_rng=True)
        opt2, m2 = _zero1_run(2, 6, resume=(module, blob),
                              compression="bf16")
        final = _params_vec(m2)
        assert np.all(np.isfinite(final))
        np.testing.assert_allclose(final, _params_vec(oracle),
                                   rtol=0.05, atol=0.05)


# ---------------------------------------------------------------------------
# Dataset world re-keying
# ---------------------------------------------------------------------------

class TestDatasetReshard:
    def test_sharded_dataset_reshard_repartitions(self, monkeypatch):
        monkeypatch.setenv("BIGDL_ELASTIC", "1")
        data = list(range(12))
        ds = ShardedDataSet(data, n_shards=4, shard_index=1)
        assert ds._shard == data[1::4]
        assert ds.size() == 12
        ds.reshard(n_shards=3, shard_index=2)
        assert ds._shard == data[2::3]
        assert ds.size() == 12

    def test_reshard_covers_every_record_exactly_once(self, monkeypatch):
        monkeypatch.setenv("BIGDL_ELASTIC", "1")
        data = list(range(10))
        shards = [ShardedDataSet(data, n_shards=4, shard_index=i)
                  .reshard(n_shards=3, shard_index=i)._shard
                  for i in range(3)]
        flat = sorted(x for s in shards for x in s)
        assert flat == data

    def test_fail_fast_runs_do_not_retain_other_shards(self, monkeypatch):
        # the N-times resident-memory cost is paid only under the flag
        monkeypatch.delenv("BIGDL_ELASTIC", raising=False)
        ds = ShardedDataSet(list(range(12)), n_shards=4, shard_index=1)
        assert ds._data is None
        assert ds._shard == list(range(12))[1::4]
        with pytest.raises(RuntimeError, match="BIGDL_ELASTIC"):
            ds.reshard(n_shards=3, shard_index=1)

    def test_global_batch_with_lent_slots(self):
        # the slots a prefetching runner lends must size themselves from
        # the RESOLVED local batch (batch_size is None in global mode),
        # and anew when a re-form changes it
        import unittest.mock as mock
        from bigdl_tpu.dataset import prefetch as pf
        samples = _data(n=16)
        tb = SampleToBatch(global_batch_size=8)
        ds = DataSet.array(samples) >> tb

        def local_batches(n):
            runner = pf.PipelineRunner(
                ds, train=True, to_device=lambda x, y: (x.copy(), y.copy()))
            try:
                return [runner.get()[0].device[0].shape[0]
                        for _ in range(n)]
            finally:
                runner.close()

        assert local_batches(4) == [8, 8, 8, 8]
        (slots,) = tb._shelf
        assert {s.x.shape[0] for s in slots if s.x is not None} == {8}
        with mock.patch.object(jax, "process_count", return_value=2):
            assert local_batches(6) == [4] * 6
        (slots,) = tb._shelf
        assert {s.x.shape[0] for s in slots} == {4}

    def test_sample_to_batch_needs_exactly_one_size(self):
        with pytest.raises(ValueError, match="exactly one"):
            SampleToBatch()
        with pytest.raises(ValueError, match="exactly one"):
            SampleToBatch(4, global_batch_size=8)

    def test_global_batch_size_resolves_against_live_world(self):
        samples = _data(n=16)
        tb = SampleToBatch(global_batch_size=8)
        # single test process: local == global
        batches = list(tb(iter(samples)))
        assert [b.data.shape[0] for b in batches] == [8, 8]

    def test_global_batch_divisibility_enforced(self):
        tb = SampleToBatch(global_batch_size=7)
        import unittest.mock as mock
        with mock.patch.object(jax, "process_count", return_value=2):
            with pytest.raises(ValueError, match="divided"):
                list(tb(iter(_data(n=14))))


# ---------------------------------------------------------------------------
# Elastic session arming on the optimizer
# ---------------------------------------------------------------------------

class TestElasticArming:
    def test_single_process_run_ignores_the_flag(self, monkeypatch):
        monkeypatch.setenv("BIGDL_ELASTIC", "1")
        opt, model = _zero1_run(2, 2)
        # trained fine, no session armed (process_count == 1)
        assert opt._elastic is None
        assert np.isfinite(opt.state["loss"])

    def test_env_knob_parsing(self, monkeypatch):
        monkeypatch.delenv("BIGDL_ELASTIC", raising=False)
        assert not elastic.enabled()
        monkeypatch.setenv("BIGDL_ELASTIC", "1")
        assert elastic.enabled()
        monkeypatch.setenv("BIGDL_ELASTIC_QUORUM", "3")
        assert elastic.quorum() == 3
        monkeypatch.setenv("BIGDL_ELASTIC_QUORUM", "bogus")
        assert elastic.quorum() == 2

    def test_ckpt_knob_parsing(self, monkeypatch):
        monkeypatch.delenv("BIGDL_CKPT_ASYNC", raising=False)
        assert not ckpt_mod.async_enabled()
        monkeypatch.setenv("BIGDL_CKPT_ASYNC", "1")
        assert ckpt_mod.async_enabled()
        monkeypatch.setenv("BIGDL_CKPT_KEEP", "5")
        assert ckpt_mod.keep_count() == 5
        monkeypatch.setenv("BIGDL_CKPT_KEEP", "junk")
        assert ckpt_mod.keep_count() == 0

    def test_async_checkpoint_single_process_end_to_end(self, tmp_path,
                                                        monkeypatch):
        monkeypatch.setenv("BIGDL_CKPT_ASYNC", "1")
        monkeypatch.setenv("BIGDL_CKPT_KEEP", "1")
        opt, model = _zero1_run(2, 6, ckpt=tmp_path, ckpt_every=2)
        # writer flushed at run end: every snapshot durable, pruned to 1
        labels = list_checkpoints(str(tmp_path))
        assert labels == [6]
        assert snapshot_valid(str(tmp_path), 6)
        got = load_latest_checkpoint(str(tmp_path))
        assert got is not None and got[2] == 6


# ---------------------------------------------------------------------------
# Async checkpoint acceptance: off-critical-path + kill-during-write
# ---------------------------------------------------------------------------

class TestAsyncOffCriticalPath:
    def test_checkpoint_step_cost_is_copy_plus_enqueue(self, tmp_path,
                                                       monkeypatch):
        """The acceptance claim: with the async writer, a checkpoint-
        cadence step pays a device copy + enqueue, not the write.  With
        File.save slowed to 0.25s/file, the sync path blocks the loop
        >= 0.5s (model + state) while the async path returns in a small
        fraction of that."""
        import bigdl_tpu.utils.file as file_mod
        opt, model = _zero1_run(2, 1)
        opt.checkpoint_path = str(tmp_path)
        params = model.params()
        net_state = model.state()
        opt_state = opt.optim_method.init_state(params)
        state = T(neval=5, epoch=1)

        real_save = file_mod.save

        def slow_save(obj, path, **kw):
            time.sleep(0.25)
            return real_save(obj, path, **kw)

        monkeypatch.setattr(file_mod, "save", slow_save)

        monkeypatch.setenv("BIGDL_CKPT_ASYNC", "0")
        t0 = time.perf_counter()
        opt._emit_checkpoint(params, net_state, opt_state, state, 5,
                             asynchronous=False)
        sync_wall = time.perf_counter() - t0
        assert sync_wall >= 0.5

        monkeypatch.setenv("BIGDL_CKPT_ASYNC", "1")
        t0 = time.perf_counter()
        opt._emit_checkpoint(params, net_state, opt_state, state, 6,
                             asynchronous=True)
        async_wall = time.perf_counter() - t0
        assert async_wall < 0.2, \
            f"async checkpoint blocked the loop {async_wall:.3f}s"
        assert opt._ckpt_writer.flush(timeout=30.0)
        monkeypatch.setattr(file_mod, "save", real_save)
        assert snapshot_valid(str(tmp_path), 5)
        assert snapshot_valid(str(tmp_path), 6)


class TestKillDuringAsyncWrite:
    def test_previous_checkpoint_survives_a_mid_write_kill(self,
                                                           tmp_path):
        """A process killed while the background writer is mid-snapshot
        must leave the PREVIOUS checkpoint loadable: the half-written
        snapshot is an unpaired/invalid set the resume scan skips."""
        import subprocess
        import sys as _sys
        import textwrap

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = textwrap.dedent("""
            import os, sys, time
            sys.path.insert(0, %r)
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
            import jax
            jax.config.update("jax_platforms", "cpu")
            import bigdl_tpu.utils.file as file_mod
            from bigdl_tpu.resilience.checkpoint import (
                AsyncCheckpointWriter)
            d = %r
            # snapshot 3: complete and durable
            file_mod.save({"ok": 3}, os.path.join(d, "model.3"))
            file_mod.save({"ok": 3}, os.path.join(d, "state.3"))
            # snapshot 6 rides the async writer with a slowed save; the
            # process dies while state.6 is still in flight
            real = file_mod.save
            def slow(obj, path, **kw):
                real(obj, path, **kw)
                time.sleep(1.0)
            file_mod.save = slow
            w = AsyncCheckpointWriter()
            w.submit([(os.path.join(d, "model.6"), {"ok": 6}),
                      (os.path.join(d, "state.6"), {"ok": 6})])
            time.sleep(0.5)   # inside snapshot 6: model written, state not
            os._exit(9)       # the kill
        """) % (repo, str(tmp_path))
        p = subprocess.run([_sys.executable, "-c", script], timeout=120)
        assert p.returncode == 9
        files = sorted(os.listdir(str(tmp_path)))
        assert "model.6" in files and "state.6" not in files, files
        # the scan must fall back past the unpaired snapshot 6
        from bigdl_tpu.optim.optimizer import list_checkpoints
        assert list_checkpoints(str(tmp_path)) == [3]
        assert File.load(str(tmp_path / "state.3"))["ok"] == 3


# ---------------------------------------------------------------------------
# Review-round regressions: unconsumed-trip fallback, worker reuse,
# orphan-shard sweep
# ---------------------------------------------------------------------------

class TestUnconsumedTripFallback:
    def test_recover_policy_downgrades_when_nobody_consumes(self,
                                                            tmp_path):
        """recover policy with no armed elastic consumer must NOT turn
        peer death into an unbounded fleet hang: after the fallback
        window the watchdog delivers the fail-fast contract."""
        dog = Watchdog(str(tmp_path), process_index=0, n_processes=2,
                       interval=0.05, timeout=0.2,
                       on_peer_death="recover")
        dog.trip_fallback = 0.6
        fell_back = []
        dog._default_on_stale = lambda stale: fell_back.append(stale)
        dog.start()
        try:
            deadline = time.time() + 10.0
            while not fell_back and time.time() < deadline:
                time.sleep(0.05)
            assert fell_back and 1 in fell_back[0]
        finally:
            dog.stop()

    def test_consumed_trip_stands_the_fallback_down(self, tmp_path):
        dog = Watchdog(str(tmp_path), process_index=0, n_processes=2,
                       interval=0.05, timeout=0.2,
                       on_peer_death="recover")
        dog.trip_fallback = 1.5
        fell_back = []
        dog._default_on_stale = lambda stale: fell_back.append(stale)
        dog.start()
        try:
            deadline = time.time() + 10.0
            while elastic.tripped() is None and time.time() < deadline:
                time.sleep(0.02)
            # a recovery owner claims the trip (what raising
            # PeerLossRecovery does in the training loop)
            elastic.PeerLossRecovery(elastic.tripped())
            assert elastic.runtime().recovering
            time.sleep(2.0)
            assert not fell_back
        finally:
            dog.stop()


class TestGuardedWorkerReuse:
    def test_healthy_calls_reuse_one_thread(self):
        assert elastic.guarded_sync(lambda: 1) == 1
        w = elastic._SYNC_WORKER
        assert w is not None
        assert elastic.guarded_sync(lambda: 2) == 2
        assert elastic._SYNC_WORKER is w

    def test_abandoned_worker_is_replaced(self):
        import threading
        elastic.guarded_sync(lambda: 0)
        w = elastic._SYNC_WORKER
        release = threading.Event()

        def trip_soon():
            time.sleep(0.2)
            elastic.note_trip([1])

        t = threading.Thread(target=trip_soon)
        t.start()
        with pytest.raises(elastic.PeerLossRecovery):
            elastic.guarded_sync(lambda: release.wait(30.0), poll=0.05)
        t.join()
        elastic.clear_trip()
        release.set()
        assert elastic.guarded_sync(lambda: 3) == 3
        assert elastic._SYNC_WORKER is not w


class TestOrphanShardSweep:
    def test_pairless_shards_are_swept(self, tmp_path):
        for n in (4, 6):
            File.save({"n": n}, str(tmp_path / f"model.{n}"))
            File.save({"n": n}, str(tmp_path / f"state.{n}"))
        # label 1: its pair was pruned earlier but a shard survived a
        # failed delete (or a lagging rank's async writer)
        File.save({"r": 0}, shard_file(str(tmp_path), 1, 0, 2))
        prune_checkpoints(str(tmp_path), keep=2)
        left = sorted(os.listdir(str(tmp_path)))
        assert not any(".shard" in f and f.startswith("state.1.")
                       for f in left), left

    def test_in_flight_newer_shard_is_not_swept(self, tmp_path):
        for n in (4, 6):
            File.save({"n": n}, str(tmp_path / f"model.{n}"))
            File.save({"n": n}, str(tmp_path / f"state.{n}"))
        # label 8: a rank's shard landed before rank 0's state.8 — newer
        # than every pair, must survive the sweep
        File.save({"r": 1}, shard_file(str(tmp_path), 8, 1, 2))
        prune_checkpoints(str(tmp_path), keep=1)
        left = sorted(os.listdir(str(tmp_path)))
        assert any(f.startswith("state.8.shard") for f in left), left


# ---------------------------------------------------------------------------
# Review round 3: shard-set-aware retention, reform batch validation,
# elastic bring-up fail-fast
# ---------------------------------------------------------------------------

class TestShardAwareRetention:
    def _pair(self, d, n):
        File.save({"n": n}, str(d / f"model.{n}"))
        File.save({"n": n}, str(d / f"state.{n}"))

    def test_incomplete_shard_set_invalidates_snapshot(self, tmp_path):
        from bigdl_tpu.optim.optimizer import shard_set_complete
        self._pair(tmp_path, 4)
        File.save({"r": 0}, shard_file(str(tmp_path), 4, 0, 3))
        File.save({"r": 1}, shard_file(str(tmp_path), 4, 1, 3))
        # shard 2 of 3 never landed (its rank died mid-write)
        assert not shard_set_complete(str(tmp_path), 4)
        assert not snapshot_valid(str(tmp_path), 4)
        for r in (2,):
            File.save({"r": r}, shard_file(str(tmp_path), 4, r, 3))
        assert shard_set_complete(str(tmp_path), 4)
        assert snapshot_valid(str(tmp_path), 4)

    def test_prune_keeps_last_complete_when_newest_lacks_a_shard(
            self, tmp_path):
        """just_written vouches only for the writing rank's files: a
        newest snapshot missing another rank's shard must not anchor
        retention — the older COMPLETE snapshot survives keep=1."""
        self._pair(tmp_path, 2)
        for r in range(2):
            File.save({"r": r}, shard_file(str(tmp_path), 2, r, 2))
        self._pair(tmp_path, 6)
        File.save({"r": 0}, shard_file(str(tmp_path), 6, 0, 2))
        # rank 1 died before state.6.shard1of2 landed
        prune_checkpoints(str(tmp_path), keep=1, just_written=6)
        labels = list_checkpoints(str(tmp_path))
        assert 2 in labels, labels


class TestReformBatchValidation:
    def test_indivisible_global_batch_aborts_recovery(self, monkeypatch):
        opt, _ = _zero1_run(2, 1)
        samples = _data(n=16)
        opt.dataset = (DataSet.array(samples)
                       >> SampleToBatch(global_batch_size=16))
        import unittest.mock as mock
        with mock.patch.object(jax, "process_count", return_value=3):
            with pytest.raises(elastic.ReformAbort, match="divided"):
                opt._reshard_dataset()

    def test_divisible_global_batch_passes(self):
        opt, _ = _zero1_run(2, 1)
        samples = _data(n=16)
        opt.dataset = (DataSet.array(samples)
                       >> SampleToBatch(global_batch_size=16))
        opt._reshard_dataset()   # process_count() == 1: divides


class TestElasticBringUpFailFast:
    def test_metadata_path_with_flag_raises(self, monkeypatch):
        from bigdl_tpu.utils.engine import Engine
        monkeypatch.setenv("BIGDL_ELASTIC", "1")
        with pytest.raises(ValueError, match="BIGDL_ELASTIC"):
            Engine.init_distributed()

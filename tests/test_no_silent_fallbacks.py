"""The fallbacks that used to hide the device, and what stands in their
place (ISSUE 21): no MFU against another chip's peak, no kernel quietly
interpreted on an unknown backend, a compile cache the environment can
place, replica workers that run where their parent runs, one replica per
local device, and a rehearsal that asks for its CPU devices plainly.
(``bench.py``'s part is in tests/test_bench_contract.py.)"""
import os
import subprocess
import sys
import types

import jax
import pytest

from bigdl_tpu.obs import ledger as obs_ledger
from bigdl_tpu.ops import pallas_kernels as pk
from bigdl_tpu.serve import cluster
from bigdl_tpu.utils import engine

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


def device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


# -- the MFU denominator -----------------------------------------------------

@pytest.mark.parametrize("kind,peak", [
    ("TPU v5 lite", 197e12), ("TPU v5e", 197e12), ("TPU v5p", 459e12),
    ("TPU v5", 459e12), ("TPU v4", 275e12), ("TPU v6 lite", 918e12),
    ("TPU v6e", 918e12)])
def test_known_chips_have_their_datasheet_peak(kind, peak):
    assert obs_ledger.device_peak_flops(device("tpu", kind)) == peak


@pytest.mark.parametrize("platform,kind", [("tpu", "TPU v9 mega"),
                                           ("gpu", "NVIDIA H100")])
def test_unknown_device_kind_is_an_error_not_the_v5e_peak(platform, kind):
    with pytest.raises(KeyError, match=kind):
        obs_ledger.device_peak_flops(device(platform, kind))


def test_the_cpu_has_no_peak():
    assert obs_ledger.device_peak_flops() is None          # this suite
    assert obs_ledger.device_peak_flops(device("cpu", "cpu")) is None


# -- kernel gates ------------------------------------------------------------

def test_on_tpu_answers_for_cpu_and_tpu_only(monkeypatch):
    assert pk._on_tpu() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pk._on_tpu() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu' backend"):
        pk._on_tpu()


def test_recurrence_gate_does_not_take_the_scan_on_an_unknown_backend(
        monkeypatch):
    from bigdl_tpu.nn import recurrent
    assert recurrent._pallas_gate() == (False, False)      # CPU: lax.scan
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu' backend"):
        recurrent._pallas_gate()


# -- the compile cache -------------------------------------------------------

@pytest.fixture
def config_updates(monkeypatch):
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.__setitem__(name, value))
    return updates


def cache_dir_setting(updates):
    return [v for k, v in updates.items() if k.endswith("cache_dir")]


def test_cache_dir_from_the_environment_is_left_to_jax(monkeypatch,
                                                       config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert engine.enable_compile_cache() == "/somewhere/else"
    assert cache_dir_setting(config_updates) == []     # jax reads the env
    assert config_updates == {
        "jax_persistent_cache_min_compile_time_secs": 0.0,
        "jax_persistent_cache_min_entry_size_bytes": 0}


def test_default_cache_dir_is_fixed_inside_the_checkout(monkeypatch,
                                                        config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".xla_cache")
    assert engine.enable_compile_cache() == want
    assert cache_dir_setting(config_updates) == [want]
    # the same path on every call: the path is part of the cache key
    assert engine.enable_compile_cache() == want


def test_one_place_in_the_tree_sets_the_cache_dir():
    setting = "jax_compilation" + "_cache_dir"
    hits = []
    for base, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d != "chiprun_out"]
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(base, name)
                with open(path, encoding="utf-8") as f:
                    if setting in f.read():
                        hits.append(os.path.relpath(path, ROOT))
    assert hits == [os.path.join("bigdl_tpu", "utils", "engine.py")]


# -- replica workers ---------------------------------------------------------

_WORKER_PROBE = """
import json, jax
from bigdl_tpu.serve.cluster import init_worker_runtime
init_worker_runtime()
print(json.dumps({"platforms": jax.config.jax_platforms,
                  "cpu_devices": jax.config.jax_num_cpu_devices}))
"""


def worker_runtime(env):
    """jax config of a replica worker started under ``env`` — read without
    touching a backend."""
    import json
    base = {k: v for k, v in os.environ.items()
            if k not in ("JAX_PLATFORMS", cluster.ENV_WORKER_PLATFORM,
                         "BIGDL_SERVE_WORKER_DEVICES")}
    out = subprocess.run([sys.executable, "-c", _WORKER_PROBE], cwd=ROOT,
                         env={**base, **env}, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_worker_is_not_pinned_to_the_cpu_by_default():
    """Nothing said: the worker takes whatever jax finds best — on a chip
    machine the chip, where the old default silently served from the CPU."""
    assert worker_runtime({})["platforms"] is None


def test_worker_follows_its_parents_platform():
    got = worker_runtime({"JAX_PLATFORMS": "cpu",
                          "BIGDL_SERVE_WORKER_DEVICES": "3"})
    # jax reads the inherited JAX_PLATFORMS itself; a CPU worker also gets
    # its device count pinned
    assert got == {"platforms": "cpu", "cpu_devices": 3}


def test_worker_platform_can_still_be_named():
    got = worker_runtime({cluster.ENV_WORKER_PLATFORM: "cpu"})
    assert got["platforms"] == "cpu" and got["cpu_devices"] == 1


def test_process_replica_child_runs_on_the_parents_platform():
    """End to end: a real ProcessReplica child of this (CPU) suite serves
    on the CPU because it inherits the platform, not because of a default."""
    import numpy as np
    import bigdl_tpu.nn as nn
    from bigdl_tpu.serve.cluster import ProcessReplica
    assert os.environ["JAX_PLATFORMS"] == "cpu"             # conftest
    assert cluster.ENV_WORKER_PLATFORM not in os.environ
    model = nn.Sequential(nn.Linear(4, 3), nn.LogSoftMax())
    replica = ProcessReplica(model, name="inherit", input_shape=(4,),
                             max_batch=2)
    try:
        y = replica.submit(np.ones((4,), np.float32)).result(timeout=60)
        assert y.shape == (3,) and np.isfinite(y).all()
    finally:
        replica.close()


def test_a_parent_that_holds_the_chip_cannot_start_chip_children(
        monkeypatch):
    from jax._src import xla_bridge
    monkeypatch.setattr(xla_bridge, "backends_are_initialized", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(cluster.ReplicaSpawnError, match="one process"):
        cluster.child_process_env()
    # told otherwise, the child may start
    env = cluster.child_process_env({cluster.ENV_WORKER_PLATFORM: "cpu"})
    assert env[cluster.ENV_WORKER_PLATFORM] == "cpu"
    assert ROOT in env["PYTHONPATH"]


# -- in-process replicas: one per local device -------------------------------

def test_pool_replicas_take_one_local_device_each():
    import numpy as np
    import bigdl_tpu.nn as nn
    from bigdl_tpu.serve.cluster import ReplicaPool
    model = nn.Sequential(nn.Linear(4, 3), nn.LogSoftMax())
    n = len(jax.local_devices())
    pool = ReplicaPool(model, n_replicas=n + 1, input_shape=(4,),
                       max_batch=2)
    try:
        devices = [r.engine.device for r in pool.replicas]
        assert devices == jax.local_devices() + jax.local_devices()[:1]
        for r in pool.replicas:
            leaves = jax.tree_util.tree_leaves(r.engine._weights)
            assert all(leaf.devices() == {r.engine.device}
                       for leaf in leaves)
        out = pool.predict(np.ones((2 * n, 4), np.float32))
        assert out.shape == (2 * n, 3)
    finally:
        pool.close()


def test_decoder_state_lives_on_its_device():
    from bigdl_tpu.models.transformer import TransformerLM, lm_decode
    from bigdl_tpu.serve.decode import ContinuousDecoder
    lm = TransformerLM(vocab_size=11, d_model=16, n_heads=2, n_layers=1,
                       hidden=32, dropout=0.0)
    device = jax.local_devices()[3]
    dec = ContinuousDecoder(lm, max_slots=2, n_pos=16, device=device)
    try:
        future = dec.submit([1, 2, 3], 4)
        dec.run()
        assert future.result() == lm_decode(lm, [1, 2, 3], 4, greedy=True)
        assert all(c.devices() == {device} for c in dec._caches)
    finally:
        dec.close()
    from bigdl_tpu.parallel.mesh import hybrid_mesh
    with pytest.raises(ValueError, match="not both"):
        ContinuousDecoder(lm, max_slots=2, n_pos=16, device=device,
                          mesh=hybrid_mesh(1, 2, jax.devices()[:2]))


# -- the multichip rehearsal -------------------------------------------------

def test_dryrun_refuses_a_backend_it_did_not_ask_for(monkeypatch):
    """After ``entry()`` took the chip in this process the rehearsal used
    to switch to whatever CPU devices existed; now it says so."""
    sys.path.insert(0, ROOT)
    import __graft_entry__ as graft

    def already_initialized(name, value):
        raise RuntimeError("config should be updated before backends")

    monkeypatch.setattr(jax.config, "update", already_initialized)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="fresh process"):
        graft.dryrun_multichip(8)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    with pytest.raises(RuntimeError, match="needs 64 CPU devices"):
        graft.dryrun_multichip(64)

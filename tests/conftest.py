"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's pattern of testing multi-node without a cluster
(DistriOptimizerSpec runs Engine.init(nodeNumber=4,...) against a local
SparkContext, SURVEY.md §4): here the "cluster" is 8 virtual XLA CPU
devices, so every sharding/collective path compiles and runs in CI with no
TPU attached.
"""
import os

# the suite never needs a chip; subprocesses the tests start inherit this
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np
import pytest

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
from bigdl_tpu.utils.engine import enable_compile_cache  # noqa: E402

# the suite is dominated by XLA recompiles (each parametrized crosscheck
# compiles fresh); warm runs pull the executable from disk instead
enable_compile_cache()
# XLA CPU may route f32 matmuls through AMX/bf16; pin full precision so
# value tests compare against numpy exactly.  (On TPU the default bf16-pass
# MXU precision is the intended fast path — production code does not set this.)
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture(autouse=True)
def _seed():
    import sys

    from bigdl_tpu.utils.random import set_seed
    from bigdl_tpu.utils.log import reset_warn_cache
    set_seed(1)
    np.random.seed(1)
    # warn_every's cache is process-global: a warning rate-limited by an
    # earlier test must not stay suppressed in this one
    reset_warn_cache()
    # the shared executable cache is process-global too: identical
    # architectures across tests share fingerprints, so compile-counter
    # assertions need a per-test registry (reset only when loaded)
    xc = sys.modules.get("bigdl_tpu.serve.xcache")
    if xc is not None:
        xc.reset()
    # same story for the obs metrics registry: engines/routers register
    # per-name series, and counter assertions need a clean registry
    mx = sys.modules.get("bigdl_tpu.obs.metrics")
    if mx is not None:
        mx.reset()
    # and the cost ledger, whose capture counter the warm-path audits
    # assert on (reset also stops an env-started HBM sampler thread)
    lg = sys.modules.get("bigdl_tpu.obs.ledger")
    if lg is not None:
        lg.reset()
    # and the flight recorder: a per-test ring keeps forensic-bundle
    # and tail-retention assertions independent across tests
    fr = sys.modules.get("bigdl_tpu.obs.recorder")
    if fr is not None:
        fr.reset()
    yield


@pytest.fixture(autouse=True, scope="module")
def _default_dtype_policy():
    """The dtype policy is process-global, and a benchmark runner sets its
    configuration's (BF16_COMPUTE): a rehearsal of one left it set, so the
    value tests that the same worker ran afterwards (test_moe_layer,
    test_perf_rewrites, test_pipeline_optimizer under ``--dist loadfile``)
    compared bf16 products with float32 ones.  Every test file starts from
    the default policy."""
    from bigdl_tpu import tensor as bt
    bt.set_policy(bt.FP32)
    yield


@pytest.fixture
def obs_run_dir(tmp_path):
    """A configured obs run directory (JSONL sink under tmp_path), torn
    back down to the env-default (ring-only) log afterwards."""
    from bigdl_tpu.obs import events
    run_dir = tmp_path / "obs"
    events.configure(str(run_dir))
    yield str(run_dir)
    events.reset()


@pytest.fixture
def rng():
    return np.random.RandomState(0)


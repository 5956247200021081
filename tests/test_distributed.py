"""Distributed tests on the 8-device virtual CPU mesh.

Mirrors the reference's no-cluster multi-node testing pattern
(DistriOptimizerSpec with Engine.init(4,4)+local SparkContext, SURVEY.md §4):
collectives, DistriOptimizer equivalence to LocalOptimizer (the
Ref-optimizer oracle pattern, RefLocalOptimizer.scala:30), ring attention.
"""
import numpy as np
import jax

from jax import shard_map
import jax.numpy as jnp
import pytest
from functools import partial
from jax.sharding import NamedSharding, PartitionSpec as P

import bigdl_tpu.nn as nn
from bigdl_tpu.parallel.mesh import make_mesh, data_parallel_mesh
from bigdl_tpu.parallel import collectives as coll
from bigdl_tpu.parallel.ring_attention import (
    ring_self_attention, full_attention,
)
from bigdl_tpu.utils.table import T


def test_mesh_construction():
    mesh = make_mesh({"data": 4, "model": 2})
    assert mesh.shape == {"data": 4, "model": 2}
    mesh2 = make_mesh({"data": -1, "model": 2})
    assert mesh2.shape["data"] == 4


def test_collectives_shard_map():
    mesh = data_parallel_mesh()
    n = mesh.size

    @partial(shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    def f(x):
        return coll.all_reduce(x.sum(keepdims=True), "data") * jnp.ones_like(x)

    x = jnp.arange(float(n * 2))
    out = f(x)
    np.testing.assert_allclose(out, x.sum(), rtol=1e-6)


def test_reduce_scatter_all_gather_roundtrip():
    """reduce-scatter + all-gather == all-reduce — the decomposition the
    reference implements by hand (SURVEY.md §2.5)."""
    mesh = data_parallel_mesh()
    n = mesh.size

    @partial(shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    def rs_ag(x):
        scattered = coll.reduce_scatter(x, "data")
        return coll.all_gather(scattered, "data")

    @partial(shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    def ar(x):
        return coll.all_reduce(x, "data")

    # local chunk (n elements) must divide by the shard count for tiled RS
    x = jnp.asarray(np.random.RandomState(0).randn(n * n).astype(np.float32))
    np.testing.assert_allclose(rs_ag(x), ar(x), rtol=1e-5)


def test_ring_shift():
    mesh = data_parallel_mesh()
    n = mesh.size

    @partial(shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    def f(x):
        return coll.ring_shift(x, "data", 1)

    x = jnp.arange(float(n))
    out = np.asarray(f(x))
    np.testing.assert_allclose(out, np.roll(np.arange(float(n)), 1))


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, causal):
        mesh = make_mesh({"seq": 8})
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(2, 16, 2, 8), jnp.float32)
        k = jnp.asarray(rs.randn(2, 16, 2, 8), jnp.float32)
        v = jnp.asarray(rs.randn(2, 16, 2, 8), jnp.float32)
        ring = ring_self_attention(q, k, v, mesh, "seq", causal=causal)
        full = full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(ring, full, atol=1e-5)

    def test_matches_full_attention_bf16_inputs(self):
        """The exact-math pair holds for bf16 q/k/v too — what the
        attention core feeds both paths under a reduced-precision
        compute policy (nn/attention.py): scores and online-softmax
        stats stay f32 via preferred_element_type, so ring and full
        agree to bf16-output rounding."""
        mesh = make_mesh({"seq": 8})
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(2, 16, 2, 8), jnp.bfloat16)
        k = jnp.asarray(rs.randn(2, 16, 2, 8), jnp.bfloat16)
        v = jnp.asarray(rs.randn(2, 16, 2, 8), jnp.bfloat16)
        ring = ring_self_attention(q, k, v, mesh, "seq", causal=True)
        full = full_attention(q, k, v, causal=True)
        assert ring.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(ring, np.float32),
                                   np.asarray(full, np.float32),
                                   atol=2e-2)

    def test_gradients_match(self):
        mesh = make_mesh({"seq": 4}, jax.devices()[:4])
        rs = np.random.RandomState(1)
        q = jnp.asarray(rs.randn(1, 8, 2, 4), jnp.float32)
        k = jnp.asarray(rs.randn(1, 8, 2, 4), jnp.float32)
        v = jnp.asarray(rs.randn(1, 8, 2, 4), jnp.float32)

        g_ring = jax.grad(lambda q_: (ring_self_attention(
            q_, k, v, mesh, "seq", causal=True) ** 2).sum())(q)
        g_full = jax.grad(lambda q_: (full_attention(
            q_, k, v, causal=True) ** 2).sum())(q)
        np.testing.assert_allclose(g_ring, g_full, atol=1e-4)


class TestDistriOptimizer:
    def _make_data(self, n=64, d=8, classes=4):
        from bigdl_tpu.dataset import Sample
        rng = np.random.RandomState(0)
        w = rng.randn(d, classes)
        xs = rng.randn(n, d).astype(np.float32)
        ys = (xs @ w).argmax(1) + 1.0
        return [Sample(x, np.asarray([y])) for x, y in zip(xs, ys)]

    def _model(self):
        from bigdl_tpu.utils.random import set_seed
        set_seed(7)
        return nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4),
                             nn.LogSoftMax())

    def test_matches_local_optimizer(self):
        """DistriOptimizer over the 8-device mesh must produce the same
        params as LocalOptimizer on one device for identical batches —
        the RefOptimizer oracle test (ref RefDistriOptimizer.scala:35)."""
        from bigdl_tpu.dataset import DataSet, SampleToBatch
        from bigdl_tpu.optim import (
            LocalOptimizer, DistriOptimizer, max_iteration)
        from bigdl_tpu.utils.random import set_seed

        samples = self._make_data()

        def run(opt_cls, **kw):
            set_seed(3)
            model = self._model()
            ds = DataSet.array(samples) >> SampleToBatch(32)
            opt = opt_cls(model, ds, nn.ClassNLLCriterion(), **kw)
            opt.set_state(T(learningRate=0.1))
            opt.set_end_when(max_iteration(4))
            return opt.optimize()

        m_local = run(LocalOptimizer)
        m_distri = run(DistriOptimizer)
        for wl, wd in zip(m_local.parameters()[0], m_distri.parameters()[0]):
            np.testing.assert_allclose(np.asarray(wl), np.asarray(wd),
                                       rtol=1e-4, atol=1e-5)

    def test_bf16_gradient_compression_matches_uncompressed(self):
        """gradient_compression='bf16' (the FP16 wire-codec role,
        FP16CompressedTensor.scala:29) must train equivalently to plain DP
        up to bf16 rounding of the gradient."""
        from bigdl_tpu.dataset import DataSet, SampleToBatch
        from bigdl_tpu.optim import DistriOptimizer, max_iteration
        from bigdl_tpu.utils.random import set_seed

        samples = self._make_data()

        def run(**kw):
            set_seed(3)
            model = self._model()
            ds = DataSet.array(samples) >> SampleToBatch(32)
            opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), **kw)
            opt.set_state(T(learningRate=0.1))
            opt.set_end_when(max_iteration(4))
            return opt.optimize()

        m_plain = run()
        m_comp = run(gradient_compression="bf16")
        for wp, wc in zip(m_plain.parameters()[0], m_comp.parameters()[0]):
            # bf16 has ~3 decimal digits; 4 SGD steps accumulate a little
            np.testing.assert_allclose(np.asarray(wp), np.asarray(wc),
                                       rtol=2e-2, atol=2e-3)

    def test_bf16_compression_composes_with_zero1(self):
        """VERDICT r3 item 2: the fp16 wire codec and the owner-partition
        update are ONE mechanism in the reference
        (AllReduceParameter.scala:162-235 — compressed gradient slices
        feed the per-partition optimMethod); here the composition is a
        bf16 psum_scatter + data-sharded flat optimizer state + f32
        all_gather.  Must be trajectory-identical to the bf16 path with
        replicated state: both round the gradient to bf16 exactly once,
        and on the power-of-two (8-rank) axis the mean's /N is an exact
        exponent shift, so the updates are the same numbers."""
        from bigdl_tpu.dataset import DataSet, SampleToBatch
        from bigdl_tpu.optim import DistriOptimizer, max_iteration
        from bigdl_tpu.utils.random import set_seed

        samples = self._make_data()

        def run(**kw):
            set_seed(3)
            # odd-sized head so the flat length (8*17+17+17*4+4 = 225)
            # does not divide the 8-rank data axis — exercises padding
            model = nn.Sequential(nn.Linear(8, 17), nn.ReLU(True),
                                  nn.Linear(17, 4), nn.LogSoftMax())
            ds = DataSet.array(samples) >> SampleToBatch(32)
            opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), **kw)
            opt.set_state(T(learningRate=0.1, momentum=0.9,
                            weightDecay=1e-4))
            opt.set_end_when(max_iteration(4))
            opt.optimize()
            return model

        m_rep = run(gradient_compression="bf16")
        m_z1 = run(gradient_compression="bf16", zero1=True)
        for wp, wc in zip(m_rep.parameters()[0], m_z1.parameters()[0]):
            np.testing.assert_allclose(np.asarray(wp), np.asarray(wc),
                                       rtol=1e-6, atol=1e-7)

    def test_bf16_zero1_opt_state_sharded(self):
        """The ZeRO-1 HBM claim, measured on the real shardings: the
        compressed-ZeRO-1 optimizer state is a flat vector sharded over
        the 8-rank data axis — per-device bytes drop 8x vs the replicated
        compressed path (plus <=7 floats of padding)."""
        import jax as _jax
        from jax.sharding import PartitionSpec as _P
        from bigdl_tpu.dataset import DataSet, SampleToBatch
        from bigdl_tpu.optim import DistriOptimizer
        from bigdl_tpu.utils.random import set_seed

        samples = self._make_data()
        set_seed(3)
        model = self._model()
        ds = DataSet.array(samples) >> SampleToBatch(32)
        opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(),
                              gradient_compression="bf16", zero1=True)
        opt.set_state(T(learningRate=0.1, momentum=0.9))
        opt._build_step()          # computes the padded flat length
        opt_state = opt._initial_opt_state(model.params())

        n_param = sum(int(np.prod(w.shape)) for w in model.parameters()[0])
        ndata = opt.mesh.shape["data"]
        vel = opt_state["velocity"]
        assert vel.shape == (opt._z1c_flat,)
        assert n_param <= opt._z1c_flat < n_param + ndata
        assert vel.sharding.spec == _P("data")
        shard = vel.addressable_shards[0].data
        assert shard.shape == (opt._z1c_flat // ndata,)

        # optimizers with scalar state leaves: flat mirrors shard, the 0-d
        # step counter stays replicated (it is rank-identical)
        from bigdl_tpu.optim import Adagrad, max_iteration
        set_seed(3)
        model2 = self._model()
        opt2 = DistriOptimizer(model2,
                               DataSet.array(samples) >> SampleToBatch(32),
                               nn.ClassNLLCriterion(),
                               gradient_compression="bf16", zero1=True)
        opt2.set_optim_method(Adagrad())
        opt2.set_state(T(learningRate=0.1))
        opt2.set_end_when(max_iteration(2))
        opt2.optimize()
        assert np.isfinite(opt2.state["loss"])

    def test_gradient_compression_with_batchnorm(self):
        """BN under the shard_map path: per-shard batch stats, pmean-merged
        running stats (the reference's per-replica BN behavior).  Verify it
        trains and its running stats land near the plain path's."""
        from bigdl_tpu.dataset import DataSet, SampleToBatch
        from bigdl_tpu.optim import DistriOptimizer, max_iteration
        from bigdl_tpu.utils.random import set_seed

        samples = self._make_data()

        def run(**kw):
            set_seed(3)
            model = nn.Sequential(nn.Linear(8, 16), nn.BatchNormalization(16),
                                  nn.ReLU(), nn.Linear(16, 4), nn.LogSoftMax())
            ds = DataSet.array(samples) >> SampleToBatch(32)
            opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), **kw)
            opt.set_state(T(learningRate=0.1))
            opt.set_end_when(max_iteration(4))
            return opt.optimize()

        m_plain = run()
        m_comp = run(gradient_compression="bf16")
        sp, sc = m_plain.state(), m_comp.state()
        flat_p = {k: v for k, v in jax.tree_util.tree_leaves_with_path(sp)}
        flat_c = {k: v for k, v in jax.tree_util.tree_leaves_with_path(sc)}
        assert flat_p.keys() == flat_c.keys() and flat_p
        for k in flat_p:
            a, b = np.asarray(flat_p[k]), np.asarray(flat_c[k])
            assert np.all(np.isfinite(b))
            # per-shard stats differ from global-batch stats by the
            # between-shard term — close but not identical
            np.testing.assert_allclose(a, b, rtol=0.35, atol=0.1)

    def test_gradient_compression_rejects_bad_mode(self):
        from bigdl_tpu.dataset import DataSet, SampleToBatch
        from bigdl_tpu.optim import DistriOptimizer
        ds = DataSet.array(self._make_data()) >> SampleToBatch(32)
        with pytest.raises(ValueError):
            DistriOptimizer(self._model(), ds, nn.ClassNLLCriterion(),
                            gradient_compression="int8")

    def test_trains_on_sharded_dataset(self):
        from bigdl_tpu.dataset import DataSet, SampleToBatch
        from bigdl_tpu.optim import Optimizer, DistriOptimizer, max_iteration

        ds = DataSet.array(self._make_data(), distributed=True) >> SampleToBatch(32)
        model = self._model()
        opt = Optimizer(model, ds, nn.ClassNLLCriterion())
        assert isinstance(opt, DistriOptimizer)
        opt.set_state(T(learningRate=0.5, momentum=0.9))
        opt.set_end_when(max_iteration(20))
        opt.optimize()
        out = model.predict(jnp.asarray(np.stack([s.feature for s in self._make_data()[:16]])))
        acc = float((np.argmax(np.asarray(out), 1) + 1 ==
                     np.asarray([s.label[0] for s in self._make_data()[:16]])).mean())
        assert acc > 0.5  # learned something real


def test_graft_entry_dryrun():
    """The driver contract: dryrun_multichip compiles+runs on 8 devices."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "__graft_entry__", os.path.join(os.path.dirname(__file__), "..",
                                        "__graft_entry__.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    m.dryrun_multichip(8)


class TestTensorParallelTraining:
    def test_dp_tp_hybrid_matches_dp(self):
        """DP x TP training must produce the same numbers as pure DP —
        sharding is a layout, not a semantic change."""
        from bigdl_tpu.dataset import DataSet, SampleToBatch, Sample
        from bigdl_tpu.optim import DistriOptimizer, max_iteration
        from bigdl_tpu.parallel.mesh import hybrid_mesh
        from bigdl_tpu.utils.random import set_seed

        rng = np.random.RandomState(0)
        samples = [Sample(rng.randn(8).astype(np.float32),
                          np.asarray([rng.randint(1, 5)], np.float32))
                   for _ in range(64)]

        def run(**kw):
            set_seed(11)
            model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                                  nn.Linear(16, 4), nn.LogSoftMax())
            ds = DataSet.array(samples) >> SampleToBatch(32)
            opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), **kw)
            opt.set_state(T(learningRate=0.1, momentum=0.9))
            opt.set_end_when(max_iteration(4))
            return opt.optimize()

        m_dp = run()
        m_tp = run(mesh=hybrid_mesh(dp=4, mp=2), tensor_parallel=True)
        for a, b in zip(m_dp.parameters()[0], m_tp.parameters()[0]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


def test_zero1_matches_replicated():
    """ZeRO-1 optimizer-state sharding must not change the numbers."""
    from bigdl_tpu.dataset import DataSet, SampleToBatch, Sample
    from bigdl_tpu.optim import DistriOptimizer, max_iteration
    from bigdl_tpu.utils.random import set_seed

    rng = np.random.RandomState(1)
    samples = [Sample(rng.randn(8).astype(np.float32),
                      np.asarray([rng.randint(1, 5)], np.float32))
               for _ in range(64)]

    def run(**kw):
        set_seed(13)
        model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                              nn.Linear(16, 4), nn.LogSoftMax())
        ds = DataSet.array(samples) >> SampleToBatch(32)
        opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(), **kw)
        opt.set_state(T(learningRate=0.1, momentum=0.9))
        opt.set_end_when(max_iteration(4))
        return opt.optimize()

    from bigdl_tpu.parallel.mesh import hybrid_mesh

    m_rep = run()
    m_z1 = run(zero1=True)
    # ZeRO-1 composed with tensor parallelism (zero1_tp_rule) must agree too
    m_z1tp = run(mesh=hybrid_mesh(dp=4, mp=2), tensor_parallel=True,
                 zero1=True)
    for variant in (m_z1, m_z1tp):
        for a, b in zip(m_rep.parameters()[0], variant.parameters()[0]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


def test_distri_iterations_per_dispatch_matches_single_step():
    """DistriOptimizer with the device-side n-step loop must reproduce
    the single-step trajectory on the 8-device mesh (deterministic
    model), including the bf16-compressed path compiling under scan."""
    import numpy as np
    import jax
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, Sample
    from bigdl_tpu.dataset.transformer import SampleToBatch
    from bigdl_tpu.optim import DistriOptimizer, max_iteration
    from bigdl_tpu.utils.table import T
    from bigdl_tpu.utils.random import set_seed

    rs = np.random.RandomState(2)
    # 48 samples / batch 16 = 3 steps per epoch: chunks of 3 align with
    # the epoch boundary, so the single-step path's end-of-epoch shuffle
    # lands at the same point (chunking defers shuffles to dispatch
    # granularity — documented semantics)
    xs = rs.randn(48, 6).astype(np.float32)
    ys = (rs.randint(0, 3, 48) + 1).astype(np.float32)
    samples = [Sample(x, np.asarray([y])) for x, y in zip(xs, ys)]

    def run(n_disp, compression=None):
        set_seed(7)
        ds = DataSet.array(samples) >> SampleToBatch(16)
        model = nn.Sequential(nn.Linear(6, 8), nn.Tanh(),
                              nn.Linear(8, 3), nn.LogSoftMax())
        opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(),
                              gradient_compression=compression)
        opt.set_state(T(learningRate=0.2, momentum=0.9))
        opt.set_end_when(max_iteration(6))
        if n_disp > 1:
            opt.set_iterations_per_dispatch(n_disp)
        opt.optimize()
        return model.params(), opt.state

    p1, s1 = run(1)
    p3, s3 = run(3)
    assert s1["neval"] == s3["neval"]
    assert s1["loss"] == pytest.approx(s3["loss"], rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p3)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

    # compressed path: same-n equivalence of its own trajectory
    pc1, sc1 = run(1, compression="bf16")
    pc3, sc3 = run(3, compression="bf16")
    assert sc1["loss"] == pytest.approx(sc3["loss"], rel=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(pc1),
                    jax.tree_util.tree_leaves(pc3)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)

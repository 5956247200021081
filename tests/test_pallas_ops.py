"""Pallas kernel tests (interpret mode on the CPU mesh)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import lax


@pytest.mark.perf
class TestPallasLRN:
    """Fused cross-channel LRN kernel (ops/pallas_kernels.lrn_channel):
    forward + closed-form backward vs the XLA reduce_window formulation,
    incl. ragged H*W not divisible by 128.  Evidence kernel — measured
    slower than XLA's path on v5e, so SpatialCrossMapLRN keeps
    _PALLAS=False (see the class comment + PERF_NOTES round 3)."""

    @pytest.mark.parametrize("shape,pars", [
        ((2, 8, 16, 8), (5, 1.0, 0.75, 1.0)),
        ((2, 6, 16, 16), (3, 2e-4, 0.9, 2.0)),
        ((2, 8, 7, 9), (5, 1.0, 0.75, 1.0)),      # ragged lanes
        ((2, 8, 16, 8), (4, 1.0, 0.75, 1.0)),     # EVEN size: asymmetric
    ])                                             # adjoint window in bwd
    def test_fwd_bwd_vs_xla(self, shape, pars):
        from bigdl_tpu.ops.pallas_kernels import lrn_channel
        size, alpha, beta, k = pars
        interpret = jax.devices()[0].platform != "tpu"

        def ref_lrn(x):
            lo = (size - 1) // 2
            hi = size - 1 - lo
            sq = lax.reduce_window(x * x, 0.0, lax.add, (1, size, 1, 1),
                                   (1, 1, 1, 1),
                                   ((0, 0), (lo, hi), (0, 0), (0, 0)))
            return x / (k + alpha / size * sq) ** beta

        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(*shape), jnp.float32)
        y = lrn_channel(x, size, alpha, beta, k, interpret)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref_lrn(x)),
                                   rtol=1e-5, atol=1e-6)
        g = jnp.asarray(rs.randn(*shape), jnp.float32)
        d_ref = jax.grad(lambda v: (ref_lrn(v) * g).sum())(x)
        d = jax.grad(
            lambda v: (lrn_channel(v, size, alpha, beta, k, interpret)
                       * g).sum())(x)
        np.testing.assert_allclose(np.asarray(d), np.asarray(d_ref),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.perf
class TestMosaicMaxPool:
    """Round-6 Mosaic maxpool pair (ops/pallas_kernels.mosaic_maxpool2d):
    argmax-storing forward + scatter-free gather backward vs the XLA
    oracle (reduce_window forward, select_and_scatter autodiff
    backward), overlapping STRIDED windows and tie positions included
    (coarsely quantized inputs).  Off by default in nn/pooling.py
    (_PALLAS_POOL) pending the device-clock A/B — these tests are the
    interpreter-mode equivalence half of the adoption contract."""

    CASES = [
        # Inception stem/transition geometry (3x3 stride 2, overlap)
        ((2, 5, 13, 17), (3, 3), (2, 2), ((1, 1), (1, 1))),
        # Inception in-block pool branches (3x3 stride 1, overlap)
        ((2, 3, 10, 12), (3, 3), (1, 1), ((1, 1), (1, 1))),
        # non-overlapping, asymmetric Torch ceil-mode style pads
        ((1, 4, 9, 11), (2, 2), (2, 2), ((0, 1), (1, 0))),
        # window larger than stride on both dims, fat pads
        ((1, 2, 12, 8), (5, 3), (3, 2), ((2, 2), (1, 1))),
        # non-tile-aligned batch (B=37) and tiny W
        ((37, 1, 13, 7), (3, 3), (2, 2), ((1, 1), (1, 1))),
        # non-tile-aligned channel count (C=100: ragged lanes)
        ((1, 100, 8, 8), (3, 3), (1, 1), ((0, 0), (0, 0))),
    ]

    @pytest.mark.parametrize("shape,win,st,pads", CASES)
    def test_fwd_bwd_vs_xla(self, shape, win, st, pads):
        from bigdl_tpu.ops.pallas_kernels import mosaic_maxpool2d
        interpret = jax.devices()[0].platform != "tpu"

        def ref_pool(v):
            return lax.reduce_window(v, -jnp.inf, lax.max, (1, 1) + win,
                                     (1, 1) + st,
                                     ((0, 0), (0, 0)) + pads)

        rs = np.random.RandomState(0)
        # quantized values force exact ties: the first-max rule must
        # match select_and_scatter's bit for bit
        x = jnp.asarray(np.round(rs.randn(*shape) * 2) / 2, jnp.float32)
        y_ref = ref_pool(x)
        y = mosaic_maxpool2d(x, win, st, pads, interpret)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref))

        g = jnp.asarray(rs.randn(*y_ref.shape).astype(np.float32))
        d_ref = jax.grad(lambda v: (ref_pool(v) * g).sum())(x)
        d = jax.grad(lambda v: (mosaic_maxpool2d(v, win, st, pads,
                                                 interpret) * g).sum())(x)
        np.testing.assert_allclose(np.asarray(d), np.asarray(d_ref),
                                   rtol=1e-5, atol=1e-5)

    def test_bf16_and_pooling_layer_route(self):
        """The nn/pooling.py _PALLAS_POOL='interpret' route produces the
        XLA path's output on the module's real geometry, bf16 included."""
        from bigdl_tpu.nn import pooling

        rs = np.random.RandomState(1)
        x = jnp.asarray(np.round(rs.randn(2, 6, 14, 14) * 2) / 2,
                        jnp.float32)
        m = pooling.SpatialMaxPooling(3, 3, 2, 2, 1, 1).ceil()
        y_ref = m.forward(x)
        old = pooling._PALLAS_POOL
        pooling._PALLAS_POOL = "interpret"
        try:
            y = pooling.SpatialMaxPooling(3, 3, 2, 2, 1, 1).ceil().forward(x)
        finally:
            pooling._PALLAS_POOL = old
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref))
        # bf16 input through the raw kernel (the policy-cast pool path)
        from bigdl_tpu.ops.pallas_kernels import mosaic_maxpool2d
        xb = x.astype(jnp.bfloat16)
        yb = mosaic_maxpool2d(xb, (3, 3), (2, 2), ((1, 1), (1, 1)), True)
        ref = lax.reduce_window(xb, -jnp.inf, lax.max, (1, 1, 3, 3),
                                (1, 1, 2, 2),
                                ((0, 0), (0, 0), (1, 1), (1, 1)))
        assert yb.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(yb, np.float32),
                                   np.asarray(ref, np.float32))


@pytest.mark.perf
class TestBlockedRecurrence:
    """Round-6 multi-timestep blocking: block_t > 1 must reproduce the
    block_t=1 kernels exactly (outputs) and up to f32 weight-grad
    summation order (gradients), including T not divisible by the
    block.  Non-tile-aligned shapes on purpose (B=37, T=13, H=100 where
    cheap enough)."""

    @pytest.mark.parametrize("block_t", [3, 8])
    def test_bilstm_blocked(self, block_t):
        from bigdl_tpu.ops.pallas_kernels import bilstm_recurrence
        rs = np.random.RandomState(0)
        t, nd, b, h = 13, 2, 37, 4
        zx = jnp.asarray(rs.randn(t, nd, b, 4 * h), jnp.float32)
        wht = jnp.asarray(rs.randn(nd, h, 4 * h) * 0.3, jnp.float32)
        go = jnp.asarray(rs.randn(t, nd, b, h), jnp.float32)
        y1 = bilstm_recurrence(zx, wht, True, 1)
        yk = bilstm_recurrence(zx, wht, True, block_t)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(yk),
                                   rtol=1e-6, atol=1e-6)
        g1 = jax.grad(lambda a, w: (bilstm_recurrence(a, w, True, 1)
                                    * go).sum(), argnums=(0, 1))(zx, wht)
        gk = jax.grad(lambda a, w: (bilstm_recurrence(a, w, True, block_t)
                                    * go).sum(), argnums=(0, 1))(zx, wht)
        for a, b_ in zip(g1, gk):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("block_t", [3, 8])
    def test_gru_blocked(self, block_t):
        from bigdl_tpu.ops.pallas_kernels import gru_recurrence
        rs = np.random.RandomState(1)
        t, nd, b, h = 13, 1, 5, 100
        zrz = jnp.asarray(rs.randn(t, nd, b, 2 * h), jnp.float32)
        zn = jnp.asarray(rs.randn(t, nd, b, h), jnp.float32)
        wrz = jnp.asarray(rs.randn(nd, h, 2 * h) * 0.1, jnp.float32)
        wh = jnp.asarray(rs.randn(nd, h, h) * 0.1, jnp.float32)
        go = jnp.asarray(rs.randn(t, nd, b, h), jnp.float32)
        y1 = gru_recurrence(zrz, zn, wrz, wh, True, 1)
        yk = gru_recurrence(zrz, zn, wrz, wh, True, block_t)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(yk),
                                   rtol=1e-6, atol=1e-6)
        g1 = jax.grad(lambda *a: (gru_recurrence(*a, True, 1) * go).sum(),
                      argnums=(0, 1, 2, 3))(zrz, zn, wrz, wh)
        gk = jax.grad(lambda *a: (gru_recurrence(*a, True, block_t)
                                  * go).sum(),
                      argnums=(0, 1, 2, 3))(zrz, zn, wrz, wh)
        for a, b_ in zip(g1, gk):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("block_t", [4])
    def test_rnn_blocked(self, block_t):
        from bigdl_tpu.ops.pallas_kernels import rnn_recurrence
        rs = np.random.RandomState(2)
        t, nd, b, h = 9, 2, 3, 6
        zx = jnp.asarray(rs.randn(t, nd, b, h), jnp.float32)
        wht = jnp.asarray(rs.randn(nd, h, h) * 0.3, jnp.float32)
        go = jnp.asarray(rs.randn(t, nd, b, h), jnp.float32)
        y1 = rnn_recurrence(zx, wht, True, 1)
        yk = rnn_recurrence(zx, wht, True, block_t)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(yk),
                                   rtol=1e-6, atol=1e-6)
        g1 = jax.grad(lambda *a: (rnn_recurrence(*a, True, 1) * go).sum(),
                      argnums=(0, 1))(zx, wht)
        gk = jax.grad(lambda *a: (rnn_recurrence(*a, True, block_t)
                                  * go).sum(), argnums=(0, 1))(zx, wht)
        for a, b_ in zip(g1, gk):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-5, atol=1e-6)

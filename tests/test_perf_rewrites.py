"""Guards for the operator forms the trained path runs (each one's
measurement is in PERF_NOTES rounds 2-5).  There is one form per operator,
so every reference here is plain ``jax.numpy`` / ``lax`` written in the
test, not a second path through the library:

- Concat merged-pointwise heads (containers.Concat._apply_merged) against
  each branch applied on its own and concatenated;
- the LRN's analytic VJP (normalization._lrn) against autodiff of the
  formula;
- the space-to-depth stem conv and its custom VJP against
  ``lax.conv_general_dilated`` on the raw operands;
- compute-dtype pooling and norm apply: only under a reduced-precision
  policy, only a float32 input, output dtype preserved;
- what each adopted form lowers to, and that no A/B switch is left beside
  them.
"""
import importlib
import inspect
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from jax import lax

import bigdl_tpu.nn as nn
from bigdl_tpu import tensor as bt
from bigdl_tpu.nn.module import Context
from bigdl_tpu.nn.normalization import SpatialCrossMapLRN
from bigdl_tpu.utils.random import set_seed


def _ctx():
    return Context(training=False, key=jax.random.PRNGKey(0))


def test_concat_merged_pointwise_matches_unmerged():
    from bigdl_tpu.models.inception import inception_module
    set_seed(3)
    blk = inception_module(192, 64, 96, 128, 16, 32, 32)
    assert blk._merge_plan() == [0, 1, 2]
    params, state = blk.params(), blk.state()
    x = jnp.asarray(np.random.RandomState(0).randn(2, 192, 14, 14),
                    jnp.float32)

    def merged(p):
        return (blk.apply(p, x, state, _ctx())[0] ** 2).sum()

    def branch_by_branch(p):
        outs = [br.apply(p[str(i)], x, state[str(i)], _ctx())[0]
                for i, br in enumerate(blk.modules)]
        return (jnp.concatenate(outs, axis=1) ** 2).sum()

    l1, g1 = jax.value_and_grad(merged)(params)
    l0, g0 = jax.value_and_grad(branch_by_branch)(params)
    assert l1 == pytest.approx(l0, rel=1e-6)
    np.testing.assert_allclose(np.asarray(ravel_pytree(g1)[0]),
                               np.asarray(ravel_pytree(g0)[0]),
                               rtol=1e-5, atol=1e-4)


def test_concat_without_pointwise_heads_unchanged():
    m = nn.Concat(2, nn.Sequential(nn.SpatialConvolution(4, 3, 3, 3, 1, 1,
                                                         1, 1)),
                  nn.Sequential(nn.SpatialMaxPooling(3, 3, 1, 1, 1, 1)))
    assert m._merge_plan() == []


@pytest.mark.parametrize("size", [5, 4])
def test_lrn_analytic_vjp_matches_autodiff(size):
    m = SpatialCrossMapLRN(size, 0.0001, 0.75)
    x = jnp.asarray(np.random.RandomState(0).randn(3, 16, 7, 7), jnp.float32)
    g = jnp.asarray(np.random.RandomState(1).randn(3, 16, 7, 7), jnp.float32)

    lo = (size - 1) // 2

    def formula(v):
        win = lax.reduce_window(
            v * v, 0.0, lax.add, (1, size, 1, 1), (1, 1, 1, 1),
            ((0, 0), (lo, size - 1 - lo), (0, 0), (0, 0)))
        return v / (1.0 + 0.0001 / size * win) ** 0.75

    y1, vjp1 = jax.vjp(lambda v: m._forward({}, v, {}, _ctx())[0], x)
    y0, vjp0 = jax.vjp(formula, x)
    dx1, dx0 = vjp1(g)[0], vjp0(g)[0]
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(dx1), np.asarray(dx0),
                               rtol=1e-5, atol=1e-6)


def test_s2d_stem_custom_vjp_matches_plain_conv():
    set_seed(4)
    m = nn.SpatialConvolution(3, 8, 7, 7, 2, 2, 3, 3)
    params = m.params()["~"]
    x = jnp.asarray(np.random.RandomState(0).randn(2, 3, 30, 30), jnp.float32)

    def plain(p, v):
        y = lax.conv_general_dilated(
            v, p["weight"], (2, 2), [(3, 3), (3, 3)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        return y + p["bias"][None, :, None, None]

    def run(f):
        y, vjp = jax.vjp(f, params, x)
        gp, gx = vjp(jnp.ones_like(y))
        return y, gp, gx

    y1, gp1, gx1 = run(lambda p, v: m._forward(p, v, {}, _ctx())[0])
    y0, gp0, gx0 = run(plain)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gp1["weight"]),
                               np.asarray(gp0["weight"]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx0),
                               rtol=1e-5, atol=1e-5)


def test_batchnorm_compute_dtype_keeps_f32_stats():
    """BN under a reduced-precision policy: the APPLY chain runs in the
    compute dtype, but batch statistics and running-stat EMAs stay f32
    and the output dtype is preserved."""
    set_seed(6)
    m = nn.SpatialBatchNormalization(4)
    x = jnp.asarray(np.random.RandomState(0).randn(8, 4, 5, 5), jnp.float32)
    ctx = Context(training=True, key=jax.random.PRNGKey(0))

    y32, s32 = m._forward(m.params()["~"], x, m.state()["~"], ctx)
    bt.set_policy(bt.BF16_COMPUTE)
    try:
        ybf, sbf = m._forward(m.params()["~"], x, m.state()["~"], ctx)
    finally:
        bt.set_policy(bt.FP32)
    assert ybf.dtype == jnp.float32
    for k in s32:
        assert sbf[k].dtype == jnp.float32
        # stats identical: they are computed from the f32 input either way
        np.testing.assert_allclose(np.asarray(sbf[k]), np.asarray(s32[k]),
                                   rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ybf), np.asarray(y32),
                               rtol=2e-2, atol=3e-2)


def test_maxpool_compute_dtype_scoped_to_policy():
    m = nn.SpatialMaxPooling(2, 2, 2, 2)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 4, 8, 8), jnp.float32)

    y_f32, _ = m._forward({}, x, {}, _ctx())
    assert y_f32.dtype == jnp.float32

    bt.set_policy(bt.BF16_COMPUTE)
    try:
        y_bf, _ = m._forward({}, x, {}, _ctx())
    finally:
        bt.set_policy(bt.FP32)
    # output dtype preserved; values equal up to bf16 rounding of the max
    assert y_bf.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y_bf), np.asarray(y_f32),
                               rtol=8e-3, atol=1e-6)
    # FP32 policy: bitwise the float32 window max
    y_ref = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 2, 2),
                              (1, 1, 2, 2), "VALID")
    np.testing.assert_array_equal(np.asarray(y_f32), np.asarray(y_ref))


@pytest.mark.parametrize("policy, dtype, narrows", [
    ("FP32", jnp.float32, False),
    ("FP32", jnp.float64, False),          # never cut down without a policy
    ("BF16_COMPUTE", jnp.float32, True),
    ("BF16_COMPUTE", jnp.bfloat16, False),
    ("BF16_COMPUTE", jnp.float64, False),  # nor under one
    ("BF16_ACT", jnp.float32, True),
])
def test_policy_narrows_only_float32_under_a_reduced_policy(policy, dtype,
                                                            narrows):
    """The one rule the pool, the LRN and the norms' apply share: a
    float64 input is never cut down, a bfloat16 one never widened."""
    x = jax.ShapeDtypeStruct((2, 4, 8, 8), dtype)
    assert getattr(bt, policy).narrows(x) is narrows


def _ops(text, name):
    """The lines of a lowered program that hold ``stablehlo.<name>``."""
    return [line for line in text.splitlines()
            if re.search(r"stablehlo\.%s\b" % name, line)]


def _lrn_backward_is_the_analytic_one():
    m = SpatialCrossMapLRN(5, 0.0001, 0.75)
    x = jax.ShapeDtypeStruct((8, 16, 7, 7), jnp.float32)
    text = jax.jit(jax.grad(
        lambda v: m._forward({}, v, {}, _ctx())[0].sum())).lower(x).as_text()
    # one window sum forward, one (reversed) backward, and no ``pad``: the
    # transpose of the forward's padded window sum is what autodiff adds
    assert len(_ops(text, "reduce_window")) == 2
    assert not _ops(text, "pad")
    assert len(_ops(text, "multiply")) <= 8     # autodiff of the formula: 15


def _overlapping_pool_runs_in_the_compute_dtype():
    m = nn.SpatialMaxPooling(3, 3, 2, 2).ceil()
    x = jax.ShapeDtypeStruct((8, 16, 14, 14), jnp.float32)
    text = jax.jit(lambda v: m._forward({}, v, {}, _ctx())[0]).lower(
        x).as_text()
    window, = re.findall(r"\) : \((tensor<[^>]*>), tensor<bf16>\) -> "
                         r"(tensor<[^>]*>)", text)
    assert window == ("tensor<8x16x14x14xbf16>", "tensor<8x16x7x7xbf16>")
    assert "-> (tensor<8x16x7x7xf32>" in text


def _tiling_pool_is_a_reshape_and_a_max():
    m = nn.SpatialMaxPooling(2, 2, 2, 2)
    x = jax.ShapeDtypeStruct((8, 16, 14, 14), jnp.float32)
    text = jax.jit(jax.grad(
        lambda v: m._forward({}, v, {}, _ctx())[0].sum())).lower(x).as_text()
    assert not _ops(text, "reduce_window")
    assert not _ops(text, "select_and_scatter")
    assert _ops(text, "reshape") and _ops(text, "reduce")


def _stem_convolves_space_to_depth_channels():
    set_seed(4)
    m = nn.SpatialConvolution(3, 8, 7, 7, 2, 2, 3, 3)
    x = jax.ShapeDtypeStruct((2, 3, 30, 30), jnp.float32)
    text = jax.jit(jax.value_and_grad(
        lambda p, v: m._forward(p, v, {}, _ctx())[0].sum(),
        argnums=(0, 1))).lower(m.params()["~"], x).as_text()
    convs = _ops(text, "convolution")
    assert len(convs) == 3                      # forward, dx, dw
    for line in convs:
        dims = [int(d) for shape in re.findall(r"tensor<([0-9x]+)x", line)
                for d in shape.split("x")]
        assert 12 in dims and 3 not in dims, line   # 3 x 2 x 2 channels


def _pointwise_heads_are_one_convolution():
    from bigdl_tpu.models.inception import inception_module
    set_seed(3)
    blk = inception_module(192, 64, 96, 128, 16, 32, 32)
    x = jax.ShapeDtypeStruct((2, 192, 14, 14), jnp.float32)
    text = jax.jit(lambda p, v: blk.apply(p, v, blk.state(), _ctx())[0]
                   ).lower(blk.params(), x).as_text()
    # the 3x3, the 5x5 and the pool's projection, + 1 for the three heads
    assert len(_ops(text, "convolution")) == 3 + 1


def _sgd_update_is_plain_xla():
    from bigdl_tpu.optim import SGD
    sgd = SGD()
    p = {"w": jax.ShapeDtypeStruct((300, 70), jnp.float32)}
    text = jax.jit(lambda g, v, w: sgd.update(
        g, v, w, {"lr": 0.1, "momentum": 0.9, "weight_decay": 1e-4})
    ).lower(p, {"velocity": p}, p).as_text()
    assert "custom_call" not in text and _ops(text, "multiply")


@pytest.mark.parametrize("form", [
    _lrn_backward_is_the_analytic_one,
    _overlapping_pool_runs_in_the_compute_dtype,
    _tiling_pool_is_a_reshape_and_a_max,
    _stem_convolves_space_to_depth_channels,
    _pointwise_heads_are_one_convolution,
    _sgd_update_is_plain_xla,
], ids=lambda f: f.__name__.strip("_"))
def test_adopted_forms_are_what_lowers(form):
    """Each adopted form, read off the lowered program under the policy
    the benchmark's cells train in."""
    bt.set_policy(bt.BF16_COMPUTE)
    try:
        form()
    finally:
        bt.set_policy(bt.FP32)


def test_no_ab_switch_left_on_the_trained_path():
    """One formulation per operator: a kernel PR flips its candidate's
    constant in its own diff and deletes the loser in the same PR.  The
    two names allowed are candidates ROADMAP S5 still has to time."""
    allowed = {"bigdl_tpu.nn.pooling._PALLAS_POOL",
               "bigdl_tpu.nn.normalization.SpatialCrossMapLRN._PALLAS"}
    found = set()
    for name in ("nn.conv", "nn.pooling", "nn.normalization",
                 "nn.containers", "optim.optim_method"):
        mod = importlib.import_module("bigdl_tpu." + name)
        owners = [(mod.__name__, mod)] + [
            (f"{mod.__name__}.{c.__name__}", c)
            for c in vars(mod).values()
            if inspect.isclass(c) and c.__module__ == mod.__name__]
        for prefix, owner in owners:
            found |= {f"{prefix}.{attr}" for attr, v in vars(owner).items()
                      if re.fullmatch(r"_[A-Z0-9_]+", attr)
                      and (isinstance(v, bool) or v == "interpret")}
    assert found == allowed
    from bigdl_tpu.optim import SGD
    with pytest.raises(TypeError):
        SGD(fused=True)

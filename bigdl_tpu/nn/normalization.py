"""Normalization layers (ref BatchNormalization.scala:31 [673 LoC],
SpatialBatchNormalization, SpatialCrossMapLRN.scala [221 LoC],
SpatialSubtractiveNormalization / SpatialDivisiveNormalization /
SpatialContrastiveNormalization).

BatchNorm running stats are the one true *state* in the module system: the
pure ``_forward`` returns updated buffers, which the eager path writes back
and the jitted trainer threads through the step function — the reference's
in-place ``runningMean/runningVar`` mutation made functional.
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.nn.module import TensorModule
from bigdl_tpu.nn import init as init_
from bigdl_tpu.tensor import policy


def _apply_in_compute_dtype(x):
    """The big (N, …) normalize apply is pure bandwidth: run it in the
    policy compute dtype when a reduced-precision policy is active
    (statistics always stay f32 — the callers compute them before this).
    Shared by BatchNormalization and LayerNorm; measured −1.6 ms/step on
    ResNet-50 (PERF_NOTES round 4)."""
    p = policy()
    return x.astype(p.compute_dtype) if p.narrows(x) else x


class BatchNormalization(TensorModule):
    """Batch norm over (N, D) input (ref BatchNormalization.scala:31).

    Constructor mirrors the reference: (nOutput, eps, momentum, affine).
    Training: batch stats + EMA update of running stats; eval: running stats.
    """

    n_dim = 2

    def __init__(self, n_output: int, eps: float = 1e-5, momentum: float = 0.1,
                 affine: bool = True):
        super().__init__()
        self.n_output = n_output
        self.eps = eps
        self.momentum = momentum
        self.affine = affine
        self.reset()

    def reset(self):
        if self.affine:
            self._add_param("weight", init_.uniform((self.n_output,), 0.0, 1.0))
            self._add_param("bias", np.zeros((self.n_output,), np.float32))
        self._add_buffer("running_mean", np.zeros((self.n_output,), np.float32))
        self._add_buffer("running_var", np.ones((self.n_output,), np.float32))
        return self

    def _stat_axes(self, x):
        return tuple(i for i in range(x.ndim) if i != 1) if x.ndim > 2 else (0,)

    def _forward(self, P, x, S, ctx):
        was_unbatched = x.ndim == self.n_dim - 1
        if was_unbatched:
            x = x[None]
        axes = self._stat_axes(x)
        bshape = [1] * x.ndim
        bshape[1 if x.ndim > 2 else -1] = self.n_output
        new_S = None
        if ctx.training:
            # statistics accumulate in f32: under the BF16_ACT policy x is
            # bfloat16 and a bf16 mean over N*H*W elements loses the tail
            x32 = x.astype(jnp.float32) if x.dtype != jnp.float32 else x
            mean = x32.mean(axis=axes)
            var = x32.var(axis=axes)
            n = x.size / self.n_output
            unbiased = var * (n / max(n - 1, 1.0))
            new_S = {
                "running_mean": (1 - self.momentum) * S["running_mean"] + self.momentum * mean,
                "running_var": (1 - self.momentum) * S["running_var"] + self.momentum * unbiased,
            }
        else:
            mean, var = S["running_mean"], S["running_var"]
        inv = lax.rsqrt(var + self.eps)
        scale, shift = inv, -mean * inv
        if self.affine:
            scale = scale * P["weight"]
            shift = shift * P["weight"] + P["bias"]
        xa = _apply_in_compute_dtype(x)
        y = (xa * scale.astype(xa.dtype).reshape(bshape)
             + shift.astype(xa.dtype).reshape(bshape))
        return ((y[0] if was_unbatched else y).astype(x.dtype)), new_S

    def __repr__(self):
        return f"{type(self).__name__}({self.n_output})"


class SpatialBatchNormalization(BatchNormalization):
    """Batch norm over (N, C, H, W) (ref SpatialBatchNormalization.scala)."""

    n_dim = 4


class LayerNorm(TensorModule):
    """Layer normalization over the trailing feature dim: (…, D) -> (…, D).

    Absent in the reference (its normalizers are batch/spatial/LRN);
    added for the attention/transformer family (``nn/attention.py``) —
    LayerNorm is per-token, so it needs NO cross-device statistics under
    data/sequence sharding, which is exactly why transformer stacks use
    it.  Statistics in f32; the (…, D) apply follows the compute-dtype
    policy like BatchNorm's."""

    def __init__(self, d_model: int, eps: float = 1e-5,
                 affine: bool = True):
        super().__init__()
        self.d_model = d_model
        self.eps = eps
        self.affine = affine
        self.reset()

    def reset(self):
        if self.affine:
            self._add_param("weight", np.ones((self.d_model,), np.float32))
            self._add_param("bias", np.zeros((self.d_model,), np.float32))
        return self

    def _forward(self, P, x, S, ctx):
        x32 = x.astype(jnp.float32) if x.dtype != jnp.float32 else x
        mean = x32.mean(axis=-1, keepdims=True)
        var = x32.var(axis=-1, keepdims=True)
        inv = lax.rsqrt(var + self.eps)
        scale, shift = inv, -mean * inv
        if self.affine:
            scale = scale * P["weight"]
            shift = shift * P["weight"] + P["bias"]
        xa = _apply_in_compute_dtype(x)
        y = xa * scale.astype(xa.dtype) + shift.astype(xa.dtype)
        return y.astype(x.dtype), None

    def __repr__(self):
        return f"LayerNorm({self.d_model})"


class SpatialCrossMapLRN(TensorModule):
    """Local response normalization across channels
    (ref SpatialCrossMapLRN.scala:221):
    y = x / (k + alpha/size * sum_{window} x^2) ** beta.

    Implemented as a window reduction over the channel dim — a single fused
    XLA op instead of the reference's per-thread sliding accumulation.
    """

    def __init__(self, size: int = 5, alpha: float = 1.0, beta: float = 0.75,
                 k: float = 1.0):
        super().__init__()
        self.size = size
        self.alpha = alpha
        self.beta = beta
        self.k = k

    # Candidate, undecided: the fused Pallas pair of PERF_NOTES round 6
    # (ops/pallas_kernels.lrn_channel; the forward stores z so the backward
    # is one pass with one adjoint window sum).  The round-3 form lost to
    # the XLA path (538 vs 808-852 us fwd+bwd on the Inception C64 56x56
    # shape).  Off until ROADMAP S5 times it on the chip and adopts or
    # deletes it; "interpret" runs the Pallas interpreter on any backend
    # (tests).
    _PALLAS = False

    def _forward(self, P, x, S, ctx):
        if self._PALLAS and x.ndim == 4:
            from bigdl_tpu.ops.pallas_kernels import (_interpreted,
                                                      lrn_channel)
            return lrn_channel(x, self.size, self.alpha, self.beta, self.k,
                               _interpreted(self._PALLAS)), None
        # LRN is pure bandwidth (window sums + eltwise): the compute-dtype
        # cast halves its bytes like every matmul/conv operand under the
        # policy.  Denominator error is bounded: z = k + (alpha/n) sum x^2
        # with k=1 dominates, and bf16 keeps ~3 significant digits of the
        # small correction term.  Measured loss drift and device win:
        # PERF_NOTES round 4.
        p = policy()
        cast = p.narrows(x)
        y = _lrn(x.astype(p.compute_dtype) if cast else x, self.size,
                 self.alpha, self.beta, self.k)
        return (y.astype(x.dtype) if cast else y), None


def _lrn_window_sum(v, size, lo, hi):
    if v.shape[0] < 8:
        # XLA:TPU (libtpu 0.0.34) rewrites convs whose batch is below 8
        # into space-to-batch form and carries the rewrite into the ops
        # that consume them; through this padded channel-window
        # reduction it builds mis-shaped HLO (forward: "Binary op with
        # incompatible shapes bf16[..,192] and bf16[..,188]") or aborts
        # the compiler (backward: space_to_batch_converter.cc "Check
        # failed").  The barrier keeps a producer conv's rewrite out of
        # the window sum; batches of 8 and more never trigger the pass
        # and keep the fused form.
        v = lax.optimization_barrier(v)
    return lax.reduce_window(
        v, 0.0, lax.add,
        window_dimensions=(1, size, 1, 1),
        window_strides=(1, 1, 1, 1),
        padding=((0, 0), (lo, hi), (0, 0), (0, 0)))


def _lrn_denom(z, beta):
    if beta == 0.75:
        # z^(3/4) = (z^(1/4))^3 via two sqrts: no exp/log transcendentals
        return jnp.sqrt(jnp.sqrt(z)) ** 3
    return z ** beta


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _lrn(x, size, alpha, beta, k):
    """LRN with the ANALYTIC backward instead of the jvp-transpose one.

    y_c = x_c z_c^{-beta} with z = k + (alpha/n) sum_win x^2 gives

        dx_d = g_d / denom_d - (2 alpha beta / n) x_d
               * sum_{c : d in win(c)} g_c y_c / z_c

    — ONE reversed-window reduce_window over g*y/z, where the
    jvp-transpose backward emits TWO window reductions plus a wider
    mul/add fusion chain (measured 1.44 ms of reduce_window + 2.1 ms of
    fusions per Inception step, PROFILE round 3/4).  Device-clock A/B in
    PERF_NOTES round 4.  Residuals: x and z only; denom/y are two-sqrt
    recomputes.

    Tried and lost as the window sum (PERF_NOTES round 2): ``size`` shifted
    slice-adds (equal alone, 2-6 ms/step slower in the Inception step) and
    a banded [C, C] matmul (XLA turns it into a 1x1 conv whose backward is
    1.6x slower in-model)."""
    return _lrn_fwd(x, size, alpha, beta, k)[0]


def _lrn_fwd(x, size, alpha, beta, k):
    lo = (size - 1) // 2
    hi = size - 1 - lo
    z = k + (alpha / size) * _lrn_window_sum(x * x, size, lo, hi)
    return x / _lrn_denom(z, beta), (x, z)


def _lrn_bwd(size, alpha, beta, k, res, g):
    x, z = res
    lo = (size - 1) // 2
    hi = size - 1 - lo
    denom = _lrn_denom(z, beta)
    # g*y/z^  — y recomputed as x/denom; z^{-beta-1} = 1/(z*denom)
    t = _lrn_window_sum(g * x / (z * denom), size, hi, lo)  # flipped window
    dx = g / denom - (2.0 * alpha * beta / size) * x * t
    return (dx,)


_lrn.defvjp(_lrn_fwd, _lrn_bwd)


def _gaussian_kernel(kernel_size: int) -> np.ndarray:
    """Normalized 2D gaussian, like image.gaussian in Torch."""
    sigma = 0.25 * kernel_size  # torch default sigma=0.25 relative to size
    xs = np.arange(kernel_size, dtype=np.float64)
    c = (kernel_size - 1) / 2.0
    g = np.exp(-((xs - c) ** 2) / (2 * sigma ** 2))
    k2 = np.outer(g, g)
    return (k2 / k2.sum()).astype(np.float32)


class SpatialSubtractiveNormalization(TensorModule):
    """Subtract a kernel-weighted local mean
    (ref SpatialSubtractiveNormalization.scala)."""

    def __init__(self, n_input_plane: int = 1, kernel=None):
        super().__init__()
        self.n_input_plane = n_input_plane
        if kernel is None:
            kernel = _gaussian_kernel(9)
        kernel = np.asarray(kernel, np.float32)
        if kernel.ndim == 1:
            kernel = np.outer(kernel, kernel)
        self.kernel = kernel / (kernel.sum() * n_input_plane)
        self.kh, self.kw = self.kernel.shape

    def _conv_sum(self, x):
        """Zero-padded cross-channel correlation with the normalized kernel:
        the reference's ``meanestimator`` conv stage
        (SpatialZeroPadding + SpatialConvolution(C,1) + Replicate,
        SpatialSubtractiveNormalization.scala:69-78) — one map shared by
        all channels, returned broadcastable as (N,1,H,W)."""
        n, c, h, w = x.shape
        k = jnp.asarray(self.kernel)[None, None].repeat(c, axis=1)  # (1,C,kh,kw)
        ph, pw = (self.kh - 1) // 2, (self.kw - 1) // 2
        pad = [(ph, self.kh - 1 - ph), (pw, self.kw - 1 - pw)]
        return lax.conv_general_dilated(
            x, k, (1, 1), pad, dimension_numbers=("NCHW", "OIHW", "NCHW"))

    def _coef(self, x):
        """Border-mass map: the conv applied to ones
        (the reference's ``coef``, SpatialSubtractiveNormalization.scala:112-121)."""
        ones = jnp.ones((1,) + x.shape[1:], x.dtype)
        return self._conv_sum(ones)

    def _local_mean(self, x):
        return self._conv_sum(x) / self._coef(x)

    def _forward(self, P, x, S, ctx):
        was3d = x.ndim == 3
        if was3d:
            x = x[None]
        y = x - self._local_mean(x)
        return (y[0] if was3d else y), None


class SpatialDivisiveNormalization(TensorModule):
    """Divide by the coef-adjusted local std-dev estimate, floored by
    Threshold(threshold, thresval)
    (ref SpatialDivisiveNormalization.scala:114-136:
    ``localstds = sqrt(conv(x^2))``, ``adjustedstds = localstds / coef``,
    ``out = x / Threshold(adjustedstds)``; the division by the border
    mass happens AFTER the sqrt, and there is no mean-std clause)."""

    def __init__(self, n_input_plane: int = 1, kernel=None,
                 threshold: float = 1e-4, thresval: float = 1e-4):
        super().__init__()
        self.sub = SpatialSubtractiveNormalization(n_input_plane, kernel)
        self.threshold = threshold
        self.thresval = thresval

    def _forward(self, P, x, S, ctx):
        was3d = x.ndim == 3
        if was3d:
            x = x[None]
        local_std = jnp.sqrt(jnp.maximum(self.sub._conv_sum(x * x), 0.0))
        adjusted = local_std / self.sub._coef(x)
        denom = jnp.where(adjusted > self.threshold, adjusted, self.thresval)
        y = x / denom
        return (y[0] if was3d else y), None


class SpatialContrastiveNormalization(TensorModule):
    """Subtractive then divisive normalization
    (ref SpatialContrastiveNormalization.scala)."""

    def __init__(self, n_input_plane: int = 1, kernel=None,
                 threshold: float = 1e-4, thresval: float = 1e-4):
        super().__init__()
        self.sub = SpatialSubtractiveNormalization(n_input_plane, kernel)
        self.div = SpatialDivisiveNormalization(n_input_plane, kernel,
                                                threshold, thresval)

    def _forward(self, P, x, S, ctx):
        y, _ = self.sub._forward(P, x, S, ctx)
        return self.div._forward(P, y, S, ctx)


class RMSNorm(TensorModule):
    """x / sqrt(mean(x^2) + eps) * weight over the trailing dim, in
    float32: (…, D) -> (…, D).  Per token, like ``LayerNorm``, without the
    mean and the shift."""

    def __init__(self, d_model: int, eps: float = 1e-5):
        super().__init__()
        self.d_model = d_model
        self.eps = eps
        self.reset()

    def reset(self):
        self._add_param("weight", np.ones((self.d_model,), np.float32))
        return self

    def _forward(self, P, x, S, ctx):
        return rms_norm(x, P["weight"], self.eps), None

    def __repr__(self):
        return f"RMSNorm({self.d_model})"


def rms_norm(x, weight, eps):
    x32 = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return x32 * inv * weight

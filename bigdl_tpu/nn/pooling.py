"""Pooling layers (ref SpatialMaxPooling.scala:279, SpatialAveragePooling.scala:458,
RoiPooling.scala:363).

The reference hand-writes strided window loops (NNPrimitive.scala maxpool
:357-499); here ``lax.reduce_window`` compiles to fused TPU window
reductions.  Ceil-mode output sizing matches Torch semantics: the last
window may start in the padded region but must begin before the end of the
real input + left padding.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.nn.module import TensorModule, Module
from bigdl_tpu.tensor import policy


# Candidate, undecided: the Mosaic kernel pair of PERF_NOTES round 6
# (ops/pallas_kernels.mosaic_maxpool2d: argmax-storing forward, scatter-free
# gather backward in place of select_and_scatter).  Off until ROADMAP S5
# times it on the chip and adopts or deletes it; "interpret" runs the Pallas
# interpreter on any backend (tests).
_PALLAS_POOL = False


def _max_pool2d(x, window, strides, padding):
    """Max pooling over NCHW: narrow, then reshape + max where the windows
    tile the input, else ``lax.reduce_window``.

    Under a reduced-precision policy a float32 input is pooled in the
    compute dtype and cast back (max of bf16 values = bf16 of the f32 max,
    so only rounding-level tie routing can differ): the window ops are pure
    bandwidth, and half the bytes took the Inception step from 33.62 to
    28.60 ms (PERF_NOTES round 4, E4).

    The windowed backward is XLA's select-and-scatter.  Tried and lost
    against it on the v5e: a gather-stencil VJP with tie-splitting (1.1-4x
    slower on every Inception pool shape, PERF_NOTES round 2), two 1-D
    passes (+3% on the Inception step) and an NHWC transpose (+1.2%; both
    PERF_NOTES round 5).
    """
    kh, kw = window
    dh, dw = strides
    p = policy()
    cast = p.narrows(x)
    xin = x.astype(p.compute_dtype) if cast else x
    n, c, h, w = xin.shape
    if _PALLAS_POOL:
        from bigdl_tpu.ops.pallas_kernels import mosaic_maxpool2d, _on_tpu
        interp = _PALLAS_POOL == "interpret"
        if interp or _on_tpu():
            y = mosaic_maxpool2d(xin, window, strides, padding, interp)
            return y.astype(x.dtype) if cast else y
    if ((kh, kw) == (dh, dw) and padding == ((0, 0), (0, 0))
            and h % kh == 0 and w % kw == 0):
        # Exact non-overlapping pool: windows tile the input, so the
        # reduce is a plain reshape+max — no window machinery forward,
        # and the backward is an eq-select instead of select_and_scatter.
        # Tie semantics: jnp.max's VJP SPLITS the cotangent EVENLY among
        # tied maxima (measured: an all-equal 2x2 window grads 0.25
        # each), where select_and_scatter routes the full value to one
        # element — an equally valid subgradient with the same
        # per-window mass; documented in porting guide #6.
        y = xin.reshape(n, c, h // kh, kh, w // kw, kw).max(axis=(3, 5))
    else:
        y = lax.reduce_window(
            xin, np.array(-np.inf, xin.dtype), lax.max,
            window_dimensions=(1, 1, kh, kw),
            window_strides=(1, 1, dh, dw),
            padding=((0, 0), (0, 0)) + padding)
    return y.astype(x.dtype) if cast else y


def _pool_out_size(in_size, k, stride, pad, ceil_mode):
    if ceil_mode:
        out = int(np.ceil(float(in_size - k + 2 * pad) / stride)) + 1
    else:
        out = int(np.floor(float(in_size - k + 2 * pad) / stride)) + 1
    if pad > 0 and (out - 1) * stride >= in_size + pad:
        out -= 1  # last window must start inside input+left-pad (Torch rule)
    return out


def _pad_amounts(in_size, k, stride, pad, out):
    """(lo, hi) padding so reduce_window emits exactly ``out`` windows."""
    needed = (out - 1) * stride + k
    hi = max(needed - in_size - pad, 0)
    return pad, hi


class SpatialMaxPooling(TensorModule):
    def __init__(self, kw: int, kh: int, dw: int = None, dh: int = None,
                 pad_w: int = 0, pad_h: int = 0):
        super().__init__()
        self.kw, self.kh = kw, kh
        self.dw = dw if dw is not None else kw
        self.dh = dh if dh is not None else kh
        self.pad_w, self.pad_h = pad_w, pad_h
        self.ceil_mode = False

    def ceil(self):
        self.ceil_mode = True
        return self

    def floor(self):
        self.ceil_mode = False
        return self

    def _forward(self, P, x, S, ctx):
        was3d = x.ndim == 3
        if was3d:
            x = x[None]
        n, c, h, w = x.shape
        oh = _pool_out_size(h, self.kh, self.dh, self.pad_h, self.ceil_mode)
        ow = _pool_out_size(w, self.kw, self.dw, self.pad_w, self.ceil_mode)
        ph = _pad_amounts(h, self.kh, self.dh, self.pad_h, oh)
        pw = _pad_amounts(w, self.kw, self.dw, self.pad_w, ow)
        y = _max_pool2d(x, (self.kh, self.kw), (self.dh, self.dw), (ph, pw))
        return (y[0] if was3d else y), None

    def __repr__(self):
        return f"SpatialMaxPooling({self.kw}x{self.kh}, {self.dw},{self.dh})"


class SpatialAveragePooling(TensorModule):
    def __init__(self, kw: int, kh: int, dw: int = 1, dh: int = 1,
                 pad_w: int = 0, pad_h: int = 0, ceil_mode: bool = False,
                 count_include_pad: bool = True, divide: bool = True):
        super().__init__()
        self.kw, self.kh = kw, kh
        self.dw, self.dh = dw, dh
        self.pad_w, self.pad_h = pad_w, pad_h
        self.ceil_mode = ceil_mode
        self.count_include_pad = count_include_pad
        self.divide = divide

    def ceil(self):
        self.ceil_mode = True
        return self

    def _forward(self, P, x, S, ctx):
        was3d = x.ndim == 3
        if was3d:
            x = x[None]
        n, c, h, w = x.shape
        oh = _pool_out_size(h, self.kh, self.dh, self.pad_h, self.ceil_mode)
        ow = _pool_out_size(w, self.kw, self.dw, self.pad_w, self.ceil_mode)
        ph = _pad_amounts(h, self.kh, self.dh, self.pad_h, oh)
        pw = _pad_amounts(w, self.kw, self.dw, self.pad_w, ow)

        def wsum(v):
            return lax.reduce_window(
                v, 0.0, lax.add,
                window_dimensions=(1, 1, self.kh, self.kw),
                window_strides=(1, 1, self.dh, self.dw),
                padding=((0, 0), (0, 0), ph, pw))

        y = wsum(x)
        if self.divide:
            if self.count_include_pad:
                y = y / float(self.kh * self.kw)
            else:
                ones = jnp.ones((1, 1, h, w), x.dtype)
                y = y / wsum(ones)
        return (y[0] if was3d else y), None

    def __repr__(self):
        return f"SpatialAveragePooling({self.kw}x{self.kh}, {self.dw},{self.dh})"


class RoiPooling(Module):
    """Region-of-interest max pooling (ref RoiPooling.scala:363).

    Input: Table(features (N,C,H,W), rois (R,5) rows [batchIdx(0-based,
    like the reference: RoiPooling.scala ``roiBatchInd >= 0 &&
    dataSize(0) > roiBatchInd``), x1, y1, x2, y2] in input-image coords
    scaled by ``spatial_scale``).  Output: (R, C, pooled_h, pooled_w).
    Coordinate rounding is the reference's ``Math.round`` = floor(x+0.5)
    (round-half-up, not banker's rounding).

    TPU-first note: the reference loops over variable-sized bins; here each
    ROI bin is computed by masked max over the full feature map, keeping
    shapes static for XLA (R is the only batch-like dim).
    """

    def __init__(self, pooled_w: int, pooled_h: int, spatial_scale: float):
        super().__init__()
        self.pooled_w = pooled_w
        self.pooled_h = pooled_h
        self.spatial_scale = spatial_scale

    def _forward(self, P, x, S, ctx):
        data, rois = x[1], x[2]
        n, c, h, w = data.shape
        r = rois.shape[0]
        batch_idx = jnp.asarray(rois[:, 0], jnp.int32)
        x1 = jnp.floor(rois[:, 1] * self.spatial_scale + 0.5)
        y1 = jnp.floor(rois[:, 2] * self.spatial_scale + 0.5)
        x2 = jnp.floor(rois[:, 3] * self.spatial_scale + 0.5)
        y2 = jnp.floor(rois[:, 4] * self.spatial_scale + 0.5)
        roi_w = jnp.maximum(x2 - x1 + 1.0, 1.0)
        roi_h = jnp.maximum(y2 - y1 + 1.0, 1.0)
        bin_w = roi_w / self.pooled_w
        bin_h = roi_h / self.pooled_h

        ys = jnp.arange(h, dtype=jnp.float32)
        xs = jnp.arange(w, dtype=jnp.float32)
        ph = jnp.arange(self.pooled_h, dtype=jnp.float32)
        pw = jnp.arange(self.pooled_w, dtype=jnp.float32)

        # bin bounds: (R, PH) and (R, PW)
        h_start = jnp.clip(jnp.floor(ph[None] * bin_h[:, None] + y1[:, None]), 0, h)
        h_end = jnp.clip(jnp.ceil((ph[None] + 1) * bin_h[:, None] + y1[:, None]), 0, h)
        w_start = jnp.clip(jnp.floor(pw[None] * bin_w[:, None] + x1[:, None]), 0, w)
        w_end = jnp.clip(jnp.ceil((pw[None] + 1) * bin_w[:, None] + x1[:, None]), 0, w)

        hmask = (ys[None, None] >= h_start[..., None]) & (ys[None, None] < h_end[..., None])  # (R,PH,H)
        wmask = (xs[None, None] >= w_start[..., None]) & (xs[None, None] < w_end[..., None])  # (R,PW,W)
        feats = data[batch_idx]  # (R,C,H,W)
        masked = (feats[:, None, None] +
                  jnp.where(hmask[:, :, None, None, :, None] & wmask[:, None, :, None, None, :],
                            0.0, -jnp.inf))  # (R,PH,PW,C,H,W)
        out = masked.max(axis=(-1, -2))  # (R,PH,PW,C)
        out = jnp.where(jnp.isfinite(out), out, 0.0)
        return jnp.transpose(out, (0, 3, 1, 2)), None

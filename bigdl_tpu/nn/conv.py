"""Spatial convolution family (SURVEY.md §2.3 "Convolution/spatial family").

The reference lowers conv to im2col + gemm with hand-threading
(SpatialConvolution.scala:31, NNPrimitive.scala im2col :25-355).  On TPU,
``lax.conv_general_dilated`` compiles directly onto the MXU — im2col,
threading and the shared-buffer trick (SpatialShareConvolution.scala) are
all compiler concerns, so this file is ~10x smaller than its reference
counterpart while covering the same layers.

Layout: NCHW activations / OIHW weights, matching the reference's Torch
semantics.  XLA re-layouts internally for the MXU.
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
from bigdl_tpu.utils.random import RNG

_DN = ("NCHW", "OIHW", "NCHW")


def _conv(x, w, stride, padding, *, lhs_dilation=None, rhs_dilation=None, groups=1):
    # Both operands cast to the compute dtype (bf16 feeds the MXU at full
    # rate; accumulation is f32 inside the MXU regardless), output cast back.
    # No preferred_element_type: its VJP would pair an f32 cotangent with
    # bf16 operands, which conv_general_dilated rejects — and a custom-VJP
    # formulation with pet=f32 in all three convs, despite a 1.7x win on an
    # isolated chained-conv microbench, measured 4-12% SLOWER end-to-end on
    # Inception-v1/VGG-16 training steps (PERF_NOTES.md), so it was removed.
    # A stride-1 1x1 conv as a channel GEMM (lax.dot_general) went the same
    # way: 1.7-2.1x faster alone, Inception 26.30 -> 27.92 ms/step in the
    # model, where it breaks the conv/ReLU/concat fusions (PERF_NOTES round 5).
    p = policy()
    y = lax.conv_general_dilated(
        p.cast_compute(x), p.cast_compute(w),
        window_strides=stride, padding=padding,
        lhs_dilation=lhs_dilation, rhs_dilation=rhs_dilation,
        dimension_numbers=_DN, feature_group_count=groups)
    return y.astype(p.output_dtype)


def _maybe_batch(x):
    """Accept 3D (C,H,W) like the reference; return (x4d, was_3d)."""
    if x.ndim == 3:
        return x[None], True
    return x, False


def bias_add(y, b):
    """Conv bias add (NCHW).  Its autodiff db shares one read of the
    cotangent with the conv's dx fusion; a custom VJP that split db into
    its own kernel took the Inception step from 33.84 to 37.64 ms
    (PERF_NOTES round 4, E1)."""
    return y + b[None, :, None, None]


def _s2d_parts(x, w, s, pad):
    """The space-to-depth operands: (xs, ws, out crop) — see
    _space_to_depth_conv."""
    o, c, kh, kw = w.shape
    b, _, h, wd = x.shape
    (plh, phh), (plw, phw) = pad
    khp = -(-kh // s)   # ceil(k/s) taps after the rewrite
    kwp = -(-kw // s)
    # pad the image to the conv's own padding, then up to a multiple of s
    hp = h + plh + phh
    wp = wd + plw + phw
    eh = (-hp) % s
    ew = (-wp) % s
    xp = jnp.pad(x, ((0, 0), (0, 0), (plh, phh + eh), (plw, phw + ew)))
    m, n = (hp + eh) // s, (wp + ew) // s
    xs = xp.reshape(b, c, m, s, n, s).transpose(0, 1, 3, 5, 2, 4)
    xs = xs.reshape(b, c * s * s, m, n)
    # weight phases: w'[o, (c, rh, rw), u, v] = w[o, c, s*u+rh, s*v+rw]
    wpad = jnp.pad(w, ((0, 0), (0, 0), (0, s * khp - kh), (0, s * kwp - kw)))
    ws = wpad.reshape(o, c, khp, s, kwp, s).transpose(0, 1, 3, 5, 2, 4)
    ws = ws.reshape(o, c * s * s, khp, kwp)
    oh = (hp - kh) // s + 1
    ow = (wp - kw) // s + 1
    return xs, ws, oh, ow


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _space_to_depth_conv(x, w, s, pad):
    """Strided low-channel conv rewritten as space-to-depth + stride-1 conv.

    A k x k stride-s conv over C channels equals a ceil(k/s)^2 stride-1
    conv over C*s*s space-to-depth channels.  For stem convs (C=3, s=2 or
    4) this multiplies the MXU contraction depth by s^2: the 7x7/s2
    Inception-v1 stem measured 33 TF/s as-is (3 input channels fill 3/128
    MXU rows) and proportionally better after this rewrite.  Exact same
    arithmetic, reassociated.

    out(i,j) = sum_t w[t] xpad[s*i + t]  becomes, with t = s*u + r,
    sum_r sum_u w[s*u + r] X_r[i + u]  where X_r is the r-th phase of the
    space-to-depth transform.

    The custom VJP keeps the BACKWARD convs in the s2d geometry too:
    plain autodiff emits the right s2d-shaped grad convs, but XLA's
    layout/canonicalization pass folds the phase transforms back in and
    rewrites the weight grad to the original low-channel form
    (out[64,3,7,7] over raw 224x224 input: 0.907 ms at 18%% of roofline,
    PROFILE round 4) — ``optimization_barrier`` on the cotangent side
    pins the s2d form the same way the round-2 maxpool lesson pinned
    residuals."""
    xs, ws, oh, ow = _s2d_parts(x, w, s, pad)
    y = _conv(xs, ws, (1, 1), [(0, 0), (0, 0)])
    return y[:, :, :oh, :ow]


def _s2d_conv_fwd(x, w, s, pad):
    return _space_to_depth_conv(x, w, s, pad), (x, w)


def _s2d_conv_bwd(s, pad, res, g):
    x, w = res

    def inner(x_, w_):
        xs, ws, oh, ow = _s2d_parts(x_, w_, s, pad)
        # barrier the s2d operands: without it XLA folds the phase
        # transforms into the grad convs and canonicalizes them back to
        # the slow low-channel geometry (A/B: PERF_NOTES r4)
        xs = lax.optimization_barrier(xs)
        ws = lax.optimization_barrier(ws)
        y = _conv(xs, ws, (1, 1), [(0, 0), (0, 0)])
        return y[:, :, :oh, :ow]

    _, vjp = jax.vjp(inner, x, w)
    dx, dw = vjp(g)
    return dx, dw


_space_to_depth_conv.defvjp(_s2d_conv_fwd, _s2d_conv_bwd)


class SpatialConvolution(TensorModule):
    """2D convolution (ref SpatialConvolution.scala:31).

    Args mirror the reference constructor: (nInputPlane, nOutputPlane,
    kernelW, kernelH, strideW, strideH, padW, padH, nGroup, propagateBack,
    initMethod).
    """

    #: quantized-serving declaration (bigdl_tpu/quant/weights.py):
    #: weight is (O, C/group, kh, kw) — per-output-plane scales
    quant_spec = {"weight": (0, 1)}

    def __init__(self, n_input_plane: int, n_output_plane: int,
                 kernel_w: int, kernel_h: int, stride_w: int = 1, stride_h: int = 1,
                 pad_w: int = 0, pad_h: int = 0, n_group: int = 1,
                 propagate_back: bool = True, init_method: str = init_.Default,
                 with_bias: bool = True):
        super().__init__()
        assert n_input_plane % n_group == 0 and n_output_plane % n_group == 0
        self.n_input_plane = n_input_plane
        self.n_output_plane = n_output_plane
        self.kernel_w, self.kernel_h = kernel_w, kernel_h
        self.stride_w, self.stride_h = stride_w, stride_h
        self.pad_w, self.pad_h = pad_w, pad_h
        self.n_group = n_group
        self.propagate_back = propagate_back
        self.init_method = init_method
        self.with_bias = with_bias
        self.reset()

    def reset(self):
        shape = (self.n_output_plane, self.n_input_plane // self.n_group,
                 self.kernel_h, self.kernel_w)
        fan_in = (self.n_input_plane // self.n_group) * self.kernel_h * self.kernel_w
        fan_out = (self.n_output_plane // self.n_group) * self.kernel_h * self.kernel_w
        if self.init_method == init_.Xavier:
            w = init_.xavier(shape, fan_in, fan_out)
            b = np.zeros((self.n_output_plane,), np.float32)
        elif self.init_method == init_.MSRA:
            n = self.kernel_w * self.kernel_h * self.n_output_plane
            w = init_.msra(shape, n)
            b = np.zeros((self.n_output_plane,), np.float32)
        else:
            stdv = 1.0 / np.sqrt(self.kernel_w * self.kernel_h * self.n_input_plane)
            w = init_.uniform(shape, -stdv, stdv)
            b = init_.uniform((self.n_output_plane,), -stdv, stdv)
        self._add_param("weight", w)
        if self.with_bias:
            self._add_param("bias", b)
        return self

    def _forward(self, P, x, S, ctx):
        x, was3d = _maybe_batch(x)
        s = self.stride_h
        if (s == self.stride_w and s > 1 and self.n_group == 1
                and self.n_input_plane * s * s <= 64
                and self.kernel_h > s and self.kernel_w > s):
            # stem convs (few input channels, strided): space-to-depth
            # rewrite fills the MXU contraction dim s^2 times better
            y = _space_to_depth_conv(
                x, P["weight"], s,
                ((self.pad_h, self.pad_h), (self.pad_w, self.pad_w)))
        else:
            y = _conv(x, P["weight"], (self.stride_h, self.stride_w),
                      [(self.pad_h, self.pad_h), (self.pad_w, self.pad_w)],
                      groups=self.n_group)
        if self.with_bias:
            y = bias_add(y, P["bias"])
        return (y[0] if was3d else y), None

    def __repr__(self):
        return (f"SpatialConvolution({self.n_input_plane} -> {self.n_output_plane}, "
                f"{self.kernel_w}x{self.kernel_h}, {self.stride_w},{self.stride_h}, "
                f"{self.pad_w},{self.pad_h})")


class SpatialShareConvolution(SpatialConvolution):
    """API-parity alias (ref SpatialShareConvolution.scala shares im2col
    buffers across layers to cut JVM memory; XLA's buffer assignment does
    this automatically, so the layer is computationally identical here)."""


class SpatialDilatedConvolution(TensorModule):
    """Atrous convolution (ref SpatialDilatedConvolution.scala, 561 LoC)."""

    #: weight is (O, C, kh, kw) — see SpatialConvolution.quant_spec
    quant_spec = {"weight": (0, 1)}

    def __init__(self, n_input_plane: int, n_output_plane: int,
                 kw: int, kh: int, dw: int = 1, dh: int = 1,
                 pad_w: int = 0, pad_h: int = 0,
                 dilation_w: int = 1, dilation_h: int = 1,
                 init_method: str = init_.Default):
        super().__init__()
        self.n_input_plane = n_input_plane
        self.n_output_plane = n_output_plane
        self.kw, self.kh = kw, kh
        self.dw, self.dh = dw, dh
        self.pad_w, self.pad_h = pad_w, pad_h
        self.dilation_w, self.dilation_h = dilation_w, dilation_h
        self.init_method = init_method
        self.reset()

    def reset(self):
        shape = (self.n_output_plane, self.n_input_plane, self.kh, self.kw)
        fan_in = self.n_input_plane * self.kh * self.kw
        if self.init_method == init_.Xavier:
            w = init_.xavier(shape, fan_in, self.n_output_plane * self.kh * self.kw)
            b = np.zeros((self.n_output_plane,), np.float32)
        else:
            stdv = 1.0 / np.sqrt(fan_in)
            w = init_.uniform(shape, -stdv, stdv)
            b = init_.uniform((self.n_output_plane,), -stdv, stdv)
        self._add_param("weight", w)
        self._add_param("bias", b)
        return self

    def _forward(self, P, x, S, ctx):
        x, was3d = _maybe_batch(x)
        y = _conv(x, P["weight"], (self.dh, self.dw),
                  [(self.pad_h, self.pad_h), (self.pad_w, self.pad_w)],
                  rhs_dilation=(self.dilation_h, self.dilation_w))
        y = y + P["bias"][None, :, None, None]
        return (y[0] if was3d else y), None


class SpatialFullConvolution(TensorModule):
    """Transposed convolution / deconvolution
    (ref SpatialFullConvolution.scala, 791 LoC).

    out = (in - 1) * stride - 2 * pad + kernel + adj.
    Implemented as input-dilated conv with a spatially-flipped,
    channel-swapped kernel — the XLA-native formulation of conv-transpose.
    Weight stored Torch-style: (nInputPlane, nOutputPlane // nGroup, kH, kW).
    """

    def __init__(self, n_input_plane: int, n_output_plane: int,
                 kw: int, kh: int, dw: int = 1, dh: int = 1,
                 pad_w: int = 0, pad_h: int = 0, adj_w: int = 0, adj_h: int = 0,
                 n_group: int = 1, no_bias: bool = False,
                 init_method: str = init_.Default):
        super().__init__()
        assert adj_w < dw and adj_h < dh, "adjW/adjH must be smaller than strides"
        self.n_input_plane = n_input_plane
        self.n_output_plane = n_output_plane
        self.kw, self.kh = kw, kh
        self.dw, self.dh = dw, dh
        self.pad_w, self.pad_h = pad_w, pad_h
        self.adj_w, self.adj_h = adj_w, adj_h
        self.n_group = n_group
        self.no_bias = no_bias
        self.init_method = init_method
        self.reset()

    def reset(self):
        shape = (self.n_input_plane, self.n_output_plane // self.n_group,
                 self.kh, self.kw)
        if self.init_method == init_.BilinearFiller:
            w = init_.bilinear_filler(shape)
            b = np.zeros((self.n_output_plane,), np.float32)
        else:
            fan_in = (self.n_input_plane // self.n_group) * self.kh * self.kw
            stdv = 1.0 / np.sqrt(fan_in)
            w = init_.uniform(shape, -stdv, stdv)
            b = init_.uniform((self.n_output_plane,), -stdv, stdv)
        self._add_param("weight", w)
        if not self.no_bias:
            self._add_param("bias", b)
        return self

    def _forward(self, P, x, S, ctx):
        x, was3d = _maybe_batch(x)
        w = P["weight"]  # (I, O/g, kh, kw)
        pad_h0 = self.kh - 1 - self.pad_h
        pad_w0 = self.kw - 1 - self.pad_w
        padding = [(pad_h0, pad_h0 + self.adj_h), (pad_w0, pad_w0 + self.adj_w)]
        g = self.n_group
        ys = []
        cin_g = self.n_input_plane // g
        for gi in range(g):  # static tiny loop; XLA fuses
            wg = w[gi * cin_g:(gi + 1) * cin_g]          # (I/g, O/g, kh, kw)
            wg = jnp.flip(wg, axis=(-1, -2)).swapaxes(0, 1)  # (O/g, I/g, kh, kw)
            xg = x[:, gi * cin_g:(gi + 1) * cin_g]
            ys.append(_conv(xg, wg, (1, 1), padding, lhs_dilation=(self.dh, self.dw)))
        y = jnp.concatenate(ys, axis=1) if g > 1 else ys[0]
        if not self.no_bias:
            y = y + P["bias"][None, :, None, None]
        return (y[0] if was3d else y), None


class SpatialConvolutionMap(TensorModule):
    """Convolution over an explicit input->output connection table
    (ref SpatialConvolutionMap.scala, 361 LoC; Torch conn tables).

    TPU-first formulation: a dense conv with a constant 0/1 connectivity
    mask on the kernel — sparse gather loops would defeat the MXU, and for
    the table sizes involved the masked dense conv is faster.
    ``conn_table`` is an (n, 2) array of 1-based (fromPlane, toPlane) pairs.
    """

    def __init__(self, conn_table, kw: int, kh: int, dw: int = 1, dh: int = 1,
                 pad_w: int = 0, pad_h: int = 0):
        super().__init__()
        conn = np.asarray(conn_table, np.int64).reshape(-1, 2)
        self.conn_table = conn
        self.n_input_plane = int(conn[:, 0].max())
        self.n_output_plane = int(conn[:, 1].max())
        self.kw, self.kh = kw, kh
        self.dw, self.dh = dw, dh
        self.pad_w, self.pad_h = pad_w, pad_h
        mask = np.zeros((self.n_output_plane, self.n_input_plane, 1, 1), np.float32)
        for f, t in conn:
            mask[t - 1, f - 1, 0, 0] = 1.0
        self._mask = mask
        self.reset()

    def reset(self):
        # Torch: per-output fan-in = (#connections into it) * kW * kH
        fan_in = np.maximum(self._mask.sum(axis=(1, 2, 3)), 1.0) * self.kw * self.kh
        stdv = 1.0 / np.sqrt(fan_in)  # (O,)
        w = (RNG.uniform(-1, 1, (self.n_output_plane, self.n_input_plane,
                                 self.kh, self.kw)) * stdv[:, None, None, None])
        b = RNG.uniform(-1, 1, (self.n_output_plane,)) * stdv
        self._add_param("weight", (w * self._mask).astype(np.float32))
        self._add_param("bias", b.astype(np.float32))
        return self

    @staticmethod
    def full(n_in: int, n_out: int):
        """fullConnection table."""
        return np.array([(i + 1, o + 1) for o in range(n_out) for i in range(n_in)])

    @staticmethod
    def one_to_one(n: int):
        return np.array([(i + 1, i + 1) for i in range(n)])

    @staticmethod
    def random(n_in: int, n_out: int, n_to: int):
        pairs = []
        for o in range(n_out):
            ins = RNG.np_rng().choice(n_in, size=n_to, replace=False)
            pairs += [(int(i) + 1, o + 1) for i in ins]
        return np.array(pairs)

    def _forward(self, P, x, S, ctx):
        x, was3d = _maybe_batch(x)
        w = P["weight"] * jnp.asarray(self._mask)
        y = _conv(x, w, (self.dh, self.dw),
                  [(self.pad_h, self.pad_h), (self.pad_w, self.pad_w)])
        y = y + P["bias"][None, :, None, None]
        return (y[0] if was3d else y), None

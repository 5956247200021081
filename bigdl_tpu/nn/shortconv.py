"""Gated short convolution: the operator that stands where attention would
in most layers of the lfm2 family.  No reference counterpart (its
convolutions are spatial, ``nn/conv.py``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bigdl_tpu.nn import init as init_
from bigdl_tpu.nn.linear import dot32
from bigdl_tpu.nn.module import TensorModule


def gated_short_conv(bcx, taps):
    """The element work between the two projections.  bcx: (B, T, 3D) =
    [B | C | x~]; taps: (D, L), one weight a channel and tap.

        u = B * x~;   c_t = sum_j taps[:, j] * u_{t-(L-1)+j};   y = C * c

    causal (zeros before the sequence's start), no bias, no activation,
    float32.  The taps are L shifted multiply-adds over T, element work
    the compiler can fuse with the gates: no convolution instruction."""
    t = bcx.shape[1]
    gate_in, gate_out, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    u = gate_in * x
    n_taps = taps.shape[1]
    c = taps[:, n_taps - 1] * u
    for back in range(1, n_taps):       # u_{t-back}, zeros before t = 0
        shifted = jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :t]
        c = c + taps[:, n_taps - 1 - back] * shifted
    return gate_out * c


class ShortConv(TensorModule):
    """(B, T, D) -> (B, T, D): ``[B | C | x~] = x W_in``; a depthwise causal
    convolution of ``kernel`` taps over ``B * x~``; gated by ``C``; ``W_out``.
    Bias-free.  A token sees itself and the ``kernel - 1`` before it: the
    state a decoder would carry is those last gated inputs, not a cache
    that grows.

    Params: ``w_in`` (D, 3D), ``conv`` (D, kernel), ``w_out`` (D, D).  The
    projections run in the policy's compute dtype with a float32 result
    (``dot32``), everything between them in float32 under the scope
    ``ShortConvCore``; under ``nn.Recompute`` the (B, T, 3D) projection is
    made again in the backward pass, not kept."""

    quant_spec = {"w_in": (1, 0), "w_out": (1, 0)}

    def __init__(self, d_model: int, kernel: int = 3):
        super().__init__()
        self.d_model = d_model
        self.kernel = kernel
        self.reset()

    def reset(self):
        d = self.d_model
        for name, shape in (("w_in", (d, 3 * d)), ("conv", (d, self.kernel)),
                            ("w_out", (d, d))):
            self._add_param(name, init_.normal_on_device(shape))
        return self

    def _forward(self, P, x, S, ctx):
        bcx = dot32(x, P["w_in"])
        with jax.named_scope("ShortConvCore"):
            y = gated_short_conv(bcx, P["conv"])
        return dot32(y, P["w_out"]), None

    def __repr__(self):
        return f"ShortConv({self.d_model}, kernel={self.kernel})"

"""Weight initialization methods (ref nn/InitializationMethod.scala:23).

The reference offers Default (per-layer Torch-style fan scaling), Xavier and
BilinearFiller; each layer's ``reset()`` draws from the global RNG so model
construction is reproducible under ``set_seed``.
"""
from __future__ import annotations

import numpy as np

from bigdl_tpu.utils.random import RNG


class InitializationMethod:
    DEFAULT = "default"
    XAVIER = "xavier"
    BILINEAR_FILLER = "bilinearfiller"
    MSRA = "msra"  # He init, used by the reference's ResNet (models/resnet/ResNet.scala:102)


Default = InitializationMethod.DEFAULT
Xavier = InitializationMethod.XAVIER
BilinearFiller = InitializationMethod.BILINEAR_FILLER
MSRA = InitializationMethod.MSRA


def uniform(shape, a, b):
    return RNG.uniform(a, b, size=shape).astype(np.float32)


def normal(shape, mean, stdv):
    return RNG.normal(mean, stdv, size=shape).astype(np.float32)


#: the language-model layers' initialiser: N(0, 0.02), what decoder
#: families that publish an ``initializer_range`` mostly give
LM_INIT_STD = 0.02


def normal_on_device(shape, stdv=LM_INIT_STD):
    """N(0, stdv) drawn on the device from the global key stream: a layer of
    hundreds of millions of weights is not drawn on the host and copied."""
    import jax
    return jax.random.normal(RNG.next_key(), shape, "float32") * stdv


def default_linear(shape, fan_in):
    """Torch nn.Linear default: U(-1/sqrt(fanIn), 1/sqrt(fanIn))."""
    stdv = 1.0 / np.sqrt(fan_in)
    return uniform(shape, -stdv, stdv)


def xavier(shape, fan_in, fan_out):
    stdv = np.sqrt(6.0 / (fan_in + fan_out))
    return uniform(shape, -stdv, stdv)


def msra(shape, fan_out_spatial):
    """He/MSRA: N(0, sqrt(2/n)) (ref ResNet.modelInit ResNet.scala:102-132)."""
    return normal(shape, 0.0, np.sqrt(2.0 / fan_out_spatial))


def bilinear_filler(shape):
    """Bilinear upsampling kernel for deconvolution
    (ref InitializationMethod BilinearFiller, used by SpatialFullConvolution)."""
    assert len(shape) == 4, "bilinear filler expects (out, in, kh, kw)"
    kh, kw = shape[2], shape[3]
    f_h, f_w = np.ceil(kh / 2.0), np.ceil(kw / 2.0)
    c_h, c_w = (2 * f_h - 1 - f_h % 2) / (2.0 * f_h), (2 * f_w - 1 - f_w % 2) / (2.0 * f_w)
    ys = np.arange(kh)[:, None]
    xs = np.arange(kw)[None, :]
    k = (1 - np.abs(ys / f_h - c_h)) * (1 - np.abs(xs / f_w - c_w))
    return np.broadcast_to(k, shape).astype(np.float32).copy()

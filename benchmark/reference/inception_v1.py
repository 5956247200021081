"""Plain reference for GoogLeNet / Inception-v1 (Szegedy et al.,
arXiv:1409.4842, Table 1; the main path without the auxiliary heads, as
``models/inception/Inception_v1.scala`` ships it): forward, loss, gradients
and the SGD step in float32 ``jax.numpy`` at ``highest`` precision.

Nothing here imports the program, and nothing here is given anything the
program made: weights come from ``init_params(key)``, the batches from the
harness's own samples, the dropout mask from the key rule the configuration
states (``dropout_mask``).

``quant="fp8"`` is the control of the output check: every convolution and
the classifier see their input and their weight rounded to 4 significant
bits (fp8 e4m3's mantissa, no range clamp), and so does the gradient that
arrives at their output in the backward pass: the nearest precision below
the bf16 compute the configuration states.  It is written as explicit
rounding arithmetic because XLA:TPU removes a float32 -> fp8 -> float32
convert pair as excess precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def conv_layers(cfg):
    """Every parameterised layer in construction order:
    (name, out_channels, in_channels, kernel, stride, pad)."""
    s = cfg["stem"]
    layers = [("conv1/7x7_s2", s["conv1"], cfg["input"][0], 7, 2, 3),
              ("conv2/3x3_reduce", s["conv2_reduce"], s["conv1"], 1, 1, 0),
              ("conv2/3x3", s["conv2"], s["conv2_reduce"], 3, 1, 1)]
    width = s["conv2"]
    for name, c1, c3r, c3, c5r, c5, pp in cfg["inception_modules"]:
        layers += [(f"{name}/1x1", c1, width, 1, 1, 0),
                   (f"{name}/3x3_reduce", c3r, width, 1, 1, 0),
                   (f"{name}/3x3", c3, c3r, 3, 1, 1),
                   (f"{name}/5x5_reduce", c5r, width, 1, 1, 0),
                   (f"{name}/5x5", c5, c5r, 5, 1, 2),
                   (f"{name}/pool_proj", pp, width, 1, 1, 0)]
        width = c1 + c3 + c5 + pp
    return layers, width


def param_shapes(cfg):
    """name -> (weight shape, bias shape), construction order (dicts keep
    it); convolutions are OIHW, the classifier (classes, features)."""
    layers, width = conv_layers(cfg)
    shapes = {name: ((o, i, k, k), (o,)) for name, o, i, k, _, _ in layers}
    shapes["loss3/classifier"] = ((cfg["classes"], width), (cfg["classes"],))
    return shapes


def init_params(key, cfg):
    """Xavier-uniform weights, zero biases, float32, in one traced call
    (one draw for all the weights, cut into the layers)."""
    shapes = param_shapes(cfg)
    sizes = [int(np.prod(w)) for w, _ in shapes.values()]
    flat = jax.random.uniform(key, (sum(sizes),), jnp.float32, -1.0, 1.0)
    params, start = {}, 0
    for (name, (wshape, bshape)), size in zip(shapes.items(), sizes):
        receptive = int(np.prod(wshape[2:]))
        bound = (6.0 / ((wshape[0] + wshape[1]) * receptive)) ** 0.5
        params[name] = {
            "weight": (flat[start:start + size] * bound).reshape(wshape),
            "bias": jnp.zeros(bshape, jnp.float32)}
        start += size
    return params


def dropout_key(seed, step):
    """The key of the dropout layer in training step ``step`` (1-based)
    as the configuration states it: the step's key is
    ``fold_in(PRNGKey(seed), step)`` and the one dropout layer draws
    ``bernoulli(split(key)[1], keep)`` over (batch, features, 1, 1)."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    return jax.random.split(key)[1]


def _rounded(x, quant):
    """``x`` as the control sees it, straight-through in the backward."""
    if quant != "fp8":
        return x
    m, e = jnp.frexp(x)
    q = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    return x + lax.stop_gradient(q - x)


@jax.custom_vjp
def _fp8_cotangent(y):
    """Identity whose backward pass rounds the arriving gradient."""
    return y


_fp8_cotangent.defvjp(lambda y: (y, None),
                      lambda _, g: (_rounded(g, "fp8"),))


def _conv(x, p, stride, pad, quant):
    w = p["weight"]
    x, w = _rounded(x, quant), _rounded(w, quant)
    y = lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)
    if quant == "fp8":
        y = _fp8_cotangent(y)
    return y + p["bias"][None, :, None, None]


def _max_pool_ceil(x, k, stride, pad):
    """Torch/Caffe ceil-mode max pooling over NCHW."""
    size = x.shape[2]
    out = -(-(size - k + 2 * pad) // stride) + 1
    if pad > 0 and (out - 1) * stride >= size + pad:
        out -= 1
    hi = max((out - 1) * stride + k - size - pad, 0)
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 1, k, k), (1, 1, stride, stride),
        ((0, 0), (0, 0), (pad, hi), (pad, hi)))


def _lrn(x, size, alpha, beta, k):
    """Across-channel local response normalisation:
    y = x / (k + alpha/size * sum_window x^2) ** beta."""
    lo = (size - 1) // 2
    sq = jnp.pad(x * x, ((0, 0), (lo, size - 1 - lo), (0, 0), (0, 0)))
    window = sum(sq[:, j:j + x.shape[1]] for j in range(size))
    return x / (k + alpha / size * window) ** beta


def forward(params, x, cfg, keep_mask, quant=None):
    """Log-probabilities (batch, classes).  ``keep_mask`` is the dropout
    keep-mask (batch, features, 1, 1) or None at inference."""
    relu = jax.nn.relu
    lrn = cfg["lrn"]

    def conv(name, h, stride=1, pad=0):
        return relu(_conv(h, params[name], stride, pad, quant))

    h = conv("conv1/7x7_s2", x, 2, 3)
    h = _max_pool_ceil(h, 3, 2, 0)
    h = _lrn(h, lrn["size"], lrn["alpha"], lrn["beta"], lrn["k"])
    h = conv("conv2/3x3_reduce", h)
    h = conv("conv2/3x3", h, 1, 1)
    h = _lrn(h, lrn["size"], lrn["alpha"], lrn["beta"], lrn["k"])
    h = _max_pool_ceil(h, 3, 2, 0)
    for name, *_ in cfg["inception_modules"]:
        h = jnp.concatenate([
            conv(f"{name}/1x1", h),
            conv(f"{name}/3x3", conv(f"{name}/3x3_reduce", h), 1, 1),
            conv(f"{name}/5x5", conv(f"{name}/5x5_reduce", h), 1, 2),
            conv(f"{name}/pool_proj", _max_pool_ceil(h, 3, 1, 1)),
        ], axis=1)
        if name in cfg["pool_after"]:
            h = _max_pool_ceil(h, 3, 2, 0)
    h = h.mean(axis=(2, 3), keepdims=True)          # 7x7 average pool
    if keep_mask is not None:
        h = jnp.where(keep_mask, h, 0.0) / (1.0 - cfg["dropout"])
    h = h.reshape(h.shape[0], -1)
    p = params["loss3/classifier"]
    w = p["weight"]
    h, w = _rounded(h, quant), _rounded(w, quant)
    logits = jnp.dot(h, w.T, precision=HIGHEST)
    if quant == "fp8":
        logits = _fp8_cotangent(logits)
    logits = logits + p["bias"]
    return jax.nn.log_softmax(logits)


def loss_sum(params, x, labels, cfg, keep_mask, quant=None):
    """Summed negative log-likelihood of 1-based ``labels`` over the rows
    given (the caller divides by the whole batch)."""
    logp = forward(params, x, cfg, keep_mask, quant)
    idx = labels.astype(jnp.int32) - 1
    return -jnp.take_along_axis(logp, idx[:, None], axis=1).sum()


def make_block_grad(cfg, quant=None):
    """Jitted (params, x_block, labels_block, mask_key, row0, batch) ->
    (loss sum, gradient sum) of one block of rows: the reference runs a
    batch in blocks so that its float32 activations fit beside nothing."""
    features = param_shapes(cfg)["loss3/classifier"][0][1]

    def block(params, x, labels, mask_key, row0, batch):
        keep = jax.random.bernoulli(mask_key, 1.0 - cfg["dropout"],
                                    (batch, features, 1, 1))
        keep = lax.dynamic_slice_in_dim(keep, row0, x.shape[0], axis=0)
        return jax.value_and_grad(loss_sum)(params, x, labels, cfg, keep,
                                            quant)

    return jax.jit(block, static_argnums=(5,))


def sgd_update(params, velocity, grads, opt):
    """The optimizer as the configuration states it: g' = g + wd * p;
    v = momentum * v + (1 - dampening) * g'; p = p - lr * v."""
    tmap = jax.tree_util.tree_map
    velocity = tmap(
        lambda p, v, g: opt["momentum"] * v
        + (1.0 - opt["dampening"]) * (g + opt["weight_decay"] * p),
        params, velocity, grads)
    params = tmap(lambda p, v: p - opt["learning_rate"] * v, params,
                  velocity)
    return params, velocity

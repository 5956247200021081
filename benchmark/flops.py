"""Operations and bytes that the algorithm needs, counted from the
configuration's shapes and never from the program under test.  A
multiply-add counts as two operations."""
from __future__ import annotations

from benchmark.reference.inception_v1 import conv_layers


def _pool_ceil(size, k, stride, pad):
    out = -(-(size - k + 2 * pad) // stride) + 1
    if pad > 0 and (out - 1) * stride >= size + pad:
        out -= 1
    return out


def inception_conv_table(cfg):
    """[(name, forward FLOPs of one record, needs input gradient)] for
    every convolution and the classifier of Inception-v1."""
    layers, width = conv_layers(cfg)
    size = cfg["input"][1]
    spatial = {}
    size = (size + 2 * 3 - 7) // 2 + 1              # conv1 7x7/2 pad 3
    spatial["conv1/7x7_s2"] = size
    size = _pool_ceil(size, 3, 2, 0)
    spatial["conv2/3x3_reduce"] = spatial["conv2/3x3"] = size
    size = _pool_ceil(size, 3, 2, 0)
    for name, *_ in cfg["inception_modules"]:
        for part in ("1x1", "3x3_reduce", "3x3", "5x5_reduce", "5x5",
                     "pool_proj"):
            spatial[f"{name}/{part}"] = size
        if name in cfg["pool_after"]:
            size = _pool_ceil(size, 3, 2, 0)
    table = []
    for name, out_c, in_c, k, _, _ in layers:
        hw = spatial[name] ** 2
        table.append((name, 2.0 * out_c * in_c * k * k * hw,
                      name != "conv1/7x7_s2"))
    table.append(("loss3/classifier", 2.0 * cfg["classes"] * width, True))
    return table


def inception_train_flops_per_record(cfg, convs_only=False):
    """Forward + backward of one record: each layer's forward, its weight
    gradient, and its input gradient except where the input is the data."""
    total = 0.0
    for name, fwd, needs_dx in inception_conv_table(cfg):
        if convs_only and name == "loss3/classifier":
            continue
        total += fwd * (3.0 if needs_dx else 2.0)
    return total


def lm_param_count(cfg):
    d, h, v, layers = (cfg["d_model"], cfg["ffn_hidden"], cfg["vocab_size"],
                       cfg["n_layers"])
    per_layer = 4 * (d * d + d) + 2 * d * h + h + d + 4 * d
    return v * d + d + layers * per_layer + 2 * d + d * v + v


def lm_position_flops(cfg, context):
    """One token at a context of ``context`` positions (itself included):
    projections, attention over the context, feed-forward, head.  The
    embedding is a row lookup."""
    d, h, v, layers = (cfg["d_model"], cfg["ffn_hidden"], cfg["vocab_size"],
                       cfg["n_layers"])
    per_layer = 2.0 * 4 * d * d + 2.0 * 2 * d * h + 2.0 * 2 * context * d
    return layers * per_layer + 2.0 * d * v


def lm_step_weight_bytes(cfg):
    """Bytes of weights one decode step has to read once: every matrix
    but the embedding table (a row lookup), at the stored width."""
    width = cfg["bytes_per_weight"]
    return (lm_param_count(cfg)
            - cfg["vocab_size"] * cfg["d_model"]) * width


def lm_kv_bytes_per_token(cfg):
    return 2 * cfg["d_model"] * cfg["n_layers"] * cfg["bytes_per_kv"]


def lm_span_flops(cfg, p_a, p_b):
    """Operations of one request's positions [p_a, p_b) (fractional ends
    allowed: position p attends p + 1 positions)."""
    d, layers = cfg["d_model"], cfg["n_layers"]
    n = p_b - p_a
    contexts = (p_b * p_b - p_a * p_a) / 2.0 + n / 2.0
    return n * lm_position_flops(cfg, 0) + layers * 2.0 * 2 * d * contexts


def lm_span_kv_bytes(cfg, p_a, p_b):
    """Live keys and values read once at each of the positions."""
    n = p_b - p_a
    contexts = (p_b * p_b - p_a * p_a) / 2.0 + n / 2.0
    return lm_kv_bytes_per_token(cfg) * contexts

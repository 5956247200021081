"""Operations that one chip's share of an lfm2_moe decoder needs (gated
short convolutions in most layers, grouped-query attention in the others,
leading dense layers, sigmoid-routed experts with no shared one, a tied
head), counted from the configuration's shapes, the causal mask's exact
pair count and the counters' assignments, never from the program under
test.  The rules are ``flops_lm``'s: a multiply-add counts as two
operations, the backward pass needs twice the forward's, work that the
program does twice because it recomputes activations is not needed work."""
from __future__ import annotations

from benchmark.flops_lm import (TRAIN, expected_assignments,  # noqa: F401
                                expert_products_train, visible_pairs)


def layers_of(cfg, kind):
    return sum(1 for k in cfg["layer_types"] if k == kind)


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def short_conv_forward_per_token(cfg):
    """One conv layer's operator: the input projection to [B | C | x~], the
    output projection, and a channel's element work: ``conv_L_cache``
    multiply-adds of the taps and the two gates' multiplies."""
    d = cfg["hidden_size"]
    return 2.0 * d * 3 * d + 2.0 * d * d + (2 * cfg["conv_L_cache"] + 2) * d


def short_conv_train(cfg, tokens):
    """Forward + backward operations of every conv layer's operator."""
    return TRAIN * layers_of(cfg, "conv") * tokens \
        * short_conv_forward_per_token(cfg)


def attention_core_forward(cfg, t):
    """QK and PV of one attention layer over one sequence, full causal."""
    return 2 * 2.0 * cfg["num_attention_heads"] * head_dim(cfg) \
        * visible_pairs(t)


def attention_core_train(cfg, t):
    """Forward + backward core operations of one sequence, over the
    attention layers."""
    return TRAIN * layers_of(cfg, "full_attention") \
        * attention_core_forward(cfg, t)


def attention_projections_forward_per_token(cfg):
    """q and o at the query heads' width, k and v at the key/value heads'."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    return 2.0 * d * hd * (2 * cfg["num_attention_heads"]
                           + 2 * cfg["num_key_value_heads"])


def dense_forward_per_token(cfg):
    """Every product whose cost is the same for each token: the operators
    outside the attention cores, dense feed-forwards, routers, the head."""
    d = cfg["hidden_size"]
    layers, dense = len(cfg["layer_types"]), cfg["num_dense_layers"]
    ffn = 2.0 * 3 * d * cfg["intermediate_size"]
    router = 2.0 * d * cfg["router_experts"]
    head = 2.0 * d * cfg["vocab_size"]
    return (layers_of(cfg, "conv") * short_conv_forward_per_token(cfg)
            + layers_of(cfg, "full_attention")
            * attention_projections_forward_per_token(cfg)
            + dense * ffn + (layers - dense) * router + head)


def train_flops_per_step(cfg, sequences, t, assignments_by_layer=None):
    """Needed forward + backward operations of one step of ``sequences``
    sequences of ``t`` tokens.  ``assignments_by_layer``: the assignments
    each expert layer held in the step (the counters' means), else the
    even share."""
    tokens = sequences * t
    sparse = len(cfg["layer_types"]) - cfg["num_dense_layers"]
    if assignments_by_layer is None:
        assignments_by_layer = [expected_assignments(cfg, tokens)] * sparse
    return (TRAIN * dense_forward_per_token(cfg) * tokens
            + sequences * attention_core_train(cfg, t)
            + sum(expert_products_train(cfg, a)
                  for a in assignments_by_layer))

"""The lfm2_moe decoder family as its public ``config.json`` files name it
(the sizes here are read as LiquidAI/LFM2-24B-A2B's keys): most layers hold
no attention but a gated short convolution (``nn.ShortConv``: a depthwise
causal convolution of ``conv_L_cache`` taps between two gates), the
``full_attention`` layers grouped-query attention with an RMSNorm on every
head's query and key and rotary positions, no gate and no window; the first
``num_dense_layers`` layers have a dense SwiGLU, the others sigmoid top-k
routed SwiGLU experts with a selection bias and no shared expert; two
RMSNorms a layer, an unscaled token embedding, and a head that is the
embedding, transposed (``nn.TiedLmHead``).

``Lfm2MoeLM`` trains with ``Optimizer(model, dataset,
TimeDistributedCriterion(ClassNLLCriterion(), True), SGD()).optimize()`` on
(B, T) 1-based token ids and targets, like ``AfmoeLM``.  Each decoder layer
is wrapped in ``nn.Recompute``: the backward pass holds one layer's input
at a time plus what the layer's loops marked (an attention core's output
and logsumexp), so a layer's recomputation redoes the projections, the
short convolution's element work, the norms, the router and the sort.
"""
from __future__ import annotations

import bigdl_tpu.nn as nn
# h' = h + Op(Norm(h)); out = h' + FFN(Norm(h')), under nn.Recompute: the
# same pre-norm layer, whatever the operator
from bigdl_tpu.models.deepseek_v3 import deepseek_v3_layer as pre_norm_layer

# the constant that the family's routing adds to the chosen scores' sum
ROUTE_NORM_EPS = 1e-6


def Lfm2MoeLM(vocab_size: int, hidden_size: int, layer_types,
              num_dense_layers: int, num_attention_heads: int,
              num_key_value_heads: int, intermediate_size: int,
              moe_intermediate_size: int, num_experts: int,
              num_experts_per_tok: int, experts_held=None,
              conv_L_cache: int = 3, norm_topk_prob: bool = True,
              routed_scaling_factor: float = 1.0,
              rope_theta: float = 1000000.0, norm_eps: float = 1e-5):
    """(B, T) token ids -> (B, T, vocab) log-probabilities.

    ``layer_types``: 'conv' or 'full_attention' per layer; the first
    ``num_dense_layers`` have a SwiGLU of ``intermediate_size``, the others
    ``num_experts`` routed experts of ``moe_intermediate_size`` (top
    ``num_experts_per_tok``; ``use_expert_bias`` is ``DroplessMoE``'s
    ``route_bias`` buffer).  ``experts_held``: the ids of the routed experts
    this model holds in every expert layer (None: all); ``vocab_size`` is
    the size of the vocabulary slice it holds; a head is ``hidden_size /
    num_attention_heads`` wide (the config has no key for it).  The
    argument names are the published config's keys."""
    head_dim = hidden_size // num_attention_heads
    body = nn.Sequential()
    for i, kind in enumerate(layer_types):
        if kind == "conv":
            operator = nn.ShortConv(hidden_size, conv_L_cache)
        elif kind == "full_attention":
            operator = nn.GroupedQueryAttention(
                hidden_size, num_attention_heads, num_key_value_heads,
                head_dim, rotary_base=rope_theta, eps=norm_eps)
        else:
            raise ValueError(f"layer {i}: unknown layer type {kind!r}")
        if i < num_dense_layers:
            ffn = nn.GatedLinearUnit(hidden_size, intermediate_size)
        else:
            ffn = nn.DroplessMoE(
                hidden_size, moe_intermediate_size, num_experts,
                num_experts_per_tok, experts_held=experts_held,
                route_norm=norm_topk_prob,
                route_scale=routed_scaling_factor,
                route_eps=ROUTE_NORM_EPS)
        body.add(pre_norm_layer(hidden_size, operator, ffn, norm_eps))
    body.add(nn.RMSNorm(hidden_size, norm_eps))
    return nn.TiedLmHead(vocab_size, hidden_size, body)

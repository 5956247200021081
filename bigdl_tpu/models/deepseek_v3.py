"""The deepseek_v3 decoder family as its public ``config.json`` files name
it (the sizes here are read as kakaocorp/kanana-2-30b-a3b's keys): latent
attention whose keys and values come out of one compressed vector a token,
with a rotary key shared by all heads; sigmoid top-k routed SwiGLU experts
(``noaux_tc`` at one group: the choice is the top k of score + bias) beside
shared experts; the first ``first_k_dense_replace`` layers dense; two
RMSNorms a layer, an unscaled token embedding and an untied head.  A model
of the family that compresses its queries (``q_lora_rank``) or limits the
choice to groups of experts (``n_group`` > 1) is not built here.

``DeepseekV3LM`` returns an ``nn.Sequential`` of ordinary modules and
trains with ``Optimizer(model, dataset, TimeDistributedCriterion(
ClassNLLCriterion(), True), SGD()).optimize()`` on (B, T) 1-based token ids
and targets, like ``AfmoeLM``.  Each decoder layer is wrapped in
``nn.Recompute``: the backward pass holds one layer's activations at a
time plus what the layer's loops marked (the attention core's output and
logsumexp, the routed experts' sum), so a layer's recomputation redoes the
latent path (down-projection, latent norm, up-projection, rotary), the
query projection, the norms, the router and the sort, and neither loop.
"""
from __future__ import annotations

import bigdl_tpu.nn as nn
from bigdl_tpu.models.transformer import _residual
from bigdl_tpu.nn.init import LM_INIT_STD


def deepseek_v3_layer(hidden_size, attention, ffn, eps):
    """h' = h + Attn(Norm(h)); out = h' + FFN(Norm(h'))."""
    return nn.Recompute(nn.Sequential(
        _residual(nn.Sequential(nn.RMSNorm(hidden_size, eps), attention)),
        _residual(nn.Sequential(nn.RMSNorm(hidden_size, eps), ffn)),
    ))


def DeepseekV3LM(vocab_size: int, hidden_size: int, num_hidden_layers: int,
                 first_k_dense_replace: int, num_attention_heads: int,
                 kv_lora_rank: int, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, v_head_dim: int,
                 intermediate_size: int, moe_intermediate_size: int,
                 n_routed_experts: int, num_experts_per_tok: int,
                 experts_held=None, n_shared_experts: int = 1,
                 routed_scaling_factor: float = 1.0,
                 norm_topk_prob: bool = True, rope_theta: float = 10000.0,
                 rms_norm_eps: float = 1e-6):
    """(B, T) token ids -> (B, T, vocab) log-probabilities.

    The first ``first_k_dense_replace`` layers have a SwiGLU of
    ``intermediate_size``, the others ``n_routed_experts`` routed experts of
    ``moe_intermediate_size`` (the router's width; top
    ``num_experts_per_tok``) and ``n_shared_experts`` shared ones.
    ``experts_held``: the ids of the routed experts this model holds in
    every expert layer (None: all); ``vocab_size`` is the size of the
    vocabulary slice it holds.  The argument names are the published
    config's keys."""
    model = nn.Sequential(
        nn.LookupTable(vocab_size, hidden_size, init_std=LM_INIT_STD))
    for i in range(num_hidden_layers):
        attention = nn.LatentAttention(
            hidden_size, num_attention_heads, kv_lora_rank,
            qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
            rotary_base=rope_theta, eps=rms_norm_eps)
        if i < first_k_dense_replace:
            ffn = nn.GatedLinearUnit(hidden_size, intermediate_size)
        else:
            ffn = nn.DroplessMoE(
                hidden_size, moe_intermediate_size, n_routed_experts,
                num_experts_per_tok, experts_held=experts_held,
                route_norm=norm_topk_prob,
                route_scale=routed_scaling_factor,
                shared_hidden=n_shared_experts * moe_intermediate_size)
        model.add(deepseek_v3_layer(hidden_size, attention, ffn,
                                    rms_norm_eps))
    model.add(nn.RMSNorm(hidden_size, rms_norm_eps))
    model.add(nn.LmHead(hidden_size, vocab_size))
    return model

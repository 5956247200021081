"""The afmoe decoder family (Arcee Trinity; the public ``config.json`` of
arcee-ai/Trinity-Mini names every size): sigmoid top-k routed SwiGLU experts
beside a shared expert, grouped-query attention with per-head q/k RMSNorm
and a sigmoid output gate, window layers with rotary positions and full
layers with none, four RMSNorms a layer, a scaled token embedding and an
untied head.

``AfmoeLM`` returns an ``nn.Sequential`` of ordinary modules: it trains
with ``Optimizer(model, dataset, TimeDistributedCriterion(
ClassNLLCriterion(), True), SGD()).optimize()`` on (B, T) 1-based token ids
and (B, T) 1-based targets, like ``TransformerLM``.  Each decoder layer is
wrapped in ``nn.Recompute``: the backward pass holds one layer's
activations at a time, plus each layer's core output and expert sum (the
attention core's output and logsumexp and the routed experts' sum are
marked where they are made, ``parallel/ring_attention.py`` and
``parallel/moe.py``: each costs a loop over blocks or chunks to make again
and one array a layer to keep, so a layer's recomputation redoes the
projections, norms, router and sort, and neither loop).
"""
from __future__ import annotations

import bigdl_tpu.nn as nn
from bigdl_tpu.models.transformer import _residual
from bigdl_tpu.nn.init import LM_INIT_STD


def afmoe_layer(hidden_size, n_heads, n_kv_heads, head_dim, ffn, window,
                rotary_base, eps):
    """h' = h + Norm(Attn(Norm(h))); out = h' + Norm(FFN(Norm(h'))).
    ``window`` None makes a full layer, which also takes no positions."""
    attention = nn.GatedGroupedQueryAttention(
        hidden_size, n_heads, n_kv_heads, head_dim, window=window,
        rotary_base=rotary_base if window is not None else None, eps=eps)
    return nn.Recompute(nn.Sequential(
        _residual(nn.Sequential(nn.RMSNorm(hidden_size, eps), attention,
                                nn.RMSNorm(hidden_size, eps))),
        _residual(nn.Sequential(nn.RMSNorm(hidden_size, eps), ffn,
                                nn.RMSNorm(hidden_size, eps))),
    ))


def AfmoeLM(vocab_size: int, hidden_size: int, layer_types,
            num_dense_layers: int, num_attention_heads: int,
            num_key_value_heads: int, head_dim: int, intermediate_size: int,
            moe_intermediate_size: int, num_experts: int,
            num_experts_per_tok: int, experts_held=None,
            num_shared_experts: int = 1, sliding_window: int = 2048,
            rope_theta: float = 10000.0, rms_norm_eps: float = 1e-5,
            route_norm: bool = True, route_scale: float = 1.0):
    """(B, T) token ids -> (B, T, vocab) log-probabilities.

    ``layer_types``: 'sliding_attention' or 'full_attention' per layer;
    the first ``num_dense_layers`` have a SwiGLU of ``intermediate_size``,
    the others ``num_experts`` routed experts of ``moe_intermediate_size``
    (top ``num_experts_per_tok``) and ``num_shared_experts`` shared ones.
    ``experts_held``: the ids of the routed experts this model holds in
    every expert layer (None: all); ``vocab_size`` is the size of the
    vocabulary slice it holds.  The argument names are the published
    config's keys."""
    model = nn.Sequential(
        nn.LookupTable(vocab_size, hidden_size, init_std=LM_INIT_STD),
        nn.MulConstant(float(hidden_size) ** 0.5),        # mup_enabled
    )
    for i, kind in enumerate(layer_types):
        if kind not in ("sliding_attention", "full_attention"):
            raise ValueError(f"layer {i}: unknown layer type {kind!r}")
        if i < num_dense_layers:
            ffn = nn.GatedLinearUnit(hidden_size, intermediate_size)
        else:
            ffn = nn.DroplessMoE(
                hidden_size, moe_intermediate_size, num_experts,
                num_experts_per_tok, experts_held=experts_held,
                route_norm=route_norm, route_scale=route_scale,
                shared_hidden=num_shared_experts * moe_intermediate_size)
        model.add(afmoe_layer(
            hidden_size, num_attention_heads, num_key_value_heads, head_dim,
            ffn, sliding_window if kind == "sliding_attention" else None,
            rope_theta, rms_norm_eps))
    model.add(nn.RMSNorm(hidden_size, rms_norm_eps))
    model.add(nn.LmHead(hidden_size, vocab_size))
    return model

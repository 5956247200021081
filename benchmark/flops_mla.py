"""Operations that one chip's share of a deepseek_v3 decoder needs (latent
attention, a leading dense layer, sigmoid-routed experts beside shared
ones), counted from the configuration's shapes, the causal mask's exact
pair count and the counters' assignments, never from the program under
test.  The rules are ``flops_lm``'s: a multiply-add counts as two
operations, the backward pass needs twice the forward's, work that the
program does twice because it recomputes activations is not needed work."""
from __future__ import annotations

from benchmark.flops_lm import (TRAIN, expected_assignments,  # noqa: F401
                                expert_products_train, visible_pairs)


def attention_core_forward(cfg, t):
    """QK over ``qk_nope_head_dim`` + ``qk_rope_head_dim`` and PV over
    ``v_head_dim`` of one layer over one sequence, every layer full
    causal."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 2.0 * cfg["num_attention_heads"] * (qk + cfg["v_head_dim"]) \
        * visible_pairs(t)


def attention_core_train(cfg, t):
    """Forward + backward core operations of one sequence, all layers."""
    return TRAIN * cfg["num_hidden_layers"] * attention_core_forward(cfg, t)


def latent_projections_forward_per_token(cfg):
    """One layer's products around the core: the query projection, the
    down-projection to the latent and the rotary key, the up-projection to
    the heads' keys and values, the output projection."""
    d, heads, r = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["kv_lora_rank"])
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return 2.0 * (d * heads * (nope + rope) + d * (r + rope)
                  + r * heads * (nope + dv) + heads * dv * d)


def dense_forward_per_token(cfg):
    """Every product whose cost is the same for each token: the latent
    attention's projections, dense and shared feed-forwards, routers,
    head."""
    d = cfg["hidden_size"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    ffn = 2.0 * 3 * d * cfg["intermediate_size"]
    shared = 2.0 * 3 * d * cfg["n_shared_experts"] \
        * cfg["moe_intermediate_size"]
    router = 2.0 * d * cfg["router_experts"]
    head = 2.0 * d * cfg["vocab_size"]
    return layers * latent_projections_forward_per_token(cfg) \
        + dense * ffn + (layers - dense) * (shared + router) + head


def train_flops_per_step(cfg, sequences, t, assignments_by_layer=None):
    """Needed forward + backward operations of one step of ``sequences``
    sequences of ``t`` tokens.  ``assignments_by_layer``: the assignments
    each expert layer held in the step (the counters' means), else the
    even share."""
    tokens = sequences * t
    sparse = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    if assignments_by_layer is None:
        assignments_by_layer = [expected_assignments(cfg, tokens)] * sparse
    return (TRAIN * dense_forward_per_token(cfg) * tokens
            + sequences * attention_core_train(cfg, t)
            + sum(expert_products_train(cfg, a)
                  for a in assignments_by_layer))

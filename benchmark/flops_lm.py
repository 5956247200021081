"""Operations that one chip's share of an afmoe decoder needs, counted from
the configuration's shapes, the attention mask's exact pair counts and the
counters' assignments, never from the program under test.  A multiply-add
counts as two operations; the backward pass needs twice the forward's (each
product's input gradient and weight gradient); work that the program does
twice because it recomputes activations is not needed work."""
from __future__ import annotations

TRAIN = 3.0         # forward + backward, in forwards


def visible_pairs(t, window=None):
    """(query, key) pairs of a causal mask over ``t`` positions: key j is
    seen by query i when j <= i and, under a window, i - j < window."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def layer_windows(cfg):
    return [cfg["sliding_window"] if kind == "sliding_attention" else None
            for kind in cfg["layer_types"]]


def attention_core_forward(cfg, t, window):
    """QK and PV of one layer over one sequence."""
    heads, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return 2 * 2.0 * heads * hd * visible_pairs(t, window)


def attention_core_train(cfg, t, kinds=("window", "full")):
    """Forward + backward core operations of one sequence, over the layers
    of the kinds asked for."""
    return TRAIN * sum(
        attention_core_forward(cfg, t, w) for w in layer_windows(cfg)
        if ("window" if w is not None else "full") in kinds)


def expert_products_train(cfg, assignments):
    """The three grouped products of a SwiGLU over ``assignments`` rows,
    forward + backward."""
    return TRAIN * 3 * 2.0 * cfg["hidden_size"] \
        * cfg["moe_intermediate_size"] * assignments


def expected_assignments(cfg, tokens):
    """Assignments an expert layer holds here under even routing."""
    return tokens * cfg["num_experts_per_tok"] \
        * len(cfg["experts_held"]) / cfg["router_experts"]


def dense_forward_per_token(cfg):
    """Every product whose cost is the same for each token: projections,
    dense and shared feed-forwards, routers, head."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q_out = cfg["num_attention_heads"] * hd
    kv_out = cfg["num_key_value_heads"] * hd
    layers = cfg["num_hidden_layers"]
    dense, sparse = cfg["num_dense_layers"], layers - cfg["num_dense_layers"]
    projections = 2.0 * d * (3 * q_out + 2 * kv_out)        # q, g, o, k, v
    ffn = 2.0 * 3 * d * cfg["intermediate_size"]
    shared = 2.0 * 3 * d * cfg["num_shared_experts"] \
        * cfg["moe_intermediate_size"]
    router = 2.0 * d * cfg["router_experts"]
    head = 2.0 * d * cfg["vocab_size"]
    return layers * projections + dense * ffn + sparse * (shared + router) \
        + head


def train_flops_per_step(cfg, sequences, t, assignments_by_layer=None):
    """Needed forward + backward operations of one step of ``sequences``
    sequences of ``t`` tokens.  ``assignments_by_layer``: the assignments
    each expert layer held in the step (the counters' means), else the
    even share."""
    tokens = sequences * t
    sparse = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    if assignments_by_layer is None:
        assignments_by_layer = [expected_assignments(cfg, tokens)] * sparse
    return (TRAIN * dense_forward_per_token(cfg) * tokens
            + sequences * attention_core_train(cfg, t)
            + sum(expert_products_train(cfg, a)
                  for a in assignments_by_layer))

"""Plain reference for one chip's share of an lfm2_moe decoder
(LiquidAI/LFM2-24B-A2B, ``config.json``): forward, loss, gradients and the
SGD step in float32 ``jax.numpy`` at ``highest`` precision, written from the
equations in the configuration's ``equations`` and not from the program.
Dense masks, a loop over the experts held, each over all tokens, no kernel.

Nothing here imports the program.  What belongs to no one model is the
afmoe reference's own, imported: the control's rounding (``_product``),
``rms_norm``, ``swiglu``, the SGD step, and the grouped-head causal softmax
under a dense mask with its rotate-half rotary (``_masked_softmax_pv``,
``rotate``: this family's attention is that one without window or gate).
The gated short convolution, the layer pattern, the routing's constants and
the tied head are here.  Weights come from ``init_params(key)``, the
sequences from the harness's own records.  A block is one sequence.

A ``conv`` layer's operator: ``[B | C | x~] = x W_in``; ``u = B * x~``;
``c_t = sum_j w[:, j] * u_{t-(L-1)+j}``, zeros before the sequence's start;
``y = C * c``; ``y W_out``.  A ``full_attention`` layer's: grouped heads, an
RMSNorm over every head's query and key, then rotary on both.  The head is
the embedding, transposed: there is no head matrix among the parameters,
and the embedding's gradient is the sum of the lookup's and the head's.

The share: the router scores all ``router_experts`` and the top
``num_experts_per_tok`` are chosen among all of them; only the experts in
``experts_held`` add to the output, what the absent ones would add is left
out, and that partial result goes on.  There is no shared expert.

Departures from the published model, each also in the configuration's
``assumed``: no auxiliary balance term and a zero selection bias (the
config defines no rate); normal(0, 0.02) matrices, the taps included, and
norms at 1.  Two departures at the cell's own size change no number
(``make_block_grad(..., query_chunk=, remat=)``; the CPU tests run without
them): queries are taken ``query_chunk`` at a time, each chunk against
every key under a dense mask, and each layer is recomputed in the backward
pass.

``quant="fp8"`` is the control of the output check: the operands of every
projection (the short convolution's two, the attention's four), of every
expert product and of the head, and the gradient that arrives at their
outputs, rounded to 4 significant bits.  The router, the norms, the gates
and taps and the attention core stay float32.

``fault`` computes a deliberately different model, for the tests and the
rehearsal: ``taps_reversed`` (tap j weighs u_{t-j}), ``conv_forward`` (the
convolution sees the L - 1 tokens after t, not before), ``gates_swapped``
(the projection read as [C | B | x~]), ``x_first`` (read as [x~ | B | C]),
``no_qk_norm``, ``no_rotary``, ``route_eps`` (1e-20 where the family writes
1e-6), ``head_untied`` (the head a matrix of its own, drawn from a fixed
key: the embedding gets the lookup's gradient alone), ``top_k_less`` (one
expert fewer a token), ``capacity`` (each expert keeps its first tokens up
to 1.25 x the even share and drops the rest).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.afmoe import (HIGHEST, _masked_softmax_pv,  # noqa: F401
                                       _product, dropout_key, rms_norm,
                                       rotate, sgd_update, swiglu)

CONV_PARTS = ("w_in", "conv", "w_out")
ATTENTION_PARTS = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
ROUTE_EPS = 1e-6


def _is_dense(cfg, i):
    return i < cfg["num_dense_layers"]


def _head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def param_shapes(cfg):
    """name -> {part: shape}, in the program's construction order.
    Projections multiply as x @ W: (in, out).  No head: it is the
    embedding."""
    d, hd, taps = cfg["hidden_size"], _head_dim(cfg), cfg["conv_L_cache"]
    q_out = cfg["num_attention_heads"] * hd
    kv_out = cfg["num_key_value_heads"] * hd
    held, h = len(cfg["experts_held"]), cfg["moe_intermediate_size"]
    norm = {"weight": (d,)}
    shapes = {"embed": {"weight": (cfg["vocab_size"], d)}}
    for i, kind in enumerate(cfg["layer_types"]):
        shapes[f"layer{i}/norm1"] = norm
        if kind == "conv":
            shapes[f"layer{i}/conv"] = {"w_in": (d, 3 * d),
                                        "conv": (d, taps), "w_out": (d, d)}
        else:
            shapes[f"layer{i}/attn"] = {
                "wq": (d, q_out), "wk": (d, kv_out), "wv": (d, kv_out),
                "wo": (q_out, d), "q_norm": (hd,), "k_norm": (hd,)}
        shapes[f"layer{i}/norm2"] = norm
        if _is_dense(cfg, i):
            w = cfg["intermediate_size"]
            shapes[f"layer{i}/ffn"] = {"w_gate": (d, w), "w_up": (d, w),
                                       "w_down": (w, d)}
        else:
            shapes[f"layer{i}/moe"] = {
                "router": (d, cfg["router_experts"]),
                "w_gate": (held, d, h), "w_up": (held, d, h),
                "w_down": (held, h, d)}
    shapes["final_norm"] = norm
    return shapes


def init_params(key, cfg):
    """Normal(0, initializer_std) matrices (the taps too), norm weights at
    1, float32."""
    std = cfg["assumed"]["initializer_std"]
    params, n = {}, 0
    for name, parts in param_shapes(cfg).items():
        params[name] = {}
        for part, shape in parts.items():
            n += 1
            if len(shape) == 1:
                params[name][part] = jnp.ones(shape, jnp.float32)
            else:
                params[name][part] = std * jax.random.normal(
                    jax.random.fold_in(key, n), shape, jnp.float32)
    return params


# -- the pieces ----------------------------------------------------------------

def short_conv(p, x, cfg, quant=None, fault=None):
    """x: (T, hidden) -> (T, hidden): the gated short convolution."""
    t, taps = x.shape[0], cfg["conv_L_cache"]
    parts = jnp.split(_product(x, p["w_in"], quant), 3, axis=-1)
    gate_in, gate_out, inner = {
        "gates_swapped": (parts[1], parts[0], parts[2]),
        "x_first": (parts[1], parts[2], parts[0])}.get(fault, parts)
    u = gate_in * inner
    w = p["conv"][:, ::-1] if fault == "taps_reversed" else p["conv"]
    if fault == "conv_forward":
        u = u[::-1]
    # c_t = sum_j w[:, j] * u_{t - (L-1) + j}: L - 1 zero rows stand before
    # the sequence's start
    before = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1])), u])
    c = sum(w[:, j] * before[j:j + t] for j in range(taps))
    if fault == "conv_forward":
        c = c[::-1]
    return _product(gate_out * c, p["w_out"], quant)


def attention(p, x, cfg, quant=None, fault=None, query_chunk=None):
    """x: (T, hidden) -> (T, hidden): full causal, grouped heads, q/k
    norms, then rotary; no gate, no window."""
    t, hd, eps = x.shape[0], _head_dim(cfg), cfg["norm_eps"]
    theta = cfg["rope_parameters"]["rope_theta"]
    q = _product(x, p["wq"], quant).reshape(t, -1, hd)
    k = _product(x, p["wk"], quant).reshape(t, -1, hd)
    v = _product(x, p["wv"], quant).reshape(t, -1, hd)
    if fault != "no_qk_norm":
        q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
    if fault != "no_rotary":
        q, k = rotate(q, theta), rotate(k, theta)
    if query_chunk is None or query_chunk >= t:
        o = _masked_softmax_pv(q, k, v, jnp.arange(t), None)
    else:
        chunk = jax.checkpoint(
            lambda q_c, rows: _masked_softmax_pv(q_c, k, v, rows, None))
        o = lax.map(lambda a: chunk(*a),
                    (q.reshape(t // query_chunk, query_chunk, -1, hd),
                     jnp.arange(t).reshape(-1, query_chunk)))
        o = o.reshape(t, -1)
    return _product(o, p["wo"], quant)


def route(p, x, cfg, fault=None):
    """(chosen expert ids (T, k), their weights (T, k)), over all
    ``router_experts``: the top k of sigmoid score + selection bias (zero:
    ``assumed``), the chosen scores over their sum + 1e-6, times
    ``routed_scaling_factor``."""
    k = cfg["num_experts_per_tok"] - (1 if fault == "top_k_less" else 0)
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router"], precision=HIGHEST))
    _, idx = lax.top_k(scores, k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True)
                 + (1e-20 if fault == "route_eps" else ROUTE_EPS))
    return idx, w * cfg["routed_scaling_factor"]


def expert_layer(p, x, cfg, quant=None, fault=None, experts_held=None,
                 choices=None, remat=False):
    """Sum over the chosen experts held of w_e * Expert_e(x); no shared
    expert.  ``experts_held`` defaults to the configuration's;
    ``p['w_*'][j]`` is the j-th of them.  ``choices``: a list that is given
    the chosen ids."""
    held = cfg["experts_held"] if experts_held is None else experts_held
    idx, w = route(p, x, cfg, fault)
    if choices is not None:
        choices.append(idx)

    def add_expert(y, expert):          # one expert held, all tokens
        e, w_gate, w_up, w_down = expert
        chosen = idx == e                               # (T, k)
        w_e = jnp.sum(jnp.where(chosen, w, 0.0), axis=-1)
        if fault == "capacity":
            cap = int(1.25 * x.shape[0] * idx.shape[1]
                      / cfg["router_experts"])
            rank = jnp.cumsum(chosen.any(axis=-1)) - 1
            w_e = jnp.where(rank < cap, w_e, 0.0)
        return y + w_e[:, None] * swiglu(x, w_gate, w_up, w_down,
                                         quant), None

    # a scan over the experts held: one body to compile; under ``remat`` an
    # expert's activations are not kept for the others'
    y, _ = lax.scan(jax.checkpoint(add_expert) if remat else add_expert,
                    jnp.zeros_like(x),
                    (jnp.asarray(list(held)), p["w_gate"], p["w_up"],
                     p["w_down"]))
    return y


def layer(params, i, h, cfg, quant=None, fault=None, query_chunk=None,
          choices=None, remat=False):
    eps, name = cfg["norm_eps"], f"layer{i}"
    norm = lambda part, a: rms_norm(a, params[f"{name}/{part}"]["weight"],
                                    eps)
    if cfg["layer_types"][i] == "conv":
        h = h + short_conv(params[f"{name}/conv"], norm("norm1", h), cfg,
                           quant, fault)
    else:
        h = h + attention(params[f"{name}/attn"], norm("norm1", h), cfg,
                          quant, fault, query_chunk)
    x = norm("norm2", h)
    if _is_dense(cfg, i):
        f = params[f"{name}/ffn"]
        return h + swiglu(x, f["w_gate"], f["w_up"], f["w_down"], quant)
    return h + expert_layer(params[f"{name}/moe"], x, cfg, quant, fault,
                            choices=choices, remat=remat)


def forward(params, ids, cfg, quant=None, fault=None, query_chunk=None,
            remat=False, choices=None):
    """ids: (T,) 1-based token ids -> (T, vocab) log-probabilities."""
    table = params["embed"]["weight"]
    h = table[ids.astype(jnp.int32) - 1]
    for i in range(len(cfg["layer_types"])):
        f = lambda p, h_, i=i: layer(p, i, h_, cfg, quant, fault,
                                     query_chunk, choices, remat)
        h = (jax.checkpoint(f) if remat else f)(params, h)
    h = rms_norm(h, params["final_norm"]["weight"], cfg["norm_eps"])
    if fault == "head_untied":
        table = cfg["assumed"]["initializer_std"] * jax.random.normal(
            jax.random.PRNGKey(0), table.shape, jnp.float32)
    return jax.nn.log_softmax(_product(h, table.T, quant))


def routing_choices(params, ids, cfg, quant=None, query_chunk=None):
    """The chosen expert ids (T, k) of every expert layer, in order."""
    choices = []
    forward(params, ids, cfg, quant=quant, query_chunk=query_chunk,
            choices=choices)
    return choices


def loss_mean(params, ids, targets, cfg, **kw):
    """Mean token cross-entropy of one sequence."""
    logp = forward(params, ids, cfg, **kw)
    picked = jnp.take_along_axis(
        logp, (targets.astype(jnp.int32) - 1)[:, None], axis=1)
    return -picked.mean()


def make_block_grad(cfg, quant=None, fault=None, query_chunk=None,
                    remat=False):
    """Jitted (params, ids (T,), targets (T,)) -> (mean loss of the
    sequence, its gradient): the caller averages over the sequences."""
    kw = dict(quant=quant, fault=fault, query_chunk=query_chunk, remat=remat)
    return jax.jit(jax.value_and_grad(
        lambda p, ids, targets: loss_mean(p, ids, targets, cfg, **kw)))

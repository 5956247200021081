"""Plain reference for one chip's share of a deepseek_v3 decoder
(kakaocorp/kanana-2-30b-a3b-instruct-2601, ``config.json``): forward, loss,
gradients and the SGD step in float32 ``jax.numpy`` at ``highest``
precision, written from the equations in the configuration's ``equations``
and not from the program.  Dense masks, a loop over the experts held, each
over all tokens, no kernel.

Nothing here imports the program.  The arithmetic that belongs to no model
(the control's rounding, ``rms_norm``, ``swiglu``, the SGD step) is the
afmoe reference's own, imported; every equation of this model is here.
Weights come from ``init_params(key)``, the sequences from the harness's
own records.  A block is one sequence.

Latent attention as the config names it, ``q_lora_rank`` null: the query is
one projection to heads of ``qk_nope_head_dim`` + ``qk_rope_head_dim``; one
down-projection gives a ``kv_lora_rank`` latent and ONE rotary key of
``qk_rope_head_dim`` a token; the latent is RMS-normed and up-projected to
heads of ``qk_nope_head_dim`` (key) + ``v_head_dim`` (value); rotary turns
the neighbouring pairs (2i, 2i + 1) of the rope part only
(``rope_interleave``), and every head's key ends in the same rotary key.
The scores are written as the sum of the two parts' products, so no key is
ever joined or copied to the heads.

The share: the router scores all ``router_experts`` and the top
``num_experts_per_tok`` are chosen among all of them (``noaux_tc`` with
``n_group`` = ``topk_group`` = 1: no group is ruled out); only the experts
in ``experts_held`` add to the output (beside the shared experts), what the
absent ones would add is left out, and that partial result goes on.

Departures from the published model, each also in the configuration's
``assumed``: no auxiliary balance term and a zero selection bias (the
config defines neither a coefficient nor an update rule); normal(0, 0.02)
matrices and norms at 1 (no ``initializer_range``).  Two departures at the
cell's own size change no number (``make_block_grad(..., query_chunk=,
remat=)``; the CPU tests run without them): queries are taken
``query_chunk`` at a time, each chunk against every key under a dense mask,
and each layer is recomputed in the backward pass.

``quant="fp8"`` is the control of the output check: the operands of every
projection, of every expert product and of the head, and the gradient that
arrives at their outputs, rounded to 4 significant bits.  The router, the
norms and the attention core stay float32.

``fault`` computes a deliberately different model, for the tests and the
rehearsal: ``no_rotary_key`` (the shared rotary key left out of the
scores), ``rotate_half`` (the rope part's pairs taken as (i, i + D/2)),
``top_k_less`` (one expert fewer a token), ``no_route_scale``, ``capacity``
(each expert keeps its first tokens up to 1.25 x the even share and drops
the rest, as a capacity factor does).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.afmoe import (HIGHEST, _product, dropout_key,  # noqa: F401
                                       rms_norm, sgd_update, swiglu)

ATTENTION_PARTS = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")


def _is_dense(cfg, i):
    return i < cfg["first_k_dense_replace"]


def param_shapes(cfg):
    """name -> {part: shape}, in the program's construction order.
    Projections multiply as x @ W: (in, out)."""
    d, heads, r = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["kv_lora_rank"])
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    held, h = len(cfg["experts_held"]), cfg["moe_intermediate_size"]
    hs = cfg["n_shared_experts"] * h
    norm = {"weight": (d,)}
    shapes = {"embed": {"weight": (cfg["vocab_size"], d)}}
    for i in range(cfg["num_hidden_layers"]):
        shapes[f"layer{i}/norm1"] = norm
        shapes[f"layer{i}/attn"] = {
            "wq": (d, heads * (nope + rope)), "wkv_a": (d, r + rope),
            "kv_norm": (r,), "wkv_b": (r, heads * (nope + dv)),
            "wo": (heads * dv, d)}
        shapes[f"layer{i}/norm2"] = norm
        if _is_dense(cfg, i):
            w = cfg["intermediate_size"]
            shapes[f"layer{i}/ffn"] = {"w_gate": (d, w), "w_up": (d, w),
                                       "w_down": (w, d)}
        else:
            shapes[f"layer{i}/moe"] = {
                "router": (d, cfg["router_experts"]),
                "w_gate": (held, d, h), "w_up": (held, d, h),
                "w_down": (held, h, d), "shared_gate": (d, hs),
                "shared_up": (d, hs), "shared_down": (hs, d)}
    shapes["final_norm"] = norm
    shapes["head"] = {"weight": (d, cfg["vocab_size"])}
    return shapes


def init_params(key, cfg):
    """Normal(0, initializer_std) matrices, norm weights at 1, float32."""
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

def rotate_pairs(x, theta, fault=None):
    """Rotary positions on (T, heads, D): the neighbours (2i, 2i + 1) turn
    by t * theta^(-2i/D), and stay where they are."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if fault == "rotate_half":
        a, b = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _masked_softmax_pv(q_nope, q_pe, k_nope, k_pe, v, rows, fault):
    """Queries at positions ``rows``: q_nope (R, H, N), q_pe (R, H, P);
    keys k_nope (T, H, N) and the one rotary key k_pe (T, P) that every
    head shares; v (T, H, V).  Causal under a dense mask over all T keys."""
    t = k_nope.shape[0]
    s = jnp.einsum("rhd,thd->hrt", q_nope, k_nope, precision=HIGHEST)
    if fault != "no_rotary_key":
        s = s + jnp.einsum("rhd,td->hrt", q_pe, k_pe, precision=HIGHEST)
    s = s / (q_nope.shape[-1] + q_pe.shape[-1]) ** 0.5
    seen = jnp.arange(t)[None, :] <= rows[:, None]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hrt,thd->rhd", p, v, precision=HIGHEST)
    return o.reshape(o.shape[0], -1)


def attention(p, x, cfg, quant=None, fault=None, query_chunk=None):
    """x: (T, hidden) -> (T, hidden)."""
    t = x.shape[0]
    heads, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, theta = cfg["qk_nope_head_dim"], cfg["rope_theta"]
    q = _product(x, p["wq"], quant).reshape(t, heads, -1)
    down = _product(x, p["wkv_a"], quant)
    latent = rms_norm(down[:, :r], p["kv_norm"], cfg["rms_norm_eps"])
    kv = _product(latent, p["wkv_b"], quant).reshape(t, heads, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_nope, q_pe = q[..., :nope], rotate_pairs(q[..., nope:], theta, fault)
    k_pe = rotate_pairs(down[:, None, r:], theta, fault)[:, 0]
    if query_chunk is None or query_chunk >= t:
        o = _masked_softmax_pv(q_nope, q_pe, k_nope, k_pe, v, jnp.arange(t),
                               fault)
    else:
        chunk = jax.checkpoint(lambda qn, qp, rows: _masked_softmax_pv(
            qn, qp, k_nope, k_pe, v, rows, fault))
        cut = lambda a: a.reshape(t // query_chunk, query_chunk,
                                  *a.shape[1:])
        o = lax.map(lambda a: chunk(*a),
                    (cut(q_nope), cut(q_pe), cut(jnp.arange(t))))
        o = o.reshape(t, -1)
    return _product(o, p["wo"], quant)


def route(p, x, cfg, fault=None):
    """(chosen expert ids (T, k), their weights (T, k)), over all
    ``router_experts``.  The selection bias is zero (``assumed``), and with
    one group the group limit rules nothing out."""
    k = cfg["num_experts_per_tok"] - (1 if fault == "top_k_less" else 0)
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router"], precision=HIGHEST))
    _, idx = lax.top_k(scores, k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    if fault != "no_route_scale":
        w = w * cfg["routed_scaling_factor"]
    return idx, w


def expert_layer(p, x, cfg, quant=None, fault=None, experts_held=None,
                 choices=None, remat=False):
    """Shared(x) + sum over the chosen experts held of w_e * Expert_e(x).
    ``experts_held`` defaults to the configuration's; ``p['w_*'][j]`` is the
    j-th of them.  ``choices``: a list that is given the chosen ids.  The
    ``n_shared_experts`` shared experts are one SwiGLU of their joint
    width, which is the same sum."""
    held = cfg["experts_held"] if experts_held is None else experts_held
    idx, w = route(p, x, cfg, fault)
    if choices is not None:
        choices.append(idx)
    y = swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"], quant)

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

    # a loop over the experts held (a scan: one body to compile, not 16;
    # under ``remat`` an expert's activations are not kept for the others')
    y, _ = lax.scan(jax.checkpoint(add_expert) if remat else add_expert, y,
                    (jnp.asarray(list(held)), p["w_gate"], p["w_up"],
                     p["w_down"]))
    return y


def layer(params, i, h, cfg, quant=None, fault=None, query_chunk=None,
          choices=None, remat=False):
    eps, name = cfg["rms_norm_eps"], f"layer{i}"
    norm = lambda part, a: rms_norm(a, params[f"{name}/{part}"]["weight"],
                                    eps)
    h = h + attention(params[f"{name}/attn"], norm("norm1", h), cfg, quant,
                      fault, query_chunk)
    x = norm("norm2", h)
    if _is_dense(cfg, i):
        f = params[f"{name}/ffn"]
        return h + swiglu(x, f["w_gate"], f["w_up"], f["w_down"], quant)
    return h + expert_layer(params[f"{name}/moe"], x, cfg, quant, fault,
                            choices=choices, remat=remat)


def forward(params, ids, cfg, quant=None, fault=None, query_chunk=None,
            remat=False, choices=None):
    """ids: (T,) 1-based token ids -> (T, vocab) log-probabilities."""
    h = params["embed"]["weight"][ids.astype(jnp.int32) - 1]
    for i in range(cfg["num_hidden_layers"]):
        f = lambda p, h_, i=i: layer(p, i, h_, cfg, quant, fault,
                                     query_chunk, choices, remat)
        h = (jax.checkpoint(f) if remat else f)(params, h)
    h = rms_norm(h, params["final_norm"]["weight"], cfg["rms_norm_eps"])
    return jax.nn.log_softmax(_product(h, params["head"]["weight"], quant))


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

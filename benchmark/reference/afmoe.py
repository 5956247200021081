"""Plain reference for one chip's share of an afmoe decoder (Arcee
Trinity-Mini, ``config.json`` of arcee-ai/Trinity-Mini): forward, loss,
gradients and the SGD step in float32 ``jax.numpy`` at ``highest``
precision, written from the equations in the configuration's ``equations``
and not from the program.  Dense masks, a loop over the experts held, each
over all tokens, no kernel.

Nothing here imports the program.  Weights come from ``init_params(key)``,
the sequences from the harness's own records.  A block is one sequence:
attention ties a sequence's tokens together, and dropless routing keeps
tokens independent in the expert layers.

The share: the router scores all ``router_experts`` and the top
``num_experts_per_tok`` are chosen among all of them; only the experts in
``experts_held`` add to the output (beside the shared expert), what the
absent ones would add is left out, and that partial result goes on.

Two departures at the cell's own size, neither of which changes a number
(``make_block_grad(..., query_chunk=, remat=)``; the CPU tests run without
them): the (T, T) scores of a sequence of 8,192 are 8.6 GB a layer, so the
queries are taken ``query_chunk`` at a time, each chunk against every key
under a dense mask; and each layer is recomputed in the backward pass, or
one sequence's float32 activations do not fit beside the parameters.

``quant="fp8"`` is the control of the output check: the operands of every
projection, of every expert product and of the head, and the gradient that
arrives at their outputs, rounded to 4 significant bits (fp8 e4m3's
mantissa): the nearest precision below the bf16 compute the configuration
states.  The router, the norms and the attention core stay float32.

``fault`` computes a deliberately different model, for the tests and the
rehearsal: ``full_window`` (window layers see every earlier key),
``top_k_less`` (one expert fewer a token), ``no_route_scale``, ``capacity``
(each expert keeps its first tokens up to 1.25 x the even share and drops
the rest, as a capacity factor does).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
ATTENTION_PARTS = ("wq", "wk", "wv", "wg", "wo", "q_norm", "k_norm")


def _is_dense(cfg, i):
    return i < cfg["num_dense_layers"]


def param_shapes(cfg):
    """name -> {part: shape}, in the program's construction order.
    Projections multiply as x @ W: (in, out)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q_out = cfg["num_attention_heads"] * hd
    kv_out = cfg["num_key_value_heads"] * hd
    held, h = len(cfg["experts_held"]), cfg["moe_intermediate_size"]
    hs = cfg["num_shared_experts"] * h
    norm = {"weight": (d,)}
    shapes = {"embed": {"weight": (cfg["vocab_size"], d)}}
    for i in range(cfg["num_hidden_layers"]):
        shapes[f"layer{i}/norm1"] = norm
        shapes[f"layer{i}/attn"] = {
            "wq": (d, q_out), "wk": (d, kv_out), "wv": (d, kv_out),
            "wg": (d, q_out), "wo": (q_out, d), "q_norm": (hd,),
            "k_norm": (hd,)}
        shapes[f"layer{i}/norm2"] = norm
        shapes[f"layer{i}/norm3"] = norm
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
        shapes[f"layer{i}/norm4"] = norm
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


def dropout_key(seed, step):
    """The model has no dropout; the harness's loop asks for a key."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), step)


# -- the control's rounding ---------------------------------------------------

_STEPS = {"fp8": 16.0, "bf16": 256.0}     # 4 and 8 significant bits


def _rounded(x, quant):
    if quant is None:
        return x
    m, e = jnp.frexp(x)
    q = jnp.ldexp(jnp.round(m * _STEPS[quant]) / _STEPS[quant], e)
    return x + lax.stop_gradient(q - x)


@jax.custom_vjp
def _fp8_cotangent(y):
    return y


_fp8_cotangent.defvjp(lambda y: (y, None),
                      lambda _, g: (_rounded(g, "fp8"),))


def _product(x, w, quant):
    """x @ w as the configuration's precision sees it: the reference in
    float32, the control with both operands and the arriving gradient
    rounded."""
    y = jnp.matmul(_rounded(x, quant), _rounded(w, quant), precision=HIGHEST)
    return _fp8_cotangent(y) if quant == "fp8" else y      # bf16: forward


# -- the pieces ----------------------------------------------------------------

def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate(x, theta):
    """Rotary positions on (T, heads, D): pairs (i, i + D/2) turn by
    t * theta^(-2i/D) (rotate-half)."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _masked_softmax_pv(q, k, v, rows, window):
    """q: (R, Hq, D) queries at positions ``rows``; k, v: (T, Hk, D).
    A dense mask over all T keys: key j is seen by query i when j <= i and,
    under a window, i - j < window."""
    r, hq, d = q.shape
    t, hk, _ = k.shape
    q = q.reshape(r, hk, hq // hk, d)
    s = jnp.einsum("rhgd,thd->hgrt", q, k, precision=HIGHEST) / d ** 0.5
    i, j = rows[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (i - j < window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("hgrt,thd->rhgd", p, v,
                      precision=HIGHEST).reshape(r, hq * d)


def attention(p, x, cfg, kind, quant=None, fault=None, query_chunk=None):
    """x: (T, hidden) -> (T, hidden).  ``kind``: the layer's type."""
    t = x.shape[0]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    q = _product(x, p["wq"], quant).reshape(t, -1, hd)
    k = _product(x, p["wk"], quant).reshape(t, -1, hd)
    v = _product(x, p["wv"], quant).reshape(t, -1, hd)
    q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
    window = None
    if kind == "sliding_attention":
        q, k = rotate(q, cfg["rope_theta"]), rotate(k, cfg["rope_theta"])
        if fault != "full_window":
            window = cfg["sliding_window"]
    if query_chunk is None or query_chunk >= t:
        o = _masked_softmax_pv(q, k, v, jnp.arange(t), window)
    else:
        chunk = jax.checkpoint(
            lambda q_c, rows: _masked_softmax_pv(q_c, k, v, rows, window))
        o = lax.map(lambda a: chunk(*a),
                    (q.reshape(t // query_chunk, query_chunk, -1, hd),
                     jnp.arange(t).reshape(-1, query_chunk)))
        o = o.reshape(t, -1)
    o = o * jax.nn.sigmoid(_product(x, p["wg"], quant))
    return _product(o, p["wo"], quant)


def swiglu(x, w_gate, w_up, w_down, quant=None):
    hidden = jax.nn.silu(_product(x, w_gate, quant)) * _product(x, w_up,
                                                                quant)
    return _product(hidden, w_down, quant)


def route(p, x, cfg, fault=None):
    """(chosen expert ids (T, k), their weights (T, k)), over all
    ``router_experts``.  The selection bias is zero (``assumed``)."""
    k = cfg["num_experts_per_tok"] - (1 if fault == "top_k_less" else 0)
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router"], precision=HIGHEST))
    _, idx = lax.top_k(scores, k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["route_norm"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    if fault != "no_route_scale":
        w = w * cfg["route_scale"]
    return idx, w


def expert_layer(p, x, cfg, quant=None, fault=None, experts_held=None,
                 choices=None, remat=False):
    """Shared(x) + sum over the chosen experts held of w_e * Expert_e(x).
    ``experts_held`` defaults to the configuration's; ``p['w_*'][j]`` is the
    j-th of them.  ``choices``: a list that is given the chosen ids."""
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
    a = attention(params[f"{name}/attn"], norm("norm1", h), cfg,
                  cfg["layer_types"][i], quant, fault, query_chunk)
    h = h + norm("norm2", a)
    x = norm("norm3", h)
    if _is_dense(cfg, i):
        f = params[f"{name}/ffn"]
        m = swiglu(x, f["w_gate"], f["w_up"], f["w_down"], quant)
    else:
        m = expert_layer(params[f"{name}/moe"], x, cfg, quant, fault,
                         choices=choices, remat=remat)
    return h + norm("norm4", m)


def forward(params, ids, cfg, quant=None, fault=None, query_chunk=None,
            remat=False, choices=None):
    """ids: (T,) 1-based token ids -> (T, vocab) log-probabilities."""
    d = cfg["hidden_size"]
    h = params["embed"]["weight"][ids.astype(jnp.int32) - 1] * d ** 0.5
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


def sgd_update(params, velocity, grads, opt):
    """g' = g + wd * p; v = momentum * v + (1 - dampening) * g';
    p = p - lr * v."""
    tmap = jax.tree_util.tree_map
    velocity = tmap(
        lambda p, v, g: opt["momentum"] * v
        + (1.0 - opt["dampening"]) * (g + opt["weight_decay"] * p),
        params, velocity, grads)
    params = tmap(lambda p, v: p - opt["learning_rate"] * v, params,
                  velocity)
    return params, velocity

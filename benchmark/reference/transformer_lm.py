"""Plain reference for the decoder-only Transformer LM at the "big" widths
of Vaswani et al. (arXiv:1706.03762, Table 3) as tensor2tensor's
``transformer_big`` sets them: pre-LN blocks, ReLU feed-forward, sinusoidal
positions, one full causal forward over a whole sequence in float32
``jax.numpy`` at ``highest`` precision.  No cache, no pages, no batching.

Nothing here imports the program or takes anything it made: weights come
from ``init_params(key)``.

``quant="fp8"`` is the control of the output check: every matrix product
sees its two operands rounded to 4 significant bits (e4m3's mantissa; no
range clamp), the nearest precision below the bf16 operands that the
configuration states for the program's products.  It is explicit arithmetic
because XLA:TPU removes a float32 -> fp8 -> float32 convert pair.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def param_shapes(cfg):
    """name -> {part: shape}, in construction order.  Linear maps are
    stored (out, in) except the attention projections (in, out), as the
    equations below use them."""
    d, h, v = cfg["d_model"], cfg["ffn_hidden"], cfg["vocab_size"]
    shapes = {"embedding": {"weight": (d, v), "bias": (d,)}}
    for i in range(cfg["n_layers"]):
        shapes[f"layer{i}/ln1"] = {"weight": (d,), "bias": (d,)}
        shapes[f"layer{i}/attention"] = {
            "wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
            "bq": (d,), "bk": (d,), "bv": (d,), "bo": (d,)}
        shapes[f"layer{i}/ln2"] = {"weight": (d,), "bias": (d,)}
        shapes[f"layer{i}/ffn1"] = {"weight": (h, d), "bias": (h,)}
        shapes[f"layer{i}/ffn2"] = {"weight": (d, h), "bias": (d,)}
    shapes["ln_f"] = {"weight": (d,), "bias": (d,)}
    shapes["head"] = {"weight": (v, d), "bias": (v,)}
    return shapes


def init_params(key, cfg):
    """Unit-variance-preserving weights (normal, std 1/sqrt(fan_in); the
    embedding's columns std 1), biases of std 0.02, LayerNorm at (1, 0);
    float32, in one traced call (one draw, cut into the leaves)."""
    shapes = param_shapes(cfg)
    drawn = [(name, part, shape) for name, parts in shapes.items()
             for part, shape in parts.items()
             if not (name.endswith(("ln1", "ln2")) or name == "ln_f")]
    sizes = [int(np.prod(shape)) for _, _, shape in drawn]
    flat = jax.random.normal(key, (sum(sizes),), jnp.float32)
    params = {name: {} for name in shapes}
    start = 0
    for (name, part, shape), size in zip(drawn, sizes):
        if len(shape) == 1:
            std = 0.02
        elif name == "embedding":
            std = 1.0
        else:
            fan_in = shape[0] if name.endswith("attention") else shape[1]
            std = 1.0 / np.sqrt(fan_in)
        params[name][part] = (flat[start:start + size] * std).reshape(shape)
        start += size
    for name, parts in shapes.items():
        if name.endswith(("ln1", "ln2")) or name == "ln_f":
            params[name] = {"weight": jnp.ones(parts["weight"], jnp.float32),
                            "bias": jnp.zeros(parts["bias"], jnp.float32)}
    return params


def positions(t, d, base=10000.0):
    ang = np.arange(t)[:, None] * np.exp(
        np.arange(0, d, 2) * (-np.log(base) / d))
    pe = np.zeros((t, d), np.float32)
    pe[:, 0::2] = np.sin(ang)
    pe[:, 1::2] = np.cos(ang[:, :d // 2])
    return pe


def _fp8(x):
    """Round to 4 significant bits (1 implicit + 3 stored, as e4m3)."""
    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)


def _mm(a, b, quant):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _layernorm(x, p, eps):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["weight"] + p["bias"]


def hidden_states(params, tokens, cfg, quant=None):
    """(T,) token ids -> (T, d) final-LayerNorm outputs, full causal
    attention over the sequence."""
    t = tokens.shape[0]
    d, heads = cfg["d_model"], cfg["n_heads"]
    hd = d // heads
    eps = cfg["layernorm_eps"]
    emb = params["embedding"]
    x = emb["weight"].T[tokens] + emb["bias"] + jnp.asarray(positions(t, d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(cfg["n_layers"]):
        a = _layernorm(x, params[f"layer{i}/ln1"], eps)
        m = params[f"layer{i}/attention"]
        q = (_mm(a, m["wq"], quant) + m["bq"]).reshape(t, heads, hd)
        k = (_mm(a, m["wk"], quant) + m["bk"]).reshape(t, heads, hd)
        v = (_mm(a, m["wv"], quant) + m["bv"]).reshape(t, heads, hd)
        if quant == "fp8":
            q, k, v = _fp8(q), _fp8(k), _fp8(v)
        s = jnp.einsum("shd,thd->hst", q, k, precision=HIGHEST) / np.sqrt(hd)
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if quant == "fp8":
            p = _fp8(p)
        o = jnp.einsum("hst,thd->shd", p, v,
                       precision=HIGHEST).reshape(t, d)
        x = x + _mm(o, m["wo"], quant) + m["bo"]
        a2 = _layernorm(x, params[f"layer{i}/ln2"], eps)
        f1, f2 = params[f"layer{i}/ffn1"], params[f"layer{i}/ffn2"]
        h = jax.nn.relu(_mm(a2, f1["weight"].T, quant) + f1["bias"])
        x = x + _mm(h, f2["weight"].T, quant) + f2["bias"]
    return _layernorm(x, params["ln_f"], eps)


def make_row_check(cfg, n_pos, n_out):
    """Jitted (params, tokens (n_pos,), first, count) -> for the ``n_out``
    positions from ``first`` on (the positions whose next token was
    served): the reference's best logit, its logit of the token served
    there, and the logit of the token that the fp8 control puts first.
    Entries past ``count`` are zero-gap padding."""

    def row(params, tokens, first, count):
        def window_logits(quant):
            hs = hidden_states(params, tokens, cfg, quant)
            hs = lax.dynamic_slice_in_dim(hs, first, n_out, axis=0)
            head = params["head"]
            return _mm(hs, head["weight"].T, quant) + head["bias"]

        logits = window_logits(None)
        served = lax.dynamic_slice_in_dim(
            jnp.concatenate([tokens, jnp.zeros((n_out,), tokens.dtype)]),
            first + 1, n_out)
        best = logits.max(axis=-1)
        pick = lambda ids: jnp.take_along_axis(
            logits, ids[:, None], axis=1)[:, 0]
        control_ids = jnp.argmax(window_logits("fp8"), axis=-1)
        live = jnp.arange(n_out) < count
        gap = jnp.where(live, best - pick(served), 0.0)
        control_gap = jnp.where(live, best - pick(control_ids), 0.0)
        return gap, control_gap

    return jax.jit(row)

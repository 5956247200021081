"""The afmoe decoder (``models/afmoe.py`` and the modules it forced) against
the plain reference (``benchmark/reference/afmoe.py``) at a small size:
seeded weights, float32 policy."""
import copy
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from benchmark.program import (from_program_tree, leaf_dicts,
                               to_program_tree)
from benchmark.reference import afmoe as ref
from bigdl_tpu import tensor as bt
from bigdl_tpu.models.afmoe import AfmoeLM, afmoe_layer
from bigdl_tpu.nn.module import Context
from bigdl_tpu.obs import events

CFG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32,
    "router_experts": 16, "num_experts_per_tok": 4, "num_shared_experts": 1,
    "experts_held": [0, 1], "num_hidden_layers": 5, "num_dense_layers": 1,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention", "sliding_attention",
                    "sliding_attention"],
    "sliding_window": 8, "rope_theta": 10000, "rms_norm_eps": 1e-5,
    "route_norm": True, "route_scale": 2.826, "vocab_size": 50,
    "assumed": {"initializer_std": 0.02},
    "optimizer": {"learning_rate": 0.05, "momentum": 0.9, "dampening": 0.0,
                  "weight_decay": 0.0},
}
T = 32
TOL = 2e-5


@pytest.fixture(autouse=True)
def _float32_policy():
    before = bt.policy()
    bt.set_policy(bt.FP32)
    yield
    bt.set_policy(before)


def build(cfg=CFG):
    return AfmoeLM(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=cfg["layer_types"],
        num_dense_layers=cfg["num_dense_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["router_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        experts_held=cfg["experts_held"],
        sliding_window=cfg["sliding_window"], rope_theta=cfg["rope_theta"],
        rms_norm_eps=cfg["rms_norm_eps"], route_norm=cfg["route_norm"],
        route_scale=cfg["route_scale"])


def tokens(seed, n=2, t=T, vocab=CFG["vocab_size"]):
    rng = np.random.RandomState(seed)
    ids = rng.randint(1, vocab + 1, (n, t + 1)).astype(np.float32)
    return ids[:, :-1], ids[:, 1:]


def run(module, params, x, state=None):
    y, _ = module.apply(params, x, module.state() if state is None else state,
                        Context(training=True, key=jax.random.PRNGKey(0)))
    return y


def close(a, b, tol=TOL):
    a, b = np.asarray(a), np.asarray(b)
    scale = max(float(np.abs(b).max()), 1e-6)
    assert float(np.abs(a - b).max()) <= tol * scale, \
        (float(np.abs(a - b).max()), scale)


def test_rms_norm_matches_reference():
    m = nn.RMSNorm(24, eps=1e-5)
    w = jax.random.normal(jax.random.PRNGKey(1), (24,)) + 1.0
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 5, 24)) * 3.0
    params = {"~": {"weight": w}}
    f = lambda p, x_: jnp.sum(jnp.sin(run(m, p, x_)))
    g = lambda w_, x_: jnp.sum(jnp.sin(ref.rms_norm(x_, w_, 1e-5)))
    close(run(m, params, x), ref.rms_norm(x, w, 1e-5))
    gp, gx = jax.grad(f, (0, 1))(params, x)
    rw, rx = jax.grad(g, (0, 1))(w, x)
    close(gp["~"]["weight"], rw)
    close(gx, rx)


def test_rotary_matches_reference():
    from bigdl_tpu.nn.attention import rotary
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 19, 3, 16))
    for b in range(2):
        close(rotary(x, 10000.0)[b], ref.rotate(x[b], 10000.0))
    # position 0 is left as it is, and a rotation keeps every pair's norm
    close(rotary(x)[:, 0], x[:, 0])
    close(jnp.linalg.norm(rotary(x), axis=-1), jnp.linalg.norm(x, axis=-1))


def _attention_pair(kind, t, block):
    cfg = CFG
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    m = nn.GatedGroupedQueryAttention(
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"], window=window,
        rotary_base=cfg["rope_theta"] if window else None)
    m.block = block
    key = jax.random.PRNGKey(7)
    own = {}
    for n, (name, leaf) in enumerate(m.params()["~"].items()):
        std = 1.0 if leaf.ndim == 1 else 0.2
        own[name] = std * jax.random.normal(jax.random.fold_in(key, n),
                                            leaf.shape)
    x = jax.random.normal(jax.random.fold_in(key, 99),
                          (2, t, cfg["hidden_size"]))
    return m, own, x


def _one_key_head_fits(monkeypatch, b, t, block, g, d, dv, resident=True):
    """Steer ``_walk_plan`` as the chip's VMEM would at a cell's size: room
    for one key head's scores and dq block, with (``resident``) or without
    that head's whole dk and dv, so a backward of several key heads walks
    in several passes."""
    from bigdl_tpu.parallel import ring_attention as ra
    t += -t % block
    monkeypatch.setattr(ra, "_WALK_VMEM_BYTES", (
        b * g * block * block * 8 + b * block * g * d * 4
        + resident * b * t * (d + dv) * 4))


@pytest.mark.parametrize("kind,t,block,passes", [
    ("sliding_attention", 37, 8, 1),    # T several windows, not a multiple
    ("sliding_attention", 32, 16, 1),
    ("full_attention", 37, 8, 1),
    ("full_attention", 24, 512, 1),     # one block
    ("sliding_attention", 37, 8, 2),    # a key head a pass of the backward
    ("sliding_attention", 37, 4, 2),    # the window longer than two blocks
    ("full_attention", 37, 8, 2),
])
def test_attention_matches_reference(kind, t, block, passes, monkeypatch):
    if passes > 1:
        _one_key_head_fits(monkeypatch, 2, t, block, 2, CFG["head_dim"],
                           CFG["head_dim"])
    m, own, x = _attention_pair(kind, t, block)
    c = jax.random.normal(jax.random.PRNGKey(11), x.shape)
    f = lambda p, x_: jnp.sum(run(m, {"~": p}, x_) * c)
    g = lambda p, x_: sum(
        jnp.sum(ref.attention(p, x_[b], CFG, kind) * c[b]) for b in range(2))
    for b in range(2):
        close(run(m, {"~": own}, x)[b], ref.attention(own, x[b], CFG, kind))
    from bigdl_tpu.parallel.ring_attention import walk_report
    with walk_report() as cores:
        gp, gx = jax.jit(jax.grad(f, (0, 1)))(own, x)
    assert [core["passes"] for core in cores] == [passes]
    rp, rx = jax.grad(g, (0, 1))(own, x)
    close(gx, rx)
    for name in ref.ATTENTION_PARTS:
        close(gp[name], rp[name])


@pytest.mark.parametrize("window,t,block,hq,hk,d,dv,walk", [
    (None, 37, 8, 4, 4, 24, 16, None),  # a latent-attention head: 24 and 16
    (None, 24, 512, 4, 2, 24, 16, None),    # one block, grouped heads
    (8, 37, 8, 4, 2, 24, 16, None),
    (8, 32, 16, 4, 4, 16, 24, None),    # the value head the wider one
    # the backward in passes of one key head (``walk``: whether the pass's
    # whole dk and dv were sized to fit): one-to-one and grouped heads,
    # full, a window shorter and one longer than two blocks
    (None, 37, 8, 6, 6, 24, 16, True),
    (None, 37, 8, 6, 6, 24, 16, False),
    (None, 37, 8, 12, 3, 24, 16, True),
    (5, 37, 8, 4, 4, 24, 16, True),
    (5, 37, 8, 8, 2, 24, 16, False),
    (20, 37, 8, 6, 6, 24, 16, False),
    (20, 37, 8, 8, 2, 16, 24, True),
])
def test_blockwise_attention_takes_a_value_head_size_of_its_own(
        window, t, block, hq, hk, d, dv, walk, monkeypatch):
    """v of another head size than q and k, against a dense float32
    softmax: the output and all three gradients, each in its operand's own
    shape; the scale is the score head's."""
    from bigdl_tpu.parallel import ring_attention as ra
    from bigdl_tpu.parallel.ring_attention import blockwise_attention
    if walk is not None:
        _one_key_head_fits(monkeypatch, 2, t, block, hq // hk, d, dv, walk)
    key = lambda n: jax.random.fold_in(jax.random.PRNGKey(17), n)
    q = jax.random.normal(key(0), (2, t, hq, d))
    k = jax.random.normal(key(1), (2, t, hk, d))
    v = jax.random.normal(key(2), (2, t, hk, dv))
    c = jax.random.normal(key(3), (2, t, hq, dv))

    def dense(q, k, v):
        s = jnp.einsum("bqhgd,bkhd->bhgqk",
                       q.reshape(2, t, hk, hq // hk, d), k) / d ** 0.5
        i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        seen = (j <= i) if window is None else (j <= i) & (i - j < window)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, v).reshape(2, t, hq, dv)

    out = blockwise_attention(q, k, v, window, block)
    assert out.shape == (2, t, hq, dv) and out.dtype == jnp.float32
    close(out, dense(q, k, v))
    with ra.walk_report() as cores:
        got = jax.jit(jax.grad(lambda *a: jnp.sum(
            blockwise_attention(*a, window, block) * c), (0, 1, 2)))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * c), (0, 1, 2))(q, k, v)
    for a, b, operand in zip(got, want, (q, k, v)):
        assert a.shape == operand.shape
        close(a, b)
    core, = cores
    assert core["passes"] == (1 if walk is None else hk)
    assert core["sliced_in_vmem"] == (walk is not False)


@pytest.mark.parametrize("shape,heads,passes,pairs,resident", [
    # kanana-2-30b-a3b's core, trinity-mini's full and window cores
    ((2, 8192, 512, 32, 1, 192, 128, None), 4, 8, 136, True),
    ((2, 8192, 512, 4, 8, 128, 128, None), 16, 2, 136, True),
    ((2, 8192, 512, 4, 8, 128, 128, 2048), 16, 2, 70, True),
    # a sequence whose dk and dv no pass holds: dq's block alone is sized
    ((2, 131072, 512, 32, 1, 192, 128, None), 16, 2, 32896, False),
    # six key heads where four would fit: a pass takes a divisor, three
    ((2, 8192, 512, 6, 1, 192, 128, None), 3, 2, 136, True),
])
def test_the_walk_is_sized_from_the_shapes(shape, heads, passes, pairs,
                                           resident):
    """``_walk_plan`` at the cells' sizes and at two that bend the rule:
    query heads a pass, passes (always a divisor of the key heads), visible
    pairs, and whether a pass's whole dk and dv were counted in."""
    from bigdl_tpu.parallel.ring_attention import _walk_plan
    b, t, block, hk, g, d, dv, window = shape
    plan = _walk_plan(*shape)
    assert (plan["heads_a_pass"], plan["passes"], plan["pairs_a_pass"],
            plan["sliced_in_vmem"]) == (heads, passes, pairs, resident)
    assert plan["key_heads_a_pass"] * plan["passes"] == hk
    assert plan["carried"] == "dq"
    assert plan["carry_bytes"] == b * block * heads * d * 4
    assert plan["sliced_bytes"] == b * t * (heads // g) * (d + dv) * 4
    assert plan["slice_bytes_a_pair"] == \
        2 * b * block * (heads // g) * (d + dv) * 4


def test_the_gate_is_the_gated_modules_alone():
    """One module with an optional gate: the gated class keeps its
    parameter names and their order (``wg`` after ``wv``: the reference's
    mapping is by construction order), the ungated one has no ``wg``, and
    the gated output is the ungated heads' (read through an identity output
    projection) times sigmoid(x Wg), through Wo."""
    d, heads, kv, hd = 32, 4, 2, 8
    gated = nn.GatedGroupedQueryAttention(d, heads, kv, hd, window=8,
                                          rotary_base=1e4)
    plain = nn.GroupedQueryAttention(d, heads, kv, hd, window=8,
                                     rotary_base=1e4)
    assert list(gated.params()["~"]) == list(ref.ATTENTION_PARTS[:5]) + [
        "q_norm", "k_norm"] == ["wq", "wk", "wv", "wg", "wo", "q_norm",
                                "k_norm"]
    assert list(plain.params()["~"]) == ["wq", "wk", "wv", "wo", "q_norm",
                                         "k_norm"]
    assert set(gated.quant_spec) == {"wq", "wk", "wv", "wg", "wo"}
    assert set(plain.quant_spec) == {"wq", "wk", "wv", "wo"}
    assert repr(gated).startswith("GatedGroupedQueryAttention(32, heads=4/2x8")
    key = lambda n: jax.random.fold_in(jax.random.PRNGKey(23), n)
    own = {name: (1.0 if leaf.ndim == 1 else 0.3) * jax.random.normal(
        key(n), leaf.shape)
        for n, (name, leaf) in enumerate(gated.params()["~"].items())}
    x = jax.random.normal(key(99), (2, 19, d))
    joined = run(plain, {"~": dict(
        {k: v for k, v in own.items() if k != "wg"}, wo=jnp.eye(d))}, x)
    close(run(gated, {"~": own}, x),
          (joined * jax.nn.sigmoid(x @ own["wg"])) @ own["wo"])
    # the model's tree: every attention leaf gated, in the reference's order
    leaves = [leaf for leaf in leaf_dicts(build().params()) if "wq" in leaf]
    assert len(leaves) == CFG["num_hidden_layers"] and all(
        tuple(leaf) == ref.ATTENTION_PARTS for leaf in leaves)


def test_window_layer_ignores_keys_outside_the_window():
    m, own, x = _attention_pair("sliding_attention", 40, 8)
    y = run(m, {"~": own}, x)
    moved = x.at[:, :8].add(5.0)        # keys 0..7: unseen from query 15 on
    y2 = run(m, {"~": own}, moved)
    close(y2[:, 15:], y[:, 15:])
    assert float(jnp.abs(y2[:, :15] - y[:, :15]).max()) > 1e-3


def _moe(held, chunk=None, cfg=CFG):
    m = nn.DroplessMoE(cfg["hidden_size"], cfg["moe_intermediate_size"],
                       cfg["router_experts"], cfg["num_experts_per_tok"],
                       experts_held=held, route_scale=cfg["route_scale"],
                       shared_hidden=cfg["moe_intermediate_size"],
                       chunk_rows=chunk)
    return m


def _whole_moe_params(key, cfg=CFG, router_std=1.0):
    d, h, e = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["router_experts"])
    k = lambda n: jax.random.fold_in(key, n)
    return {"router": router_std * jax.random.normal(k(0), (d, e)),
            "w_gate": 0.3 * jax.random.normal(k(1), (e, d, h)),
            "w_up": 0.3 * jax.random.normal(k(2), (e, d, h)),
            "w_down": 0.3 * jax.random.normal(k(3), (e, h, d)),
            "shared_gate": 0.3 * jax.random.normal(k(4), (d, h)),
            "shared_up": 0.3 * jax.random.normal(k(5), (d, h)),
            "shared_down": 0.3 * jax.random.normal(k(6), (h, d))}


def _share(whole, held):
    take = jnp.asarray(list(held))
    return dict(whole, w_gate=whole["w_gate"][take],
                w_up=whole["w_up"][take], w_down=whole["w_down"][take])


@pytest.mark.parametrize("held,chunk", [
    ((0, 1), None), ((3, 9, 4, 15), 16), (tuple(range(16)), 24)])
def test_expert_layer_matches_reference(held, chunk):
    whole = _whole_moe_params(jax.random.PRNGKey(5))
    own = _share(whole, held)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, T, CFG["hidden_size"]))
    m = _moe(held, chunk)
    c = jax.random.normal(jax.random.PRNGKey(8), x.shape)
    f = lambda p, x_: jnp.sum(run(m, {"~": p}, x_) * c)
    g = lambda p, x_: jnp.sum(ref.expert_layer(
        p, x_.reshape(-1, x_.shape[-1]), CFG,
        experts_held=held).reshape(x_.shape) * c)
    close(run(m, {"~": own}, x), ref.expert_layer(
        own, x.reshape(-1, x.shape[-1]), CFG,
        experts_held=held).reshape(x.shape))
    gp, gx = jax.jit(jax.grad(f, (0, 1)))(own, x)
    rp, rx = jax.grad(g, (0, 1))(own, x)
    close(gx, rx)
    for name in own:
        close(gp[name], rp[name])


def test_the_shares_add_up_to_the_whole_layer():
    """The 8 shares' outputs, the shared expert counted once, sum to the
    uncut reference's output for the whole layer."""
    whole = _whole_moe_params(jax.random.PRNGKey(15))
    x = jax.random.normal(jax.random.PRNGKey(16), (T, CFG["hidden_size"]))
    shared = ref.swiglu(x, whole["shared_gate"], whole["shared_up"],
                        whole["shared_down"])
    total = shared
    for chip in range(8):
        held = (2 * chip, 2 * chip + 1)
        y = run(_moe(held), {"~": _share(whole, held)}, x[None])[0]
        total = total + (y - shared)
    uncut = ref.expert_layer(whole, x, CFG, experts_held=range(16))
    close(total, uncut, 5e-5)


def test_no_token_is_dropped_under_total_imbalance():
    """A router that sends every token to one held expert: that expert
    gets all T assignments (16 times the even share) and none is lost."""
    whole = _whole_moe_params(jax.random.PRNGKey(25), router_std=0.01)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(26),
                                  (T, CFG["hidden_size"])))
    whole["router"] = whole["router"].at[:, 1].set(1.0)
    held = (0, 1)
    m = _moe(held, chunk=8)
    y, state = m.apply({"~": _share(whole, held)}, x[None], m.state(),
                       Context(training=True))
    close(y[0], ref.expert_layer(_share(whole, held), x, CFG,
                                 experts_held=held))
    assert float(state["~"]["tap_expert_max"]) == T
    dropped = ref.expert_layer(_share(whole, held), x, CFG,
                               fault="capacity", experts_held=held)
    assert float(jnp.abs(dropped - y[0]).max()) > 1e-2


def test_route_bias_enters_the_choice_only():
    whole = _whole_moe_params(jax.random.PRNGKey(35))
    held = tuple(range(16))
    x = jax.random.normal(jax.random.PRNGKey(36), (1, T, CFG["hidden_size"]))
    m = _moe(held)
    state = m.state()
    y0 = run(m, {"~": whole}, x, state)
    biased = {"~": dict(state["~"], route_bias=jnp.zeros((16,)).at[5].set(
        10.0))}
    y1, ns = m.apply({"~": whole}, x, biased, Context(training=True))
    assert float(jnp.abs(y1 - y0).max()) > 1e-3      # expert 5 is chosen
    # ... with its own score as the weight, and the bias takes no gradient
    assert float(ns["~"]["tap_assignments_held"]) == T * 4
    from bigdl_tpu.parallel.moe import sigmoid_topk_routing
    idx, w = sigmoid_topk_routing(x[0], whole["router"],
                                  biased["~"]["route_bias"], 4, True, 1.0)
    assert bool((idx == 5).any(axis=-1).all())
    close(w.sum(axis=-1), jnp.ones((T,)))


_CHOSEN = ((0, 1, 2, 3), (2, 3, 4, 5), (8, 9, 10, 11))


def _typed_tokens(counts):
    """Tokens of three kinds and a router that knows them: a token of kind
    f chooses the four experts ``_CHOSEN[f]``, so a share holds exactly
    sum(counts[f] * |_CHOSEN[f] & held|) assignments."""
    d = CFG["hidden_size"]
    kinds = np.repeat(np.arange(3), counts)
    kinds = kinds[np.random.RandomState(4).permutation(len(kinds))]
    x = 0.05 * jax.random.normal(jax.random.PRNGKey(56), (len(kinds), d))
    x = x.at[jnp.arange(len(kinds)), kinds].add(1.0)
    whole = _whole_moe_params(jax.random.PRNGKey(55), router_std=0.3)
    for f, chosen in enumerate(_CHOSEN):
        whole["router"] = whole["router"].at[f, list(chosen)].set(3.0)
    return whole, x[None]


# per case: tokens of each kind, the experts held, ``chunk_rows``, the
# assignments that leaves: with (1, 2) held a token of kind 0 leaves 2, of
# kind 1 one, of kind 2 none.  A chunk of 24 in 2, 3, 4, 6 or 12 steps:
# passes over multiples of 12, 8, 6, 4 or 2 rows.
PASS_CASES = {
    "nothing_held": ((0, 0, 40), (1, 2), 24, 0),
    "a_multiple_of_every_step": ((8, 8, 14), (1, 2), 24, 24),
    "one_row_past_a_step": ((3, 7, 20), (1, 2), 24, 13),
    "one_row_under_a_step": ((3, 5, 20), (1, 2), 24, 11),
    "two_chunks": ((13, 5, 10), (1, 2), 24, 31),
    "top_k_larger_than_the_experts_held": ((0, 29, 4), (2,), 24, 29),
}


@pytest.mark.parametrize("case", list(PASS_CASES))
def test_a_pass_runs_over_the_held_rows(case, monkeypatch):
    """In however many steps a chunk's pass may shorten, the layer's
    output and every gradient are the same bit for bit and agree with the
    reference; ``rows_moved`` is each chunk's smallest step that holds its
    assignments."""
    from bigdl_tpu.obs.taps import module_counters
    from bigdl_tpu.parallel.moe import pass_rows
    counts, held, chunk_rows, assignments = PASS_CASES[case]
    whole, x = _typed_tokens(counts)
    own = _share(whole, held)
    c = jax.random.normal(jax.random.PRNGKey(58), x.shape)
    # a share cannot get more than every token's choices among its experts
    chunk = min(chunk_rows, x.shape[1] * min(4, len(held)))

    def through(steps):
        monkeypatch.setattr(nn.DroplessMoE, "STEPS_OF_CHUNK", steps)
        m = _moe(held, chunk_rows)

        def f(p, x_):
            y, state = m.apply({"~": p}, x_, m.state(),
                               Context(training=True))
            return jnp.sum(y * c), (y, module_counters(state))

        (_, (y, counters)), grads = jax.jit(jax.value_and_grad(
            f, (0, 1), has_aux=True))(own, x)
        return (y, grads), {k: float(v) for k, v in counters.items()}

    first, _ = through(1)               # every pass over the whole chunk
    for steps in (2, 3, 4, 6, 12):
        got, counters = through(steps)
        assert counters["assignments_held/0"] == assignments
        assert counters["rows_moved/0"] == sum(
            min(n for n in pass_rows(chunk, steps)
                if n >= min(chunk, assignments - start))
            for start in range(0, assignments, chunk))
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(first)):
            assert np.array_equal(a, b)
    g = lambda p, x_: jnp.sum(ref.expert_layer(
        p, x_[0], CFG, experts_held=held) * c[0])
    y, (gp, gx) = first
    close(y[0], ref.expert_layer(own, x[0], CFG, experts_held=held))
    rp, rx = jax.grad(g, (0, 1))(own, x)
    close(gx, rx)
    for name in own:
        close(gp[name], rp[name])


def _laid_in(model, cfg, key):
    p0 = ref.init_params(key, cfg)
    names = list(ref.param_shapes(cfg))
    model.load_params(to_program_tree(model.params(), p0, names))
    return p0, names


def _reference_loss_and_grad(p, ids, targets, cfg=CFG, **kw):
    block = ref.make_block_grad(cfg, **kw)
    outs = [block(p, jnp.asarray(ids[b]), jnp.asarray(targets[b]))
            for b in range(len(ids))]
    loss = sum(o[0] for o in outs) / len(outs)
    grad = jax.tree_util.tree_map(lambda *g: sum(g) / len(outs),
                                  *[o[1] for o in outs])
    return float(loss), grad


def test_whole_model_matches_reference():
    model = build()
    p0, names = _laid_in(model, CFG, jax.random.PRNGKey(42))
    ids, targets = tokens(0)
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), True)
    for b in range(2):
        close(run(model, model.params(), ids)[b],
              ref.forward(p0, jnp.asarray(ids[b]), CFG), 1e-4)

    def loss(p):
        return crit.apply_loss(run(model, p, ids), targets)

    value, grads = jax.jit(jax.value_and_grad(loss))(model.params())
    ref_loss, ref_grad = _reference_loss_and_grad(p0, ids, targets)
    assert abs(float(value) - ref_loss) < 1e-5 * ref_loss
    got = from_program_tree(grads, names)
    for name in names:
        for part in ref_grad[name]:
            close(got[name][part], ref_grad[name][part], 2e-4)


def _attention_layer(kind):
    m, own, _ = _attention_pair(kind, T, 8)
    return m, {"~": own}


def _expert_branch():
    # the model's second branch: the closing norm needs the experts' sum
    d = CFG["hidden_size"]
    m = nn.Sequential(nn.RMSNorm(d), _moe((0, 1), 2 * T), nn.RMSNorm(d))
    return m, None


def _decoder_layer():
    m = afmoe_layer(CFG["hidden_size"], 4, 2, 16, _moe((0, 1), 2 * T), 8,
                    10000.0, 1e-5).modules[0]
    return m, None


def _unmarked():
    return nn.Sequential(nn.RMSNorm(CFG["hidden_size"]),
                         nn.GatedLinearUnit(CFG["hidden_size"], 12)), None


# per case: the builder; loops (``while``) and grouped products
# (``ragged_dot_general``) in the jaxpr of the gradient of the bare layer,
# which are ``nn.Recompute``'s too, and under ``jax.checkpoint`` with no
# policy (the ``Recompute`` of PR 28, ``set_gradient_checkpointing``); what
# ``nn.Recompute`` keeps, label -> shape.  A core is a ``scan`` over query
# blocks around a ``while`` over key blocks: forward, its recomputation,
# backward = 3, of which Recompute drops the recomputation; the chunks are
# walked by one loop for each step a pass may shorten to (``_STEPS``), each
# with 3 products forward and 9 in its backward rule (3 again + 6).  Of the
# routing ``_moe((0, 1), 2 * T)`` decides for B2 * T tokens: their four
# choices, the 2 * B2 * T assignments two held experts can get, in chunks of
# 2 * T, and the two counts (int32, a label carries its dtype).
B2 = 2
_STEPS = nn.DroplessMoE.STEPS_OF_CHUNK
_CORE = {"attention_out": ("f32", (B2, T, 2, 2, 16)),
         "attention_lse": ("f32", (B2, 2, 2, T))}
_SUM = {"route_idx": ("i32", (B2 * T, 4)),
        "route_order": ("i32", (2 * B2 * T,)), "route_sizes": ("i32", (2,)),
        "experts_out": ("f32", (B2 * T, 64))}
RECOMPUTE_CASES = {
    "window": (lambda: _attention_layer("sliding_attention"),
               (2, 0), (3, 0), _CORE),
    "full": (lambda: _attention_layer("full_attention"),
             (2, 0), (3, 0), _CORE),
    "experts": (_expert_branch, (2 * _STEPS, 12 * _STEPS),
                (3 * _STEPS, 15 * _STEPS), _SUM),
    "layer": (_decoder_layer, (2 + 2 * _STEPS, 12 * _STEPS),
              (3 + 3 * _STEPS, 15 * _STEPS), {**_CORE, **_SUM}),
    "unmarked": (_unmarked, (0, 0), (0, 0), {}),
}


def _variants(build_layer):
    """The loss through one layer, bare, under a plain ``jax.checkpoint``
    and under ``nn.Recompute``: each from a closure of its own
    (``jax.checkpoint`` caches a function's trace), all on the parameters
    of the first."""
    layer, params = build_layer()
    params = layer.params() if params is None else params
    wrapped = nn.Recompute(layer)
    plain = jax.checkpoint(lambda p, x: run(layer, p, x))
    return params, {
        "bare": lambda p, x: jnp.sum(run(layer, p, x) ** 2),
        "checkpoint": lambda p, x: jnp.sum(plain(p, x) ** 2),
        "recompute": lambda p, x: jnp.sum(
            run(wrapped, {"0": p, "~": {}}, x) ** 2),
    }


def _gradient_text(f, *args):
    return str(jax.make_jaxpr(jax.grad(f))(*args))


def _loops_and_products(text):
    return (len(re.findall(r"\bwhile\[", text)),
            len(re.findall(r"\bragged_dot_general\[", text)))


@pytest.mark.parametrize("case", list(RECOMPUTE_CASES))
def test_recompute_changes_nothing(case, capsys):
    """``nn.Recompute`` gives the gradient of a plain ``jax.checkpoint``
    bit for bit, runs the loops of the bare layer and no more, and keeps
    the marked arrays and nothing else of the layer's inside."""
    build_layer, loops, _, keeps = RECOMPUTE_CASES[case]
    params, f = _variants(build_layer)
    x = jax.random.normal(jax.random.PRNGKey(1), (B2, T, CFG["hidden_size"]))
    close(f["recompute"](params, x), f["bare"](params, x))
    with nn.containers.kept_report() as report:
        got = jax.grad(f["recompute"])(params, x)
    for a, b, c in zip(*[jax.tree_util.tree_leaves(g) for g in (
            got, jax.grad(f["checkpoint"])(params, x),
            jax.grad(f["bare"])(params, x))]):
        assert np.array_equal(a, b)
        close(a, c)
    text = _gradient_text(f["recompute"], params, x)
    assert "checkpoint" in text or "remat" in text
    assert _loops_and_products(text) == loops
    assert report == {"layers": 1, "kept": {
        label: 4 * int(np.prod(shape))
        for label, (_, shape) in keeps.items()}}
    # the arrays the backward pass is handed: the layer's input, the
    # parameters, constants of the routing, and the marked arrays
    jax.ad_checkpoint.print_saved_residuals(f["recompute"], params, x)
    inside = [line.split()[0] for line in capsys.readouterr().out.split("\n")
              if " from the argument " not in line and line.strip()
              and "from a constant" not in line and "<lambda>" not in line]
    shapes = sorted("%s[%s]" % (dtype, ",".join(map(str, shape)))
                    for dtype, shape in keeps.values())
    assert sorted(inside) == shapes


@pytest.mark.parametrize("case", list(RECOMPUTE_CASES))
@pytest.mark.parametrize("how", ["bare", "checkpoint"])
def test_the_mark_is_inert_outside_a_recompute(case, how):
    """Bare, and under a ``jax.checkpoint`` with no policy (what
    ``set_gradient_checkpointing`` and the pipeline stages build), a marked
    layer's gradient holds the loops and products it held before the mark:
    a plain checkpoint still runs every loop a third time."""
    build_layer, bare, checkpoint, _ = RECOMPUTE_CASES[case]
    params, f = _variants(build_layer)
    x = jax.random.normal(jax.random.PRNGKey(1), (B2, T, CFG["hidden_size"]))
    with nn.containers.kept_report() as report:
        counted = _loops_and_products(_gradient_text(f[how], params, x))
    assert counted == {"bare": bare, "checkpoint": checkpoint}[how]
    assert report == {"layers": 0, "kept": {}}


def test_the_mark_is_not_in_a_step_without_recompute():
    """The Inception train step, lowered at a toy batch: no ``kept/`` name
    and no ``name`` operation at all (the step's text is PR 28's)."""
    from bigdl_tpu.models.inception import Inception_v1
    from bigdl_tpu.optim import LocalOptimizer
    from bigdl_tpu.utils.table import T as Tbl
    model = Inception_v1(1000)
    opt = LocalOptimizer(model, None, nn.ClassNLLCriterion())
    opt.set_state(Tbl(learningRate=0.01, momentum=0.9))
    step = opt._build_step()
    shape = jax.ShapeDtypeStruct
    like = lambda t: jax.tree_util.tree_map(
        lambda a: shape(a.shape, a.dtype), t)
    params = model.params()
    log = events.configure(None, ring=100)
    try:
        text = step.jitted.lower(
            like(params), like(model.state()),
            like(opt.optim_method.init_state(params)),
            shape((2, 3, 224, 224), jnp.float32), shape((2, 1), jnp.float32),
            shape((), jnp.float32), like(jax.random.PRNGKey(0)),
            opt._lr_scales_arg).as_text(debug_info=True)
        logged = [e["type"] for e in log.ring_events()]
    finally:
        events.configure(None)
    assert "kept/" not in text and "recompute" not in logged
    assert len(text) > 100000


def test_the_step_logs_what_its_recomputes_keep():
    """A toy ``AfmoeLM`` through ``Optimizer``: tracing the step writes one
    ``recompute`` event with every label's bytes over the five layers."""
    from bigdl_tpu.dataset import DataSet, Sample
    from bigdl_tpu.dataset.transformer import SampleToBatch
    from bigdl_tpu.optim import SGD, Optimizer
    from bigdl_tpu.optim import trigger as Trigger

    ids, targets = tokens(5, n=4)
    log = events.configure(None, ring=1000)
    try:
        Optimizer(build(),
                  DataSet.array([Sample(ids[i], targets[i])
                                 for i in range(4)]) >> SampleToBatch(2),
                  nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), True),
                  optim_method=SGD(),
                  end_trigger=Trigger.max_iteration(2)).optimize()
        logged = log.ring_events()
    finally:
        events.configure(None)
    kept = [e for e in logged if e["type"] == "recompute"]
    assert len(kept) == 1 and events.validate_event(kept[0])
    heads, d, hidden = (CFG["num_attention_heads"], CFG["head_dim"],
                        CFG["hidden_size"])
    assert kept[0]["layers"] == 5
    # an expert layer's routing of 2 * T tokens: four choices a token,
    # 2 * 2 * T assignments that its two held experts can get (one chunk),
    # two counts, all int32
    assert kept[0]["kept"] == {
        "attention_out": 5 * 2 * T * heads * d * 4,
        "attention_lse": 5 * 2 * T * heads * 4,
        "route_idx": 4 * 2 * T * 4 * 4, "route_order": 4 * 2 * 2 * T * 4,
        "route_sizes": 4 * 2 * 4,
        "experts_out": 4 * 2 * T * hidden * 4}
    types = [e["type"] for e in logged]
    assert types.index("run_start") < types.index("recompute") \
        < types.index("step")
    # and one ``attention_walk`` event: what each core's backward carries,
    # as the shapes imply (2 key heads of 2 query heads each: one pass)
    walk = [e for e in logged if e["type"] == "attention_walk"]
    assert len(walk) == 1 and events.validate_event(walk[0])
    assert types.index("recompute") < types.index("attention_walk") \
        < types.index("step")
    cores = walk[0]["cores"]
    assert len(cores) == 5
    for core in cores:
        assert (core["carried"], core["heads_a_pass"], core["passes"]) == \
            ("dq", heads, 1)
        assert core["pairs_a_pass"] == 1        # T is one block here
        assert core["carry_bytes"] == 2 * T * heads * d * 4
        assert core["sliced_bytes"] == 2 * T * 2 * (d + d) * 4


def test_three_steps_through_the_optimizer_match_reference():
    from bigdl_tpu.dataset import DataSet, Sample
    from bigdl_tpu.dataset.transformer import SampleToBatch, Transformer
    from bigdl_tpu.obs import events
    from bigdl_tpu.optim import SGD, Optimizer
    from bigdl_tpu.optim import trigger as Trigger
    from bigdl_tpu.utils.table import T as Tbl

    model = build()
    p0, names = _laid_in(model, CFG, jax.random.PRNGKey(43))
    ids, targets = tokens(3, n=6)
    samples = [Sample(ids[i], targets[i]) for i in range(6)]
    seen = []

    class Tap(Transformer):             # which sequences each batch held
        def __call__(self, iterator):
            for batch in iterator:
                seen.append([int(np.flatnonzero(
                    (ids == row).all(axis=1))[0])
                    for row in np.asarray(batch.data)])
                yield batch

    opt_cfg = CFG["optimizer"]
    log = events.configure(None, ring=1000)
    try:
        opt = Optimizer(
            model, DataSet.array(samples) >> SampleToBatch(2) >> Tap(),
            nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), True),
            optim_method=SGD(),
            state=Tbl(learningRate=opt_cfg["learning_rate"],
                      momentum=opt_cfg["momentum"],
                      dampening=opt_cfg["dampening"]),
            end_trigger=Trigger.max_iteration(3))
        opt.set_taps(cadence=1)
        opt.optimize()
        steps = [e for e in log.ring_events() if e["type"] == "step"]
    finally:
        events.configure(None)
    losses = [e["loss"] for e in steps]
    assert len(losses) == 3
    taps = [e["taps"] for e in steps if "taps" in e]
    assert taps and all(
        t[f"rows_moved/{i}"] >= t[f"assignments_held/{i}"] > 0
        for t in taps for i in range(4))

    params = p0
    velocity = jax.tree_util.tree_map(jnp.zeros_like, p0)
    for k, rows in enumerate(seen[:3]):
        loss, grad = _reference_loss_and_grad(params, ids[rows],
                                              targets[rows])
        assert abs(losses[k] - loss) < 2e-5 * loss
        params, velocity = ref.sgd_update(params, velocity, grad, opt_cfg)
    got = from_program_tree(model.params(), names)
    for name in names:
        for part in params[name]:
            close(got[name][part], params[name][part], 1e-4)


@pytest.mark.parametrize("fault", ["full_window", "top_k_less",
                                   "no_route_scale"])
def test_the_window_and_the_routing_matter(fault):
    """The reference with the fault differs from the sound one by far more
    than the tolerance the comparisons above use."""
    p0 = ref.init_params(jax.random.PRNGKey(44), CFG)
    ids, _ = tokens(5, n=1)
    sound = ref.forward(p0, jnp.asarray(ids[0]), CFG)
    other = ref.forward(p0, jnp.asarray(ids[0]), CFG, fault=fault)
    gap = float(jnp.abs(sound - other).max() / jnp.abs(sound).max())
    assert gap > 100 * TOL, gap


def test_reference_in_chunks_and_recomputed_is_the_same_reference():
    p0 = ref.init_params(jax.random.PRNGKey(45), CFG)
    ids, targets = tokens(6, n=1)
    a = _reference_loss_and_grad(p0, ids, targets)
    b = _reference_loss_and_grad(p0, ids, targets, query_chunk=8, remat=True)
    assert abs(a[0] - b[0]) < 1e-6 * a[0]
    for x, y in zip(jax.tree_util.tree_leaves(a[1]),
                    jax.tree_util.tree_leaves(b[1])):
        close(x, y, 1e-5)


def test_gradient_buffers_are_made_on_first_use():
    m = nn.GatedLinearUnit(8, 12)
    assert all(g is None for g in m._grads.values())
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8))
    m.backward(x, jnp.ones_like(m.forward(x)))
    assert all(g is not None and g.shape == m._params[k].shape
               for k, g in m._grads.items())
    assert float(jnp.abs(m.parameters()[1][0]).sum()) > 0
    m.zero_grad_parameters()
    assert float(jnp.abs(m.get_parameters()[1]).sum()) == 0


def test_a_model_that_fills_the_device_is_taken_over_not_copied(monkeypatch):
    from bigdl_tpu.dataset import DataSet, Sample
    from bigdl_tpu.dataset.transformer import SampleToBatch
    from bigdl_tpu.optim import SGD, Optimizer, local_optimizer
    from bigdl_tpu.optim import trigger as Trigger
    from bigdl_tpu.utils.table import T as Tbl

    from bigdl_tpu.utils.random import set_seed

    ids, targets = tokens(9, n=4, t=8)

    def fails_before_step_3(state):
        if state.get("neval", 0) > 2:
            raise RuntimeError("the run breaks off")
        return False

    def train(fills, end=Trigger.max_iteration(2)):
        monkeypatch.setattr(local_optimizer, "_fills_device",
                            lambda tree: fills)
        set_seed(1)             # every run draws the same batches
        samples = [Sample(ids[i], targets[i]) for i in range(4)]
        cfg = dict(CFG, num_hidden_layers=2,
                   layer_types=CFG["layer_types"][:2])
        model = build(cfg)
        _laid_in(model, cfg, jax.random.PRNGKey(46))
        before = jax.tree_util.tree_leaves(model.params())
        try:
            Optimizer(model, DataSet.array(samples) >> SampleToBatch(2),
                      nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                                  True),
                      optim_method=SGD(), state=Tbl(learningRate=0.05),
                      end_trigger=end).optimize()
        except RuntimeError as e:
            assert "breaks off" in str(e)
        return before, jax.tree_util.tree_leaves(model.params())

    kept, copied = train(False)
    gone, taken = train(True)
    assert not any(a.is_deleted() for a in kept)
    assert all(a.is_deleted() for a in gone if a.size > 64)
    for a, b in zip(copied, taken):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a run that breaks off after two steps: a model taken over holds the
    # two steps' result, usable; a copied one still its initial weights
    started, left = train(False, Trigger.Trigger(fails_before_step_3))
    for a, b in zip(started, left):
        assert a is b
    gone, left = train(True, Trigger.Trigger(fails_before_step_3))
    assert all(a.is_deleted() for a in gone if a.size > 64)
    for a, b in zip(taken, left):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

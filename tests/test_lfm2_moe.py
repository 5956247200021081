"""The lfm2_moe decoder (``models/lfm2_moe.py``, ``nn.ShortConv``,
``nn.GroupedQueryAttention``, ``nn.TiedLmHead``) against the plain reference
(``benchmark/reference/lfm2_moe.py``) at a small size: seeded weights,
float32 policy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from benchmark.program import from_program_tree, to_program_tree
from benchmark.reference import lfm2_moe as ref
from bigdl_tpu import tensor as bt
from bigdl_tpu.models.lfm2_moe import Lfm2MoeLM, pre_norm_layer
from bigdl_tpu.nn.module import Context
from bigdl_tpu.obs import events

# 4 query heads to a key/value head, top-4 of 16 experts with 3 held, one
# dense conv layer, then a period of attention, conv, conv
CFG = {
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
    "conv_L_cache": 3, "intermediate_size": 96, "moe_intermediate_size": 32,
    "router_experts": 16, "num_experts_per_tok": 4,
    "experts_held": [0, 1, 2],
    "layer_types": ["conv", "full_attention", "conv", "conv"],
    "num_dense_layers": 1, "rope_theta": 1000000,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "norm_eps": 1e-5, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "vocab_size": 50, "assumed": {"initializer_std": 0.02},
    "optimizer": {"learning_rate": 0.05, "momentum": 0.9, "dampening": 0.0,
                  "weight_decay": 0.0},
}
T = 32
TOL = 2e-5
CONV_FAULTS = ("taps_reversed", "conv_forward", "gates_swapped", "x_first")


@pytest.fixture(autouse=True)
def _float32_policy():
    before = bt.policy()
    bt.set_policy(bt.FP32)
    yield
    bt.set_policy(before)


def build(cfg=CFG):
    keys = ("vocab_size", "hidden_size", "layer_types", "num_dense_layers",
            "num_attention_heads", "num_key_value_heads",
            "intermediate_size", "moe_intermediate_size",
            "num_experts_per_tok", "experts_held", "conv_L_cache",
            "norm_topk_prob", "routed_scaling_factor", "rope_theta",
            "norm_eps")
    return Lfm2MoeLM(num_experts=cfg["router_experts"],
                     **{k: cfg[k] for k in keys})


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


def _seeded(module, key, t, std=0.2):
    own = {}
    for n, (name, leaf) in enumerate(module.params()["~"].items()):
        own[name] = (1.0 if leaf.ndim == 1 else std) * jax.random.normal(
            jax.random.fold_in(key, n), leaf.shape)
    x = jax.random.normal(jax.random.fold_in(key, 99),
                          (2, t, CFG["hidden_size"]))
    return own, x


def _matches(module, own, x, reference, parts):
    """Forward and gradients (parameters and input) of ``module`` against
    ``reference(own, x[b])``, one sequence at a time."""
    c = jax.random.normal(jax.random.PRNGKey(11), x.shape)
    f = lambda p, x_: jnp.sum(run(module, {"~": p}, x_) * c)
    g = lambda p, x_: sum(jnp.sum(reference(p, x_[b]) * c[b])
                          for b in range(len(x)))
    for b in range(len(x)):
        close(run(module, {"~": own}, x)[b], reference(own, x[b]))
    gp, gx = jax.jit(jax.grad(f, (0, 1)))(own, x)
    rp, rx = jax.grad(g, (0, 1))(own, x)
    close(gx, rx)
    for name in parts:
        close(gp[name], rp[name])


# -- the gated short convolution ----------------------------------------------

def test_short_conv_holds_the_published_parameter_count():
    m = nn.ShortConv(2048, 3)
    shapes = {k: v.shape for k, v in m.params()["~"].items()}
    assert shapes == {"w_in": (2048, 6144), "conv": (2048, 3),
                      "w_out": (2048, 2048)}
    assert sum(int(np.prod(s)) for s in shapes.values()) == 16_783_360
    assert set(m.quant_spec) == {"w_in", "w_out"}


@pytest.mark.parametrize("t,taps", [
    (37, 3),            # T a multiple of no block
    (2, 3), (1, 3),     # T < L: every tap but the last sees only zeros
    (32, 4), (9, 1),    # other kernels: four taps, and none but the token's
])
def test_short_conv_matches_reference(t, taps):
    cfg = dict(CFG, conv_L_cache=taps)
    m = nn.ShortConv(CFG["hidden_size"], taps)
    own, x = _seeded(m, jax.random.PRNGKey(7), t)
    _matches(m, own, x, lambda p, x_: ref.short_conv(p, x_, cfg),
             ref.CONV_PARTS)


def test_short_conv_sees_the_token_and_the_two_before_it():
    """Causal and short: the output at t moves with the inputs at t-2, t-1
    and t and with no other."""
    m = nn.ShortConv(CFG["hidden_size"], 3)
    own, x = _seeded(m, jax.random.PRNGKey(8), 12)
    moved = jax.jacobian(lambda x_: run(m, {"~": own}, x_[None])[0, 7].sum())(
        x[0])
    reach = np.flatnonzero(np.abs(np.asarray(moved)).sum(axis=-1) > 0)
    assert list(reach) == [5, 6, 7]


@pytest.mark.parametrize("fault", CONV_FAULTS)
def test_the_taps_their_direction_and_the_split_matter(fault):
    m = nn.ShortConv(CFG["hidden_size"], 3)
    own, x = _seeded(m, jax.random.PRNGKey(9), T)
    y = run(m, {"~": own}, x)[0]
    other = ref.short_conv(own, x[0], CFG, fault=fault)
    assert float(jnp.abs(y - other).max() / jnp.abs(y).max()) > 1000 * TOL
    with pytest.raises(AssertionError):
        close(y, other)


# -- attention without a gate: 4 query heads a key/value head, rotary ----------

def _attention(block):
    m = nn.GroupedQueryAttention(
        CFG["hidden_size"], CFG["num_attention_heads"],
        CFG["num_key_value_heads"],
        CFG["hidden_size"] // CFG["num_attention_heads"],
        rotary_base=CFG["rope_theta"], eps=CFG["norm_eps"])
    m.block = block
    return m


def test_ungated_attention_holds_the_published_parameter_count():
    m = nn.GroupedQueryAttention(2048, 32, 8, 64, rotary_base=1e6)
    shapes = {k: v.shape for k, v in m.params()["~"].items()}
    assert shapes == {"wq": (2048, 2048), "wk": (2048, 512),
                      "wv": (2048, 512), "wo": (2048, 2048),
                      "q_norm": (64,), "k_norm": (64,)}
    assert sum(int(np.prod(s)) for s in shapes.values()) == 10_485_888
    assert set(m.quant_spec) == {"wq", "wk", "wv", "wo"}


@pytest.mark.parametrize("t,block", [(37, 8), (32, 16), (24, 512)])
def test_ungated_attention_matches_reference(t, block):
    m = _attention(block)
    own, x = _seeded(m, jax.random.PRNGKey(17), t)
    _matches(m, own, x, lambda p, x_: ref.attention(p, x_, CFG),
             ref.ATTENTION_PARTS)


@pytest.mark.parametrize("fault", ["no_qk_norm", "no_rotary"])
def test_the_head_norms_and_the_rotary_matter(fault):
    m = _attention(8)
    own, x = _seeded(m, jax.random.PRNGKey(18), T)
    y = run(m, {"~": own}, x)[0]
    other = ref.attention(own, x[0], CFG, fault=fault)
    assert float(jnp.abs(y - other).max() / jnp.abs(y).max()) > 1000 * TOL


# -- the expert layer at top-4 with no shared expert ---------------------------

def _moe(held, cfg=CFG, chunk=None):
    return nn.DroplessMoE(
        cfg["hidden_size"], cfg["moe_intermediate_size"],
        cfg["router_experts"], cfg["num_experts_per_tok"], experts_held=held,
        route_norm=cfg["norm_topk_prob"],
        route_scale=cfg["routed_scaling_factor"], chunk_rows=chunk,
        route_eps=ref.ROUTE_EPS)


def _whole_moe_params(key, cfg=CFG):
    d, h, e = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["router_experts"])
    k = lambda n: jax.random.fold_in(key, n)
    return {"router": jax.random.normal(k(0), (d, e)),
            "w_gate": 0.3 * jax.random.normal(k(1), (e, d, h)),
            "w_up": 0.3 * jax.random.normal(k(2), (e, d, h)),
            "w_down": 0.3 * jax.random.normal(k(3), (e, h, d))}


def _share(whole, held):
    take = jnp.asarray(list(held))
    return dict(whole, w_gate=whole["w_gate"][take],
                w_up=whole["w_up"][take], w_down=whole["w_down"][take])


@pytest.mark.parametrize("held,chunk", [
    ((0, 1, 2), None), ((3, 9, 4, 15), 16), ((3, 9, 4, 15), 48),
    (tuple(range(16)), None)])
def test_expert_layer_top4_without_a_shared_expert_matches_reference(held,
                                                                     chunk):
    m = _moe(held, chunk=chunk)
    assert set(m.params()["~"]) == {"router", "w_gate", "w_up", "w_down"}
    own = _share(_whole_moe_params(jax.random.PRNGKey(5)), held)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, T, CFG["hidden_size"]))
    c = jax.random.normal(jax.random.PRNGKey(8), x.shape)
    flat = lambda a: a.reshape(-1, a.shape[-1])
    f = lambda p, x_: jnp.sum(run(m, {"~": p}, x_) * c)
    g = lambda p, x_: jnp.sum(ref.expert_layer(
        p, flat(x_), CFG, experts_held=held).reshape(x_.shape) * c)
    sound = ref.expert_layer(own, flat(x), CFG, experts_held=held)
    close(run(m, {"~": own}, x), sound.reshape(x.shape))
    gp, gx = jax.jit(jax.grad(f, (0, 1)))(own, x)
    rp, rx = jax.grad(g, (0, 1))(own, x)
    close(gx, rx)
    for name in own:
        close(gp[name], rp[name])
    other = ref.expert_layer(own, flat(x), CFG, fault="top_k_less",
                             experts_held=held)
    assert float(jnp.abs(other - sound).max()) > 1e-2


def test_the_routing_divides_by_the_sum_and_1e_6():
    """Where the chosen scores are themselves near 1e-6 the constant under
    their sum decides the weights: the program's are the reference's, and
    1e-20 (the other two families') gives weights that sum to 1, far off.
    At scores near a half the two constants differ by 5e-7 of a weight,
    under float32's rounding: no run of a model shows it."""
    from bigdl_tpu.parallel.moe import sigmoid_topk_routing
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (T, 64))) + 0.5
    router = -0.2 - 0.02 * jnp.abs(
        jax.random.normal(jax.random.PRNGKey(2), (64, 16)))
    idx, w = sigmoid_topk_routing(x, router, jnp.zeros(16), 4, True, 1.0,
                                  ref.ROUTE_EPS)
    ref_idx, ref_w = ref.route({"router": router}, x, CFG)
    assert float(ref_w.sum(-1).max()) < 0.9        # the constant shows
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
    close(w, ref_w)
    _, w20 = sigmoid_topk_routing(x, router, jnp.zeros(16), 4, True, 1.0)
    _, ref_w20 = ref.route({"router": router}, x, CFG, fault="route_eps")
    close(w20, ref_w20)
    close(w20.sum(-1), jnp.ones(T))
    assert float(jnp.abs(w20 - w).max() / jnp.abs(w).max()) > 0.1


def test_the_selection_bias_enters_the_choice_only():
    """A bias that lifts expert 5 over every other makes every token choose
    it; its weight is still the unbiased score over the chosen scores'
    sum."""
    held = (5, 6)
    m = _moe(held)
    own = _share(_whole_moe_params(jax.random.PRNGKey(21)), held)
    x = jax.random.normal(jax.random.PRNGKey(22), (1, T, CFG["hidden_size"]))
    state = m.state()
    state["~"]["route_bias"] = jnp.zeros(16).at[5].set(10.0)
    y, new = m.apply({"~": own}, x, state, Context(training=True))
    scores = jax.nn.sigmoid(x[0] @ own["router"])
    top = jnp.sort(scores.at[:, 5].set(-1.0), axis=-1)[:, -3:]
    w5 = scores[:, 5] / (scores[:, 5] + top.sum(-1) + ref.ROUTE_EPS)
    six = jnp.any(jax.lax.top_k(scores.at[:, 5].set(2.0), 4)[1] == 6, -1)
    w6 = jnp.where(six, scores[:, 6] / (scores[:, 5] + top.sum(-1)
                                        + ref.ROUTE_EPS), 0.0)
    expert = lambda j: ref.swiglu(x[0], own["w_gate"][j], own["w_up"][j],
                                  own["w_down"][j])
    close(y[0], w5[:, None] * expert(0) + w6[:, None] * expert(1))
    assert float(new["~"]["tap_expert_max"]) == T


def test_the_shares_add_up_to_the_whole_layer():
    """64 experts over 8 chips, 8 each: the 8 shares' outputs (nothing is
    computed by every chip alike: there is no shared expert) sum to the
    uncut reference's whole layer."""
    cfg = dict(CFG, router_experts=64)
    whole = _whole_moe_params(jax.random.PRNGKey(15), cfg)
    x = jax.random.normal(jax.random.PRNGKey(16), (T, cfg["hidden_size"]))
    total, held_in_all = 0.0, 0.0
    for chip in range(8):
        held = tuple(range(8 * chip, 8 * chip + 8))
        m = _moe(held, cfg)
        y, state = m.apply({"~": _share(whole, held)}, x[None], m.state(),
                           Context(training=True))
        total = total + y[0]
        held_in_all += float(state["~"]["tap_assignments_held"])
    close(total, ref.expert_layer(whole, x, cfg, experts_held=range(64)),
          5e-5)
    assert held_in_all == T * cfg["num_experts_per_tok"]


# -- the tied head -------------------------------------------------------------

def _tied(vocab=11, d=16):
    body = nn.Sequential(nn.RMSNorm(d, 1e-5))
    model = nn.TiedLmHead(vocab, d, body)
    table = jax.random.normal(jax.random.PRNGKey(31), (vocab, d))
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(32), (d,))
    params = {"~": {"weight": table}, "0": {"0": {"~": {"weight": scale}}}}
    return model, params


def test_tied_head_is_one_leaf_whose_gradient_is_both_uses():
    model, params = _tied()
    leaves = jax.tree_util.tree_leaves(model.params())
    assert sorted(leaf.shape for leaf in leaves) == [(11, 16), (16,)]
    ids = np.array([[3, 1, 4, 1, 5, 9, 2, 6]], np.float32)
    targets = np.array([[1, 4, 1, 5, 9, 2, 6, 5]], np.int32)
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), True)
    loss = lambda p: crit.apply_loss(run(model, p, ids), targets)
    grads = jax.grad(loss)(params)

    def by_hand(lookup_table, head_table, scale):
        h = ref.rms_norm(lookup_table[ids[0].astype(int) - 1], scale, 1e-5)
        logp = jax.nn.log_softmax(h @ head_table.T)
        return -jnp.mean(logp[jnp.arange(8), targets[0] - 1])

    table, scale = params["~"]["weight"], params["0"]["0"]["~"]["weight"]
    assert abs(float(loss(params)) - float(by_hand(table, table, scale))) \
        < 1e-6
    g_lookup, g_head = jax.grad(by_hand, (0, 1))(table, table, scale)
    close(grads["~"]["weight"], g_lookup + g_head)
    # the lookup's part alone (what an untied embedding would get) is far
    # from it, and rows no id names get the head's part alone
    assert float(jnp.abs(g_lookup - grads["~"]["weight"]).max()) \
        > 0.1 * float(jnp.abs(grads["~"]["weight"]).max())
    unseen = np.setdiff1d(np.arange(11), ids[0].astype(int) - 1)
    close(grads["~"]["weight"][unseen], g_head[unseen])


def test_tied_head_reloads_and_resets_as_one_table():
    model, params = _tied()
    model.load_params(params)
    np.testing.assert_array_equal(
        np.asarray(model.params()["~"]["weight"]),
        np.asarray(params["~"]["weight"]))
    assert model.n_parameters() == 11 * 16 + 16
    model.reset()
    assert model.params()["~"]["weight"].shape == (11, 16)
    assert float(jnp.abs(model.params()["~"]["weight"]
                         - params["~"]["weight"]).max()) > 0.1


# -- the whole model -----------------------------------------------------------

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
    assert "head" not in names and names[0] == "embed"
    assert model.n_parameters() == sum(
        int(np.prod(s)) for leaf in ref.param_shapes(CFG).values()
        for s in leaf.values())
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
    assert set(got) == set(ref_grad)
    for name in names:
        for part in ref_grad[name]:
            close(got[name][part], ref_grad[name][part], 2e-4)
    # against the untied fault the embedding's gradient is far off: the
    # head's use is most of it
    _, untied = _reference_loss_and_grad(p0, ids, targets,
                                         fault="head_untied")
    gap = np.abs(got["embed"]["weight"] - untied["embed"]["weight"]).max()
    assert gap > 0.5 * np.abs(got["embed"]["weight"]).max()


@pytest.mark.parametrize("fault", CONV_FAULTS + (
    "no_qk_norm", "no_rotary", "head_untied", "top_k_less"))
def test_each_fault_moves_the_whole_model(fault):
    """The reference with the fault differs from the sound one by far more
    than the tolerance the comparisons above use (with the layers' matrices
    five times their initial size and q/k norm weights off 1: at 0.02 the
    scores are near zero, the softmax near uniform whatever the keys).
    ``route_eps`` is not here: it moves a weight by 5e-7 of itself
    (``test_the_routing_divides_by_the_sum_and_1e_6``)."""
    p0 = ref.init_params(jax.random.PRNGKey(44), CFG)
    for name, leaf in p0.items():
        if name.startswith("layer"):
            p0[name] = {part: 5.0 * w if w.ndim > 1 else w * 3.0
                        if part in ("q_norm", "k_norm") else w
                        for part, w in leaf.items()}
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


def test_recompute_changes_nothing_and_remakes_the_projection(capsys):
    """One conv expert layer under its ``nn.Recompute``: the gradient of
    the bare layer; nothing of the layer's inside is handed to the backward
    pass but what the routing decided (the (B, T, 3D) projection is made
    again, not kept; the routed experts' sum is offered and not held, no
    backward computation reads it; the four choices of 2 * T tokens, the
    3 * 2 * T assignments three held experts can get and the three counts
    are held, int32), neither grouped pass runs in the recomputation, and
    the routing is decided once."""
    d = CFG["hidden_size"]
    conv = nn.ShortConv(d, 3)
    wrapped = pre_norm_layer(d, conv, _moe((0, 1, 2)), CFG["norm_eps"])
    bare = wrapped.modules[0]
    params = bare.params()
    x = jax.random.normal(jax.random.PRNGKey(50), (2, T, d))
    f = {"bare": lambda p, x_: jnp.sum(run(bare, p, x_) ** 2),
         "recompute": lambda p, x_: jnp.sum(
             run(wrapped, {"0": p, "~": {}}, x_) ** 2)}
    close(f["recompute"](params, x), f["bare"](params, x))
    with nn.containers.kept_report() as report:
        got = jax.grad(f["recompute"])(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(jax.grad(f["bare"])(params, x))):
        close(a, b)
    assert report == {"layers": 1, "kept": {
        "route_idx": 4 * 2 * T * 4, "route_order": 4 * 3 * 2 * T,
        "route_sizes": 4 * 3, "experts_out": 4 * 2 * T * d}}
    jax.ad_checkpoint.print_saved_residuals(f["recompute"], params, x)
    inside = [line.split()[0] for line in capsys.readouterr().out.split("\n")
              if " from the argument " not in line and line.strip()
              and "from a constant" not in line and "<lambda>" not in line]
    assert sorted(inside) == sorted([
        "i32[%d,4]" % (2 * T), "i32[%d]" % (3 * 2 * T), "i32[3]"])
    text = str(jax.make_jaxpr(jax.grad(f["recompute"]))(params, x))
    bare_text = str(jax.make_jaxpr(jax.grad(f["bare"]))(params, x))
    for op in ("ragged_dot_general[", " top_k[", " sort["):
        assert text.count(op) == bare_text.count(op) > 0


def test_three_steps_through_the_optimizer_match_reference():
    """Through ``Optimizer.optimize()``: the three losses and the
    parameters after three steps are the reference's (the tied table moved
    by one momentum fed both uses' gradients); the step events carry the
    three counters of every expert layer."""
    from bigdl_tpu.dataset import DataSet, Sample
    from bigdl_tpu.dataset.transformer import SampleToBatch, Transformer
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
        logged = log.ring_events()
    finally:
        events.configure(None)
    steps = [e for e in logged if e["type"] == "step"]
    losses = [e["loss"] for e in steps]
    assert len(losses) == 3
    sparse = len(CFG["layer_types"]) - CFG["num_dense_layers"]
    taps = [e["taps"] for e in steps if "taps" in e]
    assert len(taps) == 3 and all(
        t[f"rows_moved/{i}"] >= t[f"assignments_held/{i}"]
        >= t[f"expert_max/{i}"] > 0 for t in taps for i in range(sparse))
    assert not any(f"assignments_held/{sparse}" in t for t in taps)
    kept = [e for e in logged if e["type"] == "recompute"]
    assert len(kept) == 1 and events.validate_event(kept[0])
    heads, hd = CFG["num_attention_heads"], 8
    assert kept[0]["layers"] == len(CFG["layer_types"])
    assert kept[0]["kept"] == {
        "attention_out": 2 * T * heads * hd * 4,        # one attention layer
        "attention_lse": 2 * T * heads * 4,
        # an expert layer's four choices of 2 * T tokens, the 3 * 2 * T
        # assignments three held experts can get, three counts: int32
        "route_idx": sparse * 2 * T * 4 * 4,
        "route_order": sparse * 3 * 2 * T * 4, "route_sizes": sparse * 3 * 4,
        "experts_out": sparse * 2 * T * CFG["hidden_size"] * 4}

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

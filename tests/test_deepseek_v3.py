"""The deepseek_v3 decoder (``models/deepseek_v3.py``, ``nn.LatentAttention``)
against the plain reference (``benchmark/reference/deepseek_v3.py``) at a
small size: seeded weights, float32 policy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from benchmark.program import from_program_tree, to_program_tree
from benchmark.reference import deepseek_v3 as ref
from bigdl_tpu import tensor as bt
from bigdl_tpu.models.deepseek_v3 import DeepseekV3LM, deepseek_v3_layer
from bigdl_tpu.nn.attention import rotary_interleaved
from bigdl_tpu.nn.module import Context
from bigdl_tpu.obs import events

# a value head (12) that is neither the score head (16 + 8) nor its nope
# part, top-6 of 16 experts with 2 shared, 3 of the 16 held
CFG = {
    "hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 12,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "router_experts": 16, "num_experts_per_tok": 6, "n_shared_experts": 2,
    "experts_held": [0, 1, 2], "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "rope_theta": 1000000, "rms_norm_eps": 1e-6,
    "norm_topk_prob": True, "routed_scaling_factor": 2.448, "vocab_size": 50,
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
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "first_k_dense_replace", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "intermediate_size", "moe_intermediate_size",
            "num_experts_per_tok", "experts_held", "n_shared_experts",
            "routed_scaling_factor", "norm_topk_prob", "rope_theta",
            "rms_norm_eps")
    return DeepseekV3LM(n_routed_experts=cfg["router_experts"],
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


def _attention_pair(t, block, cfg=CFG):
    m = nn.LatentAttention(
        cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"],
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
        rotary_base=cfg["rope_theta"], eps=cfg["rms_norm_eps"])
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


def test_latent_attention_holds_the_published_parameter_count():
    m = nn.LatentAttention(2048, 32, 512, 128, 64, 128)
    shapes = {k: v.shape for k, v in m.params()["~"].items()}
    assert shapes == {"wq": (2048, 6144), "wkv_a": (2048, 576),
                      "kv_norm": (512,), "wkv_b": (512, 8192),
                      "wo": (4096, 2048)}
    assert sum(int(np.prod(s)) for s in shapes.values()) == 26_345_984
    assert set(m.quant_spec) == set(shapes) - {"kv_norm"}


@pytest.mark.parametrize("t,block", [
    (37, 8),            # several blocks, T not a multiple of the block
    (32, 16),
    (24, 512),          # one block
])
def test_latent_attention_matches_reference(t, block):
    m, own, x = _attention_pair(t, block)
    c = jax.random.normal(jax.random.PRNGKey(11), x.shape)
    f = lambda p, x_: jnp.sum(run(m, {"~": p}, x_) * c)
    g = lambda p, x_: sum(
        jnp.sum(ref.attention(p, x_[b], CFG) * c[b]) for b in range(2))
    for b in range(2):
        close(run(m, {"~": own}, x)[b], ref.attention(own, x[b], CFG))
    gp, gx = jax.jit(jax.grad(f, (0, 1)))(own, x)
    rp, rx = jax.grad(g, (0, 1))(own, x)
    close(gx, rx)
    for name in ref.ATTENTION_PARTS:
        close(gp[name], rp[name])


@pytest.mark.parametrize("fault", ["no_rotary_key", "rotate_half"])
def test_the_rotary_key_and_its_pairing_matter(fault):
    """Left out of the scores, or turned in the other pairing, the shared
    rotary key makes the comparison above fail by far."""
    m, own, x = _attention_pair(T, 8)
    y = run(m, {"~": own}, x)[0]
    other = ref.attention(own, x[0], CFG, fault=fault)
    assert float(jnp.abs(y - other).max() / jnp.abs(y).max()) > 1000 * TOL
    with pytest.raises(AssertionError):
        close(y, other)


def test_interleaved_rotary_matches_reference():
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 19, 3, 8))
    # the program hands the pairs back first members first: one fixed
    # permutation of the reference's order
    order = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
    for b in range(2):
        close(rotary_interleaved(x, 1e6)[b],
              ref.rotate_pairs(x[b], 1e6)[..., order])
    # the neighbours are the pairs: (0, 1) turns as one, (0, 4) does not
    one = jnp.zeros((1, 5, 1, 8)).at[..., 0].set(1.0)
    turned = ref.rotate_pairs(one[0], 10.0)
    assert float(jnp.abs(turned[1:, 0, 1]).min()) > 1e-3
    assert float(jnp.abs(turned[..., 2:]).max()) == 0.0
    # position 0 is left as it is, and a rotation keeps every pair's norm
    close(rotary_interleaved(x)[:, 0], x[:, 0][..., order])
    close(jnp.linalg.norm(rotary_interleaved(x), axis=-1),
          jnp.linalg.norm(x, axis=-1))
    # the same permutation on both sides: scores are the reference's
    y = jax.random.normal(jax.random.PRNGKey(4), (2, 19, 3, 8))
    close(jnp.sum(rotary_interleaved(x, 1e6) * rotary_interleaved(y, 1e6), -1)[0],
          jnp.sum(ref.rotate_pairs(x[0], 1e6) * ref.rotate_pairs(y[0], 1e6),
                  -1))


def test_rotating_the_rope_part_leaves_the_rest_alone():
    """With the rope columns of Wq and Wkv_a at zero nothing is left to
    rotate, and the layer is a position-free causal attention over the
    nope part, scaled by the whole score head: written out here with no
    rotation at all."""
    m, own, x = _attention_pair(T, 8)
    heads, r, nope = (CFG["num_attention_heads"], CFG["kv_lora_rank"],
                      CFG["qk_nope_head_dim"])
    rope, dv = CFG["qk_rope_head_dim"], CFG["v_head_dim"]
    wq = own["wq"].reshape(-1, heads, nope + rope).at[..., nope:].set(0.0)
    own = dict(own, wq=wq.reshape(own["wq"].shape),
               wkv_a=own["wkv_a"].at[:, r:].set(0.0))
    y = run(m, {"~": own}, x)[0]
    q = (x[0] @ own["wq"]).reshape(T, heads, -1)[..., :nope]
    c = ref.rms_norm((x[0] @ own["wkv_a"])[:, :r], own["kv_norm"],
                     CFG["rms_norm_eps"])
    kv = (c @ own["wkv_b"]).reshape(T, heads, nope + dv)
    s = jnp.einsum("qhd,khd->hqk", q, kv[..., :nope]) / (nope + rope) ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), kv[..., nope:])
    close(y, o.reshape(T, -1) @ own["wo"])


# -- the expert layer at top-6 with two shared experts -------------------------

def _moe(held, cfg=CFG, chunk=None):
    return nn.DroplessMoE(
        cfg["hidden_size"], cfg["moe_intermediate_size"],
        cfg["router_experts"], cfg["num_experts_per_tok"], experts_held=held,
        route_norm=cfg["norm_topk_prob"],
        route_scale=cfg["routed_scaling_factor"],
        shared_hidden=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        chunk_rows=chunk)


def _whole_moe_params(key, cfg=CFG):
    d, h, e = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["router_experts"])
    hs = cfg["n_shared_experts"] * h
    k = lambda n: jax.random.fold_in(key, n)
    return {"router": jax.random.normal(k(0), (d, e)),
            "w_gate": 0.3 * jax.random.normal(k(1), (e, d, h)),
            "w_up": 0.3 * jax.random.normal(k(2), (e, d, h)),
            "w_down": 0.3 * jax.random.normal(k(3), (e, h, d)),
            "shared_gate": 0.3 * jax.random.normal(k(4), (d, hs)),
            "shared_up": 0.3 * jax.random.normal(k(5), (d, hs)),
            "shared_down": 0.3 * jax.random.normal(k(6), (hs, d))}


def _share(whole, held):
    take = jnp.asarray(list(held))
    return dict(whole, w_gate=whole["w_gate"][take],
                w_up=whole["w_up"][take], w_down=whole["w_down"][take])


@pytest.mark.parametrize("held,chunk", [
    ((0, 1, 2), None), ((3, 9, 4, 15), 16), (tuple(range(16)), None)])
def test_expert_layer_top6_with_two_shared_matches_reference(held, chunk):
    whole = _whole_moe_params(jax.random.PRNGKey(5))
    own = _share(whole, held)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, T, CFG["hidden_size"]))
    m = _moe(held, chunk=chunk)
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
    for fault in ("top_k_less", "no_route_scale"):
        other = ref.expert_layer(own, flat(x), CFG, fault=fault,
                                 experts_held=held)
        assert float(jnp.abs(other - sound).max()) > 1e-2, fault


def test_the_shares_add_up_to_the_whole_layer():
    """128 experts over 8 chips, 16 each: the 8 shares' outputs, the shared
    experts counted once, sum to the uncut reference's whole layer."""
    cfg = dict(CFG, router_experts=128)
    whole = _whole_moe_params(jax.random.PRNGKey(15), cfg)
    x = jax.random.normal(jax.random.PRNGKey(16), (T, cfg["hidden_size"]))
    shared = ref.swiglu(x, whole["shared_gate"], whole["shared_up"],
                        whole["shared_down"])
    total, held_in_all = shared, 0.0
    for chip in range(8):
        held = tuple(range(16 * chip, 16 * chip + 16))
        m = _moe(held, cfg)
        y, state = m.apply({"~": _share(whole, held)}, x[None], m.state(),
                           Context(training=True))
        total = total + (y[0] - shared)
        held_in_all += float(state["~"]["tap_assignments_held"])
    close(total, ref.expert_layer(whole, x, cfg, experts_held=range(128)),
          5e-5)
    assert held_in_all == T * cfg["num_experts_per_tok"]


def test_a_capacity_would_drop_tokens_this_layer_keeps():
    whole = _whole_moe_params(jax.random.PRNGKey(25))
    whole["router"] = 0.01 * whole["router"].at[:, 1].set(100.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(26),
                                  (T, CFG["hidden_size"])))
    held = (0, 1, 2)
    m = _moe(held, chunk=8)
    y, state = m.apply({"~": _share(whole, held)}, x[None], m.state(),
                       Context(training=True))
    close(y[0], ref.expert_layer(_share(whole, held), x, CFG,
                                 experts_held=held))
    assert float(state["~"]["tap_expert_max"]) == T
    dropped = ref.expert_layer(_share(whole, held), x, CFG, fault="capacity",
                               experts_held=held)
    assert float(jnp.abs(dropped - y[0]).max()) > 1e-2


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


@pytest.mark.parametrize("fault", ["no_rotary_key", "rotate_half",
                                   "top_k_less", "no_route_scale"])
def test_each_fault_moves_the_whole_model(fault):
    """The reference with the fault differs from the sound one by far more
    than the tolerance the comparisons above use (with the layers' matrices
    five times their initial size: at 0.02 the scores are near zero, the
    softmax near uniform whatever the keys, and three held experts' sum
    small beside the residual stream)."""
    p0 = ref.init_params(jax.random.PRNGKey(44), CFG)
    for name, leaf in p0.items():
        if name.startswith("layer"):
            p0[name] = {part: 5.0 * w if w.ndim > 1 else w
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


def test_recompute_changes_nothing_and_holds_the_core_only(capsys):
    """One expert layer under its ``nn.Recompute``: the gradient of the
    bare layer; of the layer's inside the core's output (in the value
    head's size) and its logsumexp are handed to the backward pass, and the
    latent path is made again.  The routed experts' sum is marked too, but
    here it goes straight into the residual add: no backward computation
    reads it, so it is offered and not held (an afmoe layer's closing norm
    reads it), and neither grouped pass runs in the recomputation.  What
    the routing decided (the choices, the sorted assignments, the counts:
    int32) is held, and decided once."""
    heads, dv, d = (CFG["num_attention_heads"], CFG["v_head_dim"],
                    CFG["hidden_size"])
    attention, own, x = _attention_pair(T, 8)
    wrapped = deepseek_v3_layer(d, attention, _moe((0, 1, 2)),
                                CFG["rms_norm_eps"])
    bare = wrapped.modules[0]
    params = bare.params()
    f = {"bare": lambda p, x_: jnp.sum(run(bare, p, x_) ** 2),
         "recompute": lambda p, x_: jnp.sum(
             run(wrapped, {"0": p, "~": {}}, x_) ** 2)}
    close(f["recompute"](params, x), f["bare"](params, x))
    with nn.containers.kept_report() as report:
        got = jax.grad(f["recompute"])(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(jax.grad(f["bare"])(params, x))):
        close(a, b)
    # 2 * T tokens choose 6 of 16, three of them held: 3 * 2 * T
    # assignments at most, one chunk
    keeps = {"attention_out": ("f32", (2, T, heads, 1, dv)),
             "attention_lse": ("f32", (2, heads, 1, T)),
             "route_idx": ("i32", (2 * T, 6)),
             "route_order": ("i32", (3 * 2 * T,)),
             "route_sizes": ("i32", (3,)), "experts_out": ("f32", (2 * T, d))}
    assert report == {"layers": 1, "kept": {
        label: 4 * int(np.prod(shape))
        for label, (_, shape) in keeps.items()}}
    jax.ad_checkpoint.print_saved_residuals(f["recompute"], params, x)
    inside = [line.split()[0] for line in capsys.readouterr().out.split("\n")
              if " from the argument " not in line and line.strip()
              and "from a constant" not in line and "<lambda>" not in line]
    assert sorted(inside) == sorted(
        "%s[%s]" % (dtype, ",".join(map(str, shape)))
        for label, (dtype, shape) in keeps.items() if label != "experts_out")
    text = str(jax.make_jaxpr(jax.grad(f["recompute"]))(params, x))
    bare_text = str(jax.make_jaxpr(jax.grad(f["bare"]))(params, x))
    for op in ("while[", "ragged_dot_general[", " top_k[", " sort["):
        assert text.count(op) == bare_text.count(op) > 0


def test_three_steps_through_the_optimizer_match_reference():
    """Through ``Optimizer.optimize()``: the three losses and the
    parameters after three steps are the reference's; the step's
    ``recompute`` event lists what the latent layers keep, and the step
    events carry the three counters of every expert layer."""
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
    layers, sparse = CFG["num_hidden_layers"], 2
    taps = [e["taps"] for e in steps if "taps" in e]
    assert len(taps) == 3 and all(
        t[f"rows_moved/{i}"] >= t[f"assignments_held/{i}"]
        >= t[f"expert_max/{i}"] > 0 for t in taps for i in range(sparse))
    assert not any(f"assignments_held/{sparse}" in t for t in taps)
    kept = [e for e in logged if e["type"] == "recompute"]
    assert len(kept) == 1 and events.validate_event(kept[0])
    heads, dv = CFG["num_attention_heads"], CFG["v_head_dim"]
    assert kept[0]["layers"] == layers
    # an expert layer's routing: six choices of 2 * T tokens, the 3 * 2 * T
    # assignments three held experts can get, three counts, all int32
    assert kept[0]["kept"] == {
        "attention_out": layers * 2 * T * heads * dv * 4,
        "attention_lse": layers * 2 * T * heads * 4,
        "route_idx": sparse * 2 * T * 6 * 4,
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

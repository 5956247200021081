"""What a routing decides is made once a step (PR 36): ``nn.Recompute``
keeps ``route_idx``, ``route_order`` and ``route_sizes`` of a
``DroplessMoE``, and the routing's two selections (the chosen scores, the
local index of a chosen expert) are compares against the experts' ids, not
scalar gathers.  Both give the gathers' results bit for bit."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from bigdl_tpu import nn
from bigdl_tpu.nn.module import Context
from bigdl_tpu.parallel.moe import sigmoid_topk_routing, sort_assignments

T, D = 96, 32

# (top_k, experts, the ids held, in local order): afmoe's, deepseek_v3's and
# lfm2_moe's shares of the three cells, and a share that is no range
ROUTINGS = {
    "top8_of_128_holds_16": (8, 128, tuple(range(16))),
    "top6_of_128_holds_16": (6, 128, tuple(range(16))),
    "top4_of_64_holds_8": (4, 64, tuple(range(8))),
    "holds_no_range": (8, 128, (3, 120, 7, 64, 65, 1)),
}


def _inputs(n_experts, top_k):
    keys = jax.random.split(jax.random.PRNGKey(n_experts + top_k), 4)
    return (jax.random.normal(keys[0], (T, D)),
            0.3 * jax.random.normal(keys[1], (D, n_experts)),
            0.5 * jax.random.normal(keys[2], (n_experts,)),
            jax.random.normal(keys[3], (T, top_k)))


def _gathered_routing(x, router_w, bias, top_k, route_norm, route_scale,
                      norm_eps=1e-20):
    """``sigmoid_topk_routing`` as it was up to PR 35: the chosen scores
    by ``take_along_axis``."""
    logits = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(scores + lax.stop_gradient(bias), top_k)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if route_norm:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + norm_eps)
    return idx, weights * route_scale


def _gathered_sort(idx, held, n_experts):
    """``sort_assignments`` as it was: the local index looked up."""
    local_of = np.full((n_experts,), len(held), np.int32)
    local_of[list(held)] = np.arange(len(held))
    local = jnp.asarray(local_of)[idx].reshape(-1)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    sizes = jnp.sum(local[:, None] == jnp.arange(len(held)), axis=0,
                    dtype=jnp.int32)
    return order, sizes


@pytest.mark.parametrize("norm", [True, False])
@pytest.mark.parametrize("case", list(ROUTINGS))
def test_the_selections_are_the_gathers_bit_for_bit(case, norm):
    top_k, n_experts, held = ROUTINGS[case]
    x, router_w, bias, c = _inputs(n_experts, top_k)

    def through(routing):
        def f(x_, w_):
            idx, weights = routing(x_, w_, bias, top_k, norm, 2.5, 1e-6)
            return jnp.sum(weights * c), (idx, weights)
        return jax.jit(jax.value_and_grad(f, (0, 1), has_aux=True))(
            x, router_w)

    (_, (idx, weights)), grads = through(sigmoid_topk_routing)
    (_, (ref_idx, ref_weights)), ref_grads = through(_gathered_routing)
    assert idx.dtype == jnp.int32 and np.array_equal(idx, ref_idx)
    # the bias moved the choice: not the top k of the scores alone
    unbiased, _ = _gathered_routing(x, router_w, 0 * bias, top_k, norm, 2.5)
    assert not np.array_equal(idx, unbiased)
    assert np.array_equal(weights, ref_weights)
    for got, want in zip(grads, ref_grads):
        assert np.array_equal(got, want) and float(jnp.abs(got).max()) > 0
    order, sizes = jax.jit(lambda i: sort_assignments(i, held))(idx)
    ref_order, ref_sizes = _gathered_sort(idx, held, n_experts)
    assert order.dtype == sizes.dtype == jnp.int32
    assert np.array_equal(order, ref_order)
    assert np.array_equal(sizes, ref_sizes) and int(sizes.sum()) > 0


@pytest.mark.parametrize("case", list(ROUTINGS))
def test_the_routing_alone_runs_no_scalar_gather_or_scatter(case):
    """``MoeRoute`` outside the passes, forward and backward: one top-k,
    one sort, and neither a gather nor a scatter of any kind."""
    top_k, n_experts, held = ROUTINGS[case]
    x, router_w, bias, c = _inputs(n_experts, top_k)

    def f(x_, w_):
        idx, weights = sigmoid_topk_routing(x_, w_, bias, top_k, True, 1.0)
        order, sizes = sort_assignments(idx, held)
        return jnp.sum(weights * c), (order, sizes)

    text = str(jax.make_jaxpr(jax.value_and_grad(f, (0, 1), has_aux=True))(
        x, router_w))
    count = lambda name: len(re.findall(r"\b%s\[" % name, text))
    assert (count("top_k"), count("sort")) == (1, 1)
    assert not re.search(r"\b(gather|scatter[-_a-z]*|dynamic_slice)\[", text)
    # the (token, choice, expert) compares are there, for XLA to fuse into
    # the reductions that consume them (tests/test_chip_compile.py)
    assert "bool[%d,%d,%d]" % (T, top_k, n_experts) in text


def _expert_layer(case):
    top_k, n_experts, held = ROUTINGS[case]
    return nn.Sequential(
        nn.RMSNorm(D), nn.DroplessMoE(D, 16, n_experts, top_k,
                                      experts_held=held), nn.RMSNorm(D))


@pytest.mark.parametrize("case", list(ROUTINGS))
def test_a_recomputed_layer_decides_its_routing_once(case):
    """The gradient of a ``Recompute``d expert layer holds one top-k and
    one sort, as the bare layer's; under a ``jax.checkpoint`` with no
    policy two of each, as before the marks.  Same gradient, bit for bit,
    and the backward pass is handed the three integer arrays."""
    top_k, _, held = ROUTINGS[case]
    layer = _expert_layer(case)
    params = layer.params()
    x = jax.random.normal(jax.random.PRNGKey(7), (2, T, D))
    run = lambda m, p, x_: m.apply(p, x_, m.state(),
                                   Context(training=True))[0]
    wrapped = nn.Recompute(layer)
    plain = jax.checkpoint(lambda p, x_: run(layer, p, x_))
    f = {"bare": lambda p, x_: jnp.sum(run(layer, p, x_) ** 2),
         "checkpoint": lambda p, x_: jnp.sum(plain(p, x_) ** 2),
         "recompute": lambda p, x_: jnp.sum(
             run(wrapped, {"0": p, "~": {}}, x_) ** 2)}
    decided = {}
    for how, loss in f.items():
        text = str(jax.make_jaxpr(jax.grad(loss))(params, x))
        decided[how] = tuple(len(re.findall(r"\b%s\[" % name, text))
                             for name in ("top_k", "sort"))
    assert decided == {"bare": (1, 1), "checkpoint": (2, 2),
                       "recompute": (1, 1)}
    with nn.containers.kept_report() as report:
        got = jax.grad(f["recompute"])(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(
            jax.grad(f["checkpoint"])(params, x))):
        assert np.array_equal(a, b)
    tokens = 2 * T
    most, chunk = layer.modules[1].chunk_of(tokens)
    assert most == tokens * min(top_k, len(held))
    assert report == {"layers": 1, "kept": {
        "route_idx": 4 * tokens * top_k,
        "route_order": 4 * (most + -most % chunk),
        "route_sizes": 4 * len(held), "experts_out": 4 * tokens * D}}

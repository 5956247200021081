"""Mixture-of-experts FFN as a model-zoo module.

The reference's ``MixtureTable`` (nn/MixtureTable.scala:221) is a
single-device soft mixture over branch outputs; a sparse expert layer
trainable through the Optimizer is absent (SURVEY.md §2.9: EP = NO).
This module is the missing front door: drop ``nn.MoE`` into a
``Sequential`` and train it like any layer — and with
``DistriOptimizer(expert_parallel=True)`` over a mesh with an ``expert``
axis, the expert-stacked parameters shard across chips and XLA GSPMD
partitions the dispatch/expert/combine einsums (all-to-all over ICI),
the same computation the hand-scheduled ``parallel/moe.moe_apply``
expresses with shard_map.

Formulation: GShard/Switch static-capacity top-1 routing
(``parallel.moe.top1_gating``): one-hot dispatch (T, E, C) einsums keep
every shape static for XLA; tokens over an expert's capacity are dropped
(standard switch semantics — pair with a residual connection).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from bigdl_tpu.nn.containers import kept
from bigdl_tpu.nn.module import TensorModule
from bigdl_tpu.nn import init as init_
from bigdl_tpu.nn.linear import swiglu
from bigdl_tpu.tensor import policy


class MoE(TensorModule):
    """Top-1 switch-routed expert FFN: (…, D) -> (…, D).

    Params: ``router`` (D, E); expert-stacked ``w1`` (E, D, H), ``b1``
    (E, H), ``w2`` (E, H, D), ``b2`` (E, D) — the leading expert dim is
    what ``expert_parallel`` shards.
    """

    def __init__(self, d_model: int, hidden: int, n_experts: int,
                 capacity_factor: float = 1.25):
        super().__init__()
        self.d_model = d_model
        self.hidden = hidden
        self.n_experts = n_experts
        self.capacity_factor = capacity_factor
        self.reset()

    def reset(self):
        d, h, e = self.d_model, self.hidden, self.n_experts
        self._add_param("router", init_.default_linear((d, e), d))
        self._add_param("w1", init_.default_linear((e, d, h), d))
        self._add_param("b1", np.zeros((e, h), np.float32))
        self._add_param("w2", init_.default_linear((e, h, d), h))
        self._add_param("b2", np.zeros((e, d), np.float32))
        return self

    def _forward(self, P, x, S, ctx):
        from bigdl_tpu.parallel.moe import expert_capacity, top1_gating
        p = policy()
        d = x.shape[-1]
        xt = x.reshape(-1, d)                        # (T, D) tokens
        n_tok = xt.shape[0]
        e = self.n_experts
        cap = expert_capacity(n_tok, e, self.capacity_factor)

        logits = jnp.matmul(p.cast_compute(xt),
                            p.cast_compute(P["router"])).astype(jnp.float32)
        dispatch, combine = top1_gating(logits, e, cap)  # (T, E, C) each

        cc = p.cast_compute
        xe = jnp.einsum("tec,td->ecd", cc(dispatch), cc(xt))
        hdn = jnp.einsum("ecd,edh->ech", xe, cc(P["w1"]))
        hdn = jax.nn.relu(hdn.astype(jnp.float32) + P["b1"][:, None])
        ye = jnp.einsum("ech,ehd->ecd", cc(hdn), cc(P["w2"]))
        ye = ye.astype(jnp.float32) + P["b2"][:, None]
        y = jnp.einsum("tec,ecd->td", cc(combine), cc(ye))
        return y.astype(p.output_dtype).reshape(x.shape), None

    def __repr__(self):
        return (f"MoE({self.d_model}, hidden={self.hidden}, "
                f"experts={self.n_experts})")


class DroplessMoE(TensorModule):
    """Sigmoid top-k routed SwiGLU experts with an optional shared expert,
    told which experts it holds: (…, D) -> (…, D).

    The router scores all ``n_experts`` (``parallel.moe.
    sigmoid_topk_routing``); this layer computes the part of the result
    that the experts in ``experts_held`` give (all of them by default),
    plus the shared expert's, and leaves out what the absent ones would
    add: one chip's share under expert parallelism, without the exchange.
    No capacity and no dropped token: the assignments are sorted by expert
    and run as grouped products (``parallel.moe.grouped_experts``),
    ``chunk_rows`` sorted assignments at a time for as many passes as hold
    one.  A second pass pays the fixed part of a pass again (the
    scatter-adds' and the weight-gradient sums'), so the default is
    ``CHUNK_OF_EVEN_SHARE`` times what the layer would hold were the
    routing even; a builder who knows its routing passes its own.  What a
    pass costs beyond that follows the rows it is run over, padding
    included (all but the grouped products' kernels, which take the time
    of the rows held): so the last chunk's pass runs over its first rows
    only, the smallest of ``STEPS_OF_CHUNK`` equal steps of the chunk that
    holds its assignments, and a layer far under its chunk pays for the
    rows up to its next step, not for the chunk.

    Params: ``router`` (D, E); ``w_gate``, ``w_up`` (n_held, D, H) and
    ``w_down`` (n_held, H, D); ``shared_gate``/``shared_up`` (D, Hs) and
    ``shared_down`` (Hs, D) where ``shared_hidden``.  Buffers:
    ``route_bias`` (E,), added to the scores for the choice only, no
    gradient (``route_eps``: what the chosen scores' sum is raised by where
    ``route_norm`` divides by it, a family's own constant);
    ``tap_assignments_held``, ``tap_expert_max`` and
    ``tap_rows_moved``, the last call's count of assignments held here,
    its busiest expert's count and the rows its passes ran over
    (``obs.taps.module_counters`` hands them to the step's taps)."""

    # from a v5e at hidden 2048 x 1024, 16 of 128 experts held, top-8,
    # 16,384 tokens (PERF.md section 6, PRs 28 and 32): randomly initialised
    # layers with no balance term held 0.07 to 2.7 times the even share by
    # seed and step, most of them under it.  One layer's forward and
    # backward cost 14.4 ms + 0.42 ms a thousand rows passed + 0.43 ms a
    # thousand held (9.0 ms + the same since PR 36: 5.4 ms of the fixed part
    # were the routing's scalar gathers, their scatter, and a second top-k
    # and sort in the recomputation), and a second pass 6.8 ms before its
    # first row.  Passes of one or two even shares (a third pass for the
    # rare layer over two) pay for 1.5 times the rows held where one of
    # three paid for 3.5 times; a third size of pass would save ~1% of that
    # model's step more and adds as much code again to load at every start.
    # A second shape runs on the same two constants (hidden 2048 x 768,
    # top-6, 2 shared experts: deepseek_v3, PERF.md section 6, PR 33; even
    # share 12,288, passes of 12,288 or 24,576 rows): its routing collapses
    # under the cut, a layer holds 0.001 to 3.1 even shares, and
    # `rows_moved` over `assignments_held` comes to 1.73 over 80 layers and
    # steps (1.05 .. 1,024 a layer; the median layer 1.75)
    CHUNK_OF_EVEN_SHARE = 2
    STEPS_OF_CHUNK = 2

    def __init__(self, d_model: int, hidden: int, n_experts: int,
                 top_k: int, experts_held=None, route_norm: bool = True,
                 route_scale: float = 1.0, shared_hidden: int = 0,
                 chunk_rows=None, route_eps: float = 1e-20):
        super().__init__()
        self.d_model = d_model
        self.hidden = hidden
        self.n_experts = n_experts
        self.top_k = top_k
        self.experts_held = tuple(range(n_experts) if experts_held is None
                                  else experts_held)
        if len(set(self.experts_held)) != len(self.experts_held) or not all(
                0 <= e < n_experts for e in self.experts_held):
            raise ValueError(f"experts_held {self.experts_held} are not "
                             f"distinct ids under {n_experts}")
        self.route_norm = route_norm
        self.route_scale = route_scale
        self.shared_hidden = shared_hidden
        self.chunk_rows = chunk_rows
        self.route_eps = route_eps
        self.reset()

    def reset(self):
        d, h, held = self.d_model, self.hidden, len(self.experts_held)
        shapes = [("router", (d, self.n_experts)), ("w_gate", (held, d, h)),
                  ("w_up", (held, d, h)), ("w_down", (held, h, d))]
        if self.shared_hidden:
            hs = self.shared_hidden
            shapes += [("shared_gate", (d, hs)), ("shared_up", (d, hs)),
                       ("shared_down", (hs, d))]
        for name, shape in shapes:
            self._add_param(name, init_.normal_on_device(shape))
        self._add_buffer("route_bias", np.zeros((self.n_experts,),
                                                np.float32))
        self._add_buffer("tap_assignments_held", np.zeros((), np.float32))
        self._add_buffer("tap_expert_max", np.zeros((), np.float32))
        self._add_buffer("tap_rows_moved", np.zeros((), np.float32))
        return self

    def chunk_of(self, n_tokens: int):
        """(the most assignments this share can get of ``n_tokens``: every
        token's choices among the experts held; the rows of a chunk)."""
        n_held = len(self.experts_held)
        most = n_tokens * min(self.top_k, n_held)
        even = n_tokens * self.top_k * n_held / self.n_experts
        return most, min(most, self.chunk_rows or -(-int(
            self.CHUNK_OF_EVEN_SHARE * even) // 256) * 256)

    def _forward(self, P, x, S, ctx):
        from bigdl_tpu.parallel.moe import (grouped_experts, rows_moved,
                                            sigmoid_topk_routing,
                                            sort_assignments)
        xt = x.reshape(-1, x.shape[-1])
        k = self.top_k
        most, chunk = self.chunk_of(xt.shape[0])
        with jax.named_scope("MoeRoute"):
            idx, weights = sigmoid_topk_routing(
                xt, P["router"], S["route_bias"], k, self.route_norm,
                self.route_scale, self.route_eps)
            order, sizes = sort_assignments(idx, self.experts_held)
            # what the routing decided (with ``route_idx``, marked where it
            # is chosen), half a megabyte a layer: a ``Recompute`` around
            # the layer hands it to the backward pass, and the layer's
            # recomputation holds no top-k, no sort and no count
            order = kept(jnp.pad(order[:most], (0, -most % chunk)),
                         "route_order")
            sizes = kept(sizes, "route_sizes")
            y = grouped_experts(xt, P["w_gate"], P["w_up"], P["w_down"],
                                weights, order, sizes, chunk,
                                self.STEPS_OF_CHUNK, k,
                                policy().cast_compute)
        if self.shared_hidden:
            with jax.named_scope("MoeShared"):
                y = y + swiglu(xt, P["shared_gate"], P["shared_up"],
                               P["shared_down"])
        counts = sizes.astype(jnp.float32)
        moved = rows_moved(sizes, chunk, self.STEPS_OF_CHUNK)
        new_s = dict(S, tap_assignments_held=counts.sum(),
                     tap_expert_max=counts.max(),
                     tap_rows_moved=moved.astype(jnp.float32))
        return y.reshape(x.shape), new_s

    def __repr__(self):
        return (f"DroplessMoE({self.d_model}, hidden={self.hidden}, "
                f"top{self.top_k} of {self.n_experts}, holds "
                f"{len(self.experts_held)})")

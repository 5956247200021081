"""Containers (ref SURVEY.md §2.3: 8 containers).

Sequential (Sequential.scala:26), Concat (Concat.scala — the reference runs
branches on a thread pool, Concat.scala:73; under XLA the branches fuse into
one program and the compiler schedules them), ConcatTable, ParallelTable,
MapTable, Bottle (Bottle.scala).  Recurrent/TimeDistributed live in
``recurrent.py``.
"""
from __future__ import annotations

import contextlib
import contextvars

import numpy as np
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from bigdl_tpu.nn.module import Container, Module
from bigdl_tpu.utils.table import Table


def _child_apply(container, i, params, x, state, ctx):
    """The one place a container calls a child through.  The child's class
    name goes into the ``op_name`` of every operation it traces (forward
    as ``jvp(..)/SpatialMaxPooling/reduce_window_max``, backward under
    ``transpose(jvp(..))/..``): metadata only, the compiled instructions
    are the same, and a profile can be summed by module kind."""
    name = str(i)
    m = container.modules[i]
    with jax.named_scope(type(m).__name__):
        y, ns = m.apply(params[name], x, state[name], ctx)
    return y, ns


class Sequential(Container):
    """Chain modules serially (ref Sequential.scala:26)."""

    def apply(self, params, x, state, ctx):
        new_state = dict(state)
        for i in range(len(self.modules)):
            x, ns = _child_apply(self, i, params, x, state, ctx)
            new_state[str(i)] = ns
        return x, new_state


KEPT = "kept/"    # the one place that defines what a ``Recompute`` keeps


def kept(x, label: str):
    """Mark ``x`` as dear to recompute and small to keep: a ``Recompute``
    around the caller hands it to the backward pass instead of computing
    it again.  The code that owns a loop decides this, where the cost is
    known.  Under no ``Recompute`` (bare, or under a ``jax.checkpoint``
    with no policy) the mark is an identity that lowers to nothing."""
    return checkpoint_name(x, KEPT + label)


_kept_report = contextvars.ContextVar("kept_report", default=None)


@contextlib.contextmanager
def kept_report():
    """What the ``Recompute``s differentiated inside the block kept:
    ``{"kept": {label: bytes over all of them}, "layers": how many}``,
    complete when the gradient has been traced (the optimizer's step
    logs it as its ``recompute`` event)."""
    report = {"kept": {}, "layers": 0}
    token = _kept_report.set(report)
    try:
        yield report
    finally:
        _kept_report.reset(token)


class Recompute(Container):
    """Run the one child under ``jax.checkpoint``: the backward pass keeps
    the child's input, every array a module inside has marked with
    :func:`kept` (the attention core's output and logsumexp, the routed
    experts' sum: a loop each to make, one array to hold), and computes
    the other inner activations again.  A model whose layers are wrapped
    one by one holds one layer's activations at a time, plus each layer's
    marked arrays (``LocalOptimizer.set_gradient_checkpointing`` wraps the
    whole model, which bounds nothing by layer and keeps no mark).  Same
    parameters, same result, same gradient."""

    def __init__(self, module: Module):
        super().__init__(module)

    def apply(self, params, x, state, ctx):
        report = _kept_report.get()
        if report is not None:
            report["layers"] += 1

        def keep_marked(prim, *avals, **eqn):
            # asked once per operation when the gradient is traced
            if prim.name != "name" or not eqn["name"].startswith(KEPT):
                return False
            if report is not None:
                label = eqn["name"][len(KEPT):]
                a, = avals
                report["kept"][label] = (report["kept"].get(label, 0)
                                         + a.size * a.dtype.itemsize)
            return True

        inner = jax.checkpoint(
            lambda p, x_, s: _child_apply(self, 0, {"0": p}, x_, {"0": s},
                                          ctx), policy=keep_marked)
        y, ns = inner(params["0"], x, state["0"])
        return y, dict(state, **{"0": ns})


class Concat(Container):
    """Apply every branch to the same input, concatenate outputs along
    ``dimension`` (1-based, ref Concat.scala).

    TPU execution detail: when several branches START with a pointwise
    (1x1/s1/p0, grouped=1, biased) convolution of the shared input —
    the Inception block shape — those heads execute as ONE conv whose
    weight is the trace-time concat of the branch weights, and the
    result is sliced back per branch.  Exact same arithmetic and the
    identical parameter tree (the concat/slice pair is differentiable,
    so each branch's grads land on its own weight); what changes is the
    kernel economy: one (B*HW, C) x (C, sum(c_i)) MXU matmul instead of
    three skinny ones, in a step whose measured limiter is inter-kernel
    scheduling of many small kernels (PERF_NOTES round 3: ~6 ms/step of
    gaps; round 4 A/B table for this rewrite)."""

    def __init__(self, dimension: int, *modules):
        super().__init__(*modules)
        self.dimension = dimension

    def _merge_plan(self):
        """Branch indices whose leading module is a mergeable pointwise
        conv (>= 2 needed to merge).  Recomputed per apply — it is a
        microsecond loop that only runs at trace time under jit, and a
        cache would go stale if a branch head were surgically swapped
        between calls."""
        from bigdl_tpu.nn.conv import SpatialConvolution
        plan = []
        if self.dimension == 2:
            for i, br in enumerate(self.modules):
                if not (isinstance(br, Sequential) and br.modules):
                    continue
                c = br.modules[0]
                if (isinstance(c, SpatialConvolution)
                        and c.kernel_w == 1 and c.kernel_h == 1
                        and c.stride_w == 1 and c.stride_h == 1
                        and c.pad_w == 0 and c.pad_h == 0
                        and c.n_group == 1 and c.with_bias):
                    plan.append(i)
        return plan if len(plan) >= 2 else []

    def apply(self, params, x, state, ctx):
        plan = self._merge_plan()
        if plan and hasattr(x, "ndim") and x.ndim == 4:
            return self._apply_merged(params, x, state, ctx, plan)
        outs = []
        new_state = dict(state)
        for i in range(len(self.modules)):
            y, ns = _child_apply(self, i, params, x, state, ctx)
            outs.append(y)
            new_state[str(i)] = ns
        return jnp.concatenate(outs, axis=self.dimension - 1), new_state

    def _apply_merged(self, params, x, state, ctx, plan):
        from bigdl_tpu.nn.conv import _conv, bias_add
        heads = [params[str(i)]["0"]["~"] for i in plan]
        w = jnp.concatenate([h["weight"] for h in heads], axis=0)
        b = jnp.concatenate([h["bias"] for h in heads], axis=0)
        # the heads' own scope, as if each had run through _child_apply
        with jax.named_scope("SpatialConvolution"):
            merged = bias_add(_conv(x, w, (1, 1), [(0, 0), (0, 0)]), b)
        sizes = [h["weight"].shape[0] for h in heads]
        offs = np.cumsum([0] + sizes)
        slices = {i: merged[:, offs[k]:offs[k + 1]]
                  for k, i in enumerate(plan)}

        outs = []
        new_state = dict(state)
        for i in range(len(self.modules)):
            if i in slices:
                br = self.modules[i]
                bparams, bstate = params[str(i)], state[str(i)]
                y = slices[i]
                ns = dict(bstate)
                with jax.named_scope(type(br).__name__):
                    for j in range(1, len(br.modules)):
                        y, s_j = _child_apply(br, j, bparams, y, bstate,
                                              ctx)
                        ns[str(j)] = s_j
            else:
                y, ns = _child_apply(self, i, params, x, state, ctx)
            outs.append(y)
            new_state[str(i)] = ns
        return jnp.concatenate(outs, axis=self.dimension - 1), new_state


class ConcatTable(Container):
    """Apply every branch to the same input; output is a Table of results
    (ref ConcatTable.scala)."""

    def apply(self, params, x, state, ctx):
        out = Table()
        new_state = dict(state)
        for i in range(len(self.modules)):
            y, ns = _child_apply(self, i, params, x, state, ctx)
            out[i + 1] = y
            new_state[str(i)] = ns
        return out, new_state


class ParallelTable(Container):
    """i-th module consumes i-th element of the input Table
    (ref ParallelTable.scala)."""

    def apply(self, params, x, state, ctx):
        out = Table()
        new_state = dict(state)
        for i in range(len(self.modules)):
            y, ns = _child_apply(self, i, params, x[i + 1], state, ctx)
            out[i + 1] = y
            new_state[str(i)] = ns
        return out, new_state


class MapTable(Container):
    """Apply the same module to every element of the input Table
    (ref MapTable.scala).  The single child's parameters are shared across
    all elements — exactly the reference's clone-with-shared-storage."""

    def __init__(self, module: Module = None):
        super().__init__()
        if module is not None:
            self.add(module)

    def apply(self, params, x, state, ctx):
        out = Table()
        new_state = dict(state)
        n = x.length()
        ns = state["0"]
        for i in range(n):
            y, ns = self.modules[0].apply(params["0"], x[i + 1], ns, ctx)
            out[i + 1] = y
        new_state["0"] = ns
        return out, new_state


class Bottle(Container):
    """Flatten leading dims to apply an n-D module to higher-D input
    (ref Bottle.scala): input (d1..dk, rest) -> view (prod(d1..dk), rest)
    -> module -> restore leading dims."""

    def __init__(self, module: Module, n_input_dim: int = 2, n_output_dim: int = None):
        super().__init__(module)
        self.n_input_dim = n_input_dim
        self.n_output_dim = n_output_dim if n_output_dim is not None else n_input_dim

    def apply(self, params, x, state, ctx):
        in_shape = x.shape
        lead = in_shape[: x.ndim - self.n_input_dim + 1]
        rest = in_shape[x.ndim - self.n_input_dim + 1:]
        squashed = x.reshape((-1,) + rest)
        y, ns = _child_apply(self, 0, params, squashed, state, ctx)
        out_rest = y.shape[1:]
        y = y.reshape(lead + out_rest)
        new_state = dict(state)
        new_state["0"] = ns
        return y, new_state

"""Runner of the training front door: the window drives
``Optimizer(model, dataset, criterion, ...).optimize()`` with every default
(prefetch on, one step per dispatch) until a deadline trigger fires.

Set-up builds the one optimizer, drives it from the seed through its first
three steps by the same call and feed (one ``optimize()`` of one step, whose
result gives the first gradient, then one of two more), and hands that same
object to the window.  After the window the plain reference follows those
three steps from its own weights and the harness's own records.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from benchmark import check, traffic
from benchmark.program import (build_model, from_program_tree,
                               load_reference, to_program_tree)


class _Tap:
    """Last stage of the feed: passes every batch on and notes, for the
    first few of each pass, which records it holds (their first pixel
    identifies them), so that the reference can be given the same rows."""

    def __init__(self, keep):
        from bigdl_tpu.dataset.transformer import Transformer
        self.passes = []            # one list of batches per pass started
        tap = self

        class Stage(Transformer):
            def __call__(self, iterator):
                seen = []
                tap.passes.append(seen)
                for batch in iterator:
                    if len(seen) < keep:
                        seen.append(np.array(batch.data[:, 0, 0, 0]))
                    yield batch

        self.stage = Stage()


class _Stop:
    """The end trigger.  In set-up it ends a call after a number of
    iterations.  In the window's call it lets ``warm`` iterations pass
    (the call traces and loads its step anew, and its feed fills), waits
    for the device to drain, opens the window, and fires at the deadline.
    It runs on the loop's own thread, between two iterations."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.opt = None
        self.max_neval = None
        self.open_at = None         # neval at which the window opens
        self.neval_open = None
        self.spans_open = None
        self.deadline = None
        self.ticks = []             # (clock, neval) at every call once open

    def __call__(self, state):
        neval = state.get("neval", 0)
        if self.open_at is None:
            return neval > self.max_neval
        if self.deadline is None:
            if neval < self.open_at:
                return False
            _drain_device()
            self.ctx.lap("window_call_warm_up")
            self.neval_open = neval
            self.spans_open = _span_totals(self.opt)
            self.deadline = self.ctx.open_window() + self.ctx.seconds
            self.ticks.append((self.ctx.t_open, neval))
            return False
        now = time.perf_counter()
        self.ticks.append((now, neval))
        return now >= self.deadline


def _drain_device():
    """Wait until everything dispatched so far has run: the chip runs one
    stream in order, so a tiny operation queued now ends after it."""
    import jax.numpy as jnp
    (jnp.zeros((), jnp.float32) + 1).block_until_ready()


def _span_totals(opt):
    return {path: (total, count)
            for path, _, _, total, count in opt.spans.rows()}


def run(ctx):
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu import tensor as bt
    from bigdl_tpu.dataset import DataSet, Sample
    from bigdl_tpu.dataset.transformer import SampleToBatch
    from bigdl_tpu.obs import events
    from bigdl_tpu.optim import SGD, Optimizer
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.utils.random import set_seed
    from bigdl_tpu.utils.table import T

    ref = load_reference(ctx.config)
    cfg, mix = ctx.config, ctx.traffic
    batch = mix["batch"]
    opt_cfg = cfg["optimizer"]
    seed = traffic.seed32(ctx.seed)
    ctx.lap("import")

    # weights: on the device, in one jitted call from the seed
    bt.set_policy(getattr(bt, cfg["program"]["policy"]))
    p0 = jax.jit(lambda k: ref.init_params(k, cfg))(jax.random.PRNGKey(seed))
    jax.block_until_ready(p0)
    ctx.lap("weights")
    set_seed(seed)
    model = build_model(cfg)
    names = list(ref.param_shapes(cfg))      # construction order
    model.load_params(to_program_tree(model.params(), p0, names))
    ctx.lap("model_build")

    images, labels = traffic.image_records(
        mix, ctx.seed, tuple(cfg["input"]), cfg["classes"])
    samples = [Sample(images[i], labels[i:i + 1]) for i in range(len(images))]
    tap = _Tap(keep=3)
    dataset = (DataSet.array(samples)
               >> SampleToBatch(batch, drop_last=True) >> tap.stage)
    stop = _Stop(ctx)
    opt = Optimizer(model, dataset, nn.ClassNLLCriterion(),
                    optim_method=SGD(),
                    state=T(learningRate=opt_cfg["learning_rate"],
                            momentum=opt_cfg["momentum"],
                            weightDecay=opt_cfg["weight_decay"]),
                    end_trigger=Trigger(stop, "benchmark"))
    stop.opt = opt
    ctx.lap("data")

    # the first three steps, through the window's own call and feed
    log = events.configure(None, ring=100000)
    set_seed(seed)                      # dropout keys count from here
    _drain_device()                     # compiles the barrier's program
    stop.max_neval = 1
    opt.optimize()
    p1 = from_program_tree(model.params(), names)
    second_call = len(tap.passes)       # the feed starts a pass an epoch
    stop.max_neval = 3
    opt.optimize()
    p3 = from_program_tree(model.params(), names)
    fed = {"a": tap.passes[0], "b": tap.passes[second_call]}
    losses = [e["loss"] for e in log.ring_events() if e["type"] == "step"]
    ctx.lap("first_steps")

    # the window opens inside this call, once its warm-up steps are done
    stop.open_at = int(opt.state["neval"]) + ctx.cell["warm_steps"]
    opt.optimize()
    jax.block_until_ready(model.params())
    t_open, t_close = ctx.t_open, ctx.close_window()

    neval0 = stop.neval_open
    steps = int(opt.state["neval"]) - neval0
    window_events = [e for e in log.ring_events()
                     if e.get("step", 0) >= neval0]
    step_losses = [e["loss"] for e in window_events if e["type"] == "step"]
    failed = sum(1 for v in step_losses if not np.isfinite(v))
    spans1 = _span_totals(opt)
    spans0 = stop.spans_open
    spans = {path: (spans1[path][0] - spans0.get(path, (0.0, 0))[0],
                    spans1[path][1] - spans0.get(path, (0.0, 0))[1])
             for path in spans1}
    wall = t_close - t_open
    obs = {"steps": steps, "records": steps * batch, "batch": batch,
           "spans": spans, "wall_s": wall}
    if ctx.traced:
        obs["program_text"] = _program_text(opt, model, images, labels,
                                            batch)
    detail = {"records_per_s_by_slice": _slice_rates(
        stop.ticks, t_open, ctx.seconds, batch)}

    # free the program's state, then let the reference follow the steps
    del opt, dataset, samples, model
    rows = _rows_fed(fed, images)
    checks = _compare(ctx, ref, cfg, p0, p1, p3, losses, rows, images,
                      labels, seed, batch)
    return {"attempted": steps, "failed": failed,
            "end_to_end": {"train_records_per_s": steps * batch / wall},
            "obs": obs, "checks": checks, "detail": detail}


def _slice_rates(ticks, t_open, seconds, batch, slice_s=10.0):
    """Records per second in each ``slice_s`` of the window, from the
    iteration counts the end trigger saw: whether a run's rate drifts
    inside the window or differs from run to run.  Beside the metric, not
    the metric."""
    rates, k = [], 0
    at = lambda t: max((n for when, n in ticks if when <= t),
                       default=ticks[0][1])
    while (k + 1) * slice_s <= seconds + 1e-9:
        a, b = t_open + k * slice_s, t_open + (k + 1) * slice_s
        rates.append((at(b) - at(a)) * batch / slice_s)
        k += 1
    return rates


def _program_text(opt, model, images, labels, batch):
    """The compiled step's text, in a traced run only: the program's own
    jitted step lowered on the window's shapes (served from the compile
    cache), for the join of device operations to source files.  It reaches
    for the optimizer's step builder, which has no public name; where that
    moves, the traced run fails here rather than drop the kernels' metrics."""
    import jax
    import jax.numpy as jnp

    from benchmark.trace import ProgramText
    step = opt._build_step()
    params = model.params()
    shape = jax.ShapeDtypeStruct
    like = lambda t: jax.tree_util.tree_map(
        lambda a: shape(a.shape, a.dtype), t)
    lowered = step.jitted.lower(
        like(params), like(model.state()),
        like(opt.optim_method.init_state(params)),
        shape((batch, *images.shape[1:]), jnp.float32),
        shape((batch, 1), jnp.float32), shape((), jnp.float32),
        like(jax.random.PRNGKey(0)), opt._lr_scales_arg)
    return ProgramText(lowered.compile().as_text())


def _rows_fed(fed, images):
    """Which records each of the three steps saw: step 1 is the first
    batch of the first call, steps 2 and 3 the first two of the second."""
    first_pixel = {float(images[i, 0, 0, 0]): i for i in range(len(images))}
    batches = [fed["a"][0], fed["b"][0], fed["b"][1]]
    return [np.array([first_pixel[float(v)] for v in b]) for b in batches]


def _host(tree):
    return {k: {a: np.asarray(b) for a, b in v.items()}
            for k, v in tree.items()}


def _compare(ctx, ref, cfg, p0, p1, p3, losses, rows, images, labels, seed,
             batch):
    """The reference's three steps against the program's: each step's
    loss, the first gradient as the optimizer gets it (worked out from the
    parameters after one step), the parameters' change after three."""
    opt_cfg = cfg["optimizer"]
    lr, wd = opt_cfg["learning_rate"], opt_cfg["weight_decay"]
    keep = 1.0 - opt_cfg["dampening"]
    reference = reference_steps(
        ref, cfg, p0, rows, images, labels, seed, batch,
        block=ctx.traffic["reference_block"])
    p0_host = _host(p0)
    # program: v1 = (p0 - p1) / lr = (1 - dampening) * (g + wd * p0)
    g1 = {k: {a: (p0_host[k][a] - p1[k][a]) / (lr * keep)
              - wd * p0_host[k][a] for a in p1[k]} for k in p1}
    return compare_numbers(ctx.limits, p0_host, reference,
                           (losses, g1, p3))


def compare_numbers(limits, p0_host, reference, other):
    """[(name, value, limit)] of ``other`` = (losses, first gradient,
    parameters after three steps) against the reference's same three.
    The gaps of norms catch a step that does other work (a state left
    unchanged, rows left out); they are second order in unbiased rounding,
    so a lower precision passes them.  ``change3_error``, the norm of the
    whole change's difference from the reference's over the reference's
    norm, is first order in it, and is what the control fails."""
    ref_losses, ref_g1, ref_p3 = reference
    losses, g1, p3 = other
    change = {k: {a: np.asarray(p3[k][a]) - p0_host[k][a] for a in p3[k]}
              for k in p3}
    ref_change = {k: {a: np.asarray(ref_p3[k][a]) - p0_host[k][a]
                      for a in ref_p3[k]} for k in ref_p3}
    ref_g1_norms = check.leaf_norms(ref_g1)
    g1_norms, change_norms = check.leaf_norms(g1), check.leaf_norms(change)
    ref_change_norms = check.leaf_norms(ref_change)
    grad_gap, _ = check.worst_leaf_gap(g1_norms, ref_g1_norms)
    skip = check.negligible_gradient_leaves(ref_g1_norms)
    change_gap, _ = check.worst_leaf_gap(change_norms, ref_change_norms,
                                         skip=skip)
    change_error = check.tree_relative_error(change, ref_change)
    loss_gap = (max(abs(a - b) / abs(b)
                    for a, b in zip(losses[:3], ref_losses))
                if len(losses) >= 3 else float("inf"))
    for label, prog, refn in (("grad1", g1_norms, ref_g1_norms),
                              ("change3", change_norms, ref_change_norms)):
        for row in check.leaf_gap_table(prog, refn, top=3):
            print(f"check detail: {label} {row[0]} gap {row[1]:.4g} "
                  f"norm {row[2]:.6g} reference {row[3]:.6g}",
                  file=sys.stderr)
    print(f"check detail: losses {list(losses[:3])} reference "
          f"{ref_losses}; {len(skip)} leaves left out of the change",
          file=sys.stderr)
    return [("loss_gap", loss_gap, limits["loss_gap"]),
            ("first_grad_gap", grad_gap, limits["first_grad_gap"]),
            ("change3_gap", change_gap, limits["change3_gap"]),
            ("change3_error", change_error, limits["change3_error"])]


def variant_numbers(cell, config, seed, what, sizes=None):
    """The check's numbers with the reference put in the program's place,
    no program run: ``control`` computes it with fp8 operands,
    ``half_batch`` leaves half of every batch out and takes the mean over
    the rest.  Same weights, records and dropout keys as a run of ``seed``;
    the rows are the first batches in storage order."""
    import jax

    from benchmark.harness import apply_sizes
    mix, cfg, limits = apply_sizes(cell, config, sizes)
    ref = load_reference(cfg)
    batch, s32 = mix["batch"], traffic.seed32(seed)
    p0 = jax.jit(lambda k: ref.init_params(k, cfg))(jax.random.PRNGKey(s32))
    images, labels = traffic.image_records(mix, seed, tuple(cfg["input"]),
                                           cfg["classes"])
    rows = [np.arange(k * batch, (k + 1) * batch) for k in range(3)]
    args = (ref, cfg, p0, rows, images, labels, s32, batch)
    block = mix["reference_block"]
    reference = reference_steps(*args, block=block)
    if what == "control":
        other = reference_steps(*args, block=block, quant="fp8")
    elif what == "half_batch":
        other = reference_steps(*args, block=block, rows_used=batch // 2)
    else:
        raise ValueError(f"unknown variant {what!r}")
    return compare_numbers(limits, _host(p0), reference,
                           (other[0], other[1], _host(other[2])))


def reference_steps(ref, cfg, p0, rows, images, labels, seed, batch, block,
                    quant=None, restarts=(0, 1), rows_used=None):
    """Three steps of the reference from ``p0``: returns (losses, the first
    step's gradient, the parameters after the third).  ``restarts`` are the
    steps (0-based) at which the optimizer's state starts from zero: the
    front door builds it anew in every ``optimize()`` call.  ``rows_used``
    (< batch) leaves the other rows of every batch out and takes the mean
    over the rest: the half-batch fault."""
    import jax
    import jax.numpy as jnp

    block_grad = ref.make_block_grad(cfg, quant)
    tmap = jax.tree_util.tree_map
    zeros = jax.jit(lambda p: tmap(jnp.zeros_like, p))
    add = jax.jit(lambda a, b: tmap(jnp.add, a, b))

    @jax.jit
    def finish(params, velocity, grad_sum):
        grads = tmap(lambda a: a / used, grad_sum)
        return grads, ref.sgd_update(params, velocity, grads,
                                     cfg["optimizer"])

    used = rows_used or batch
    params = p0
    losses, g1 = [], None
    velocity = None
    for k, ids in enumerate(rows):
        if k in restarts:
            velocity = zeros(params)
        key = ref.dropout_key(seed, k + 1)
        total, grad_sum = 0.0, zeros(params)
        for r0 in range(0, used, block):
            take = ids[r0:min(r0 + block, used)]
            loss, g = block_grad(params, jnp.asarray(images[take]),
                                 jnp.asarray(labels[take]), key, r0, batch)
            total += float(loss)
            grad_sum = add(grad_sum, g)
        grads, (params, velocity) = finish(params, velocity, grad_sum)
        losses.append(total / used)
        if k == 0:
            g1 = {n: {a: np.asarray(b) for a, b in leaf.items()}
                  for n, leaf in grads.items()}
    return losses, g1, params

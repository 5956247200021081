"""Runner of the training front door for a language model: the window drives
``Optimizer(model, DataSet.array(sequences) >> SampleToBatch(b),
TimeDistributedCriterion(ClassNLLCriterion), SGD).optimize()`` with every
default until a deadline trigger fires, as ``runners/train.py`` does for
images.  A record is one sequence of ``seq_len`` token ids; its targets are
the ids shifted by one.

Set-up builds the one optimizer, drives it from the seed through its first
three steps by the window's own call and feed, and hands that same object to
the window.  After the window, with the program's state freed, the plain
reference follows those three steps on the same sequences, one sequence a
block, and holds no more than the parameters, one gradient sum and one block
gradient on the device at a time (the momentum waits on the host).
"""
from __future__ import annotations

import gc
import importlib
import sys

import numpy as np

from benchmark import traffic
from benchmark.program import (build_model, from_program_tree,
                               load_reference, to_program_tree)
from benchmark.runners.train import (_Stop, _drain_device, _slice_rates,
                                     _span_totals, compare_numbers)


def token_records(mix, seed, vocab):
    """``records`` sequences of ``seq_len`` + 1 token ids (1-based, float32
    as the feed carries them), Zipf with the mix's exponent over the
    ``vocab`` ids of the slice, the ranks dealt to the ids by the seed."""
    rng = np.random.Generator(np.random.PCG64(traffic.seed32(seed)))
    weights = 1.0 / np.arange(1, vocab + 1) ** float(mix["zipf_exponent"])
    cdf = np.cumsum(weights / weights.sum())
    ranks = np.searchsorted(
        cdf, rng.random((int(mix["records"]), int(mix["seq_len"]) + 1)))
    ids = rng.permutation(vocab)[np.minimum(ranks, vocab - 1)] + 1
    return ids.astype(np.float32)


class _Tap:
    """Last stage of the feed: notes, for the first few batches of each
    pass, which sequences a batch holds."""

    def __init__(self, keep, tokens):
        from bigdl_tpu.dataset.transformer import Transformer
        self.passes = []
        tap = self

        class Stage(Transformer):
            def __call__(self, iterator):
                seen = []
                tap.passes.append(seen)
                for batch in iterator:
                    if len(seen) < keep:
                        seen.append(np.array([
                            np.flatnonzero((tokens[:, :-1] == row)
                                           .all(axis=1))[0]
                            for row in np.asarray(batch.data)]))
                    yield batch

        self.stage = Stage()


def _host(tree):
    return {k: {a: np.asarray(b) for a, b in v.items()}
            for k, v in tree.items()}


def run(ctx):
    import jax

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
    # a program without the builder (the parent of the PR that brought the
    # configuration) fails here, before anything is put on the device
    importlib.import_module(cfg["program"]["builder"].split(":")[0])
    batch = mix["sequences_per_step"]
    opt_cfg = cfg["optimizer"]
    seed = traffic.seed32(ctx.seed)
    ctx.lap("import")

    # weights: on the device, in one jitted call from the seed; the model
    # takes the arrays themselves, the check keeps a copy on the host
    bt.set_policy(getattr(bt, cfg["program"]["policy"]))
    p0 = jax.jit(lambda k: ref.init_params(k, cfg))(jax.random.PRNGKey(seed))
    jax.block_until_ready(p0)
    ctx.lap("weights")
    set_seed(seed)
    model = build_model(cfg)
    names = list(ref.param_shapes(cfg))      # construction order
    model.load_params(to_program_tree(model.params(), p0, names))
    p0_host = _host(p0)
    del p0
    ctx.lap("model_build")

    tokens = token_records(mix, ctx.seed, cfg["vocab_size"])
    samples = [Sample(row[:-1], row[1:]) for row in tokens]
    tap = _Tap(keep=3, tokens=tokens)
    dataset = (DataSet.array(samples)
               >> SampleToBatch(batch, drop_last=True) >> tap.stage)
    stop = _Stop(ctx)
    opt = Optimizer(
        model, dataset,
        nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), True),
        optim_method=SGD(),
        state=T(learningRate=opt_cfg["learning_rate"],
                momentum=opt_cfg["momentum"],
                dampening=opt_cfg["dampening"],
                weightDecay=opt_cfg["weight_decay"]),
        end_trigger=Trigger(stop, "benchmark"))
    stop.opt = opt
    ctx.lap("data")

    # the first three steps, through the window's own call and feed
    log = events.configure(None, ring=100000)
    set_seed(seed)
    _drain_device()
    stop.max_neval = 1
    opt.optimize()
    p1 = from_program_tree(model.params(), names)
    second_call = len(tap.passes)
    stop.max_neval = 3
    opt.optimize()
    p3 = from_program_tree(model.params(), names)
    rows = [tap.passes[0][0], tap.passes[second_call][0],
            tap.passes[second_call][1]]
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
                     if e["type"] == "step" and e.get("step", 0) >= neval0]
    failed = sum(1 for e in window_events if not np.isfinite(e["loss"]))
    spans1, spans0 = _span_totals(opt), stop.spans_open
    spans = {path: (spans1[path][0] - spans0.get(path, (0.0, 0))[0],
                    spans1[path][1] - spans0.get(path, (0.0, 0))[1])
             for path in spans1}
    wall = t_close - t_open
    obs = {"steps": steps, "records": steps * batch, "batch": batch,
           "seq_len": int(mix["seq_len"]), "spans": spans, "wall_s": wall,
           "expert_counters": _expert_counters(window_events)}
    if ctx.traced:
        obs["program_text"] = _program_text(opt, model, batch,
                                            int(mix["seq_len"]))
    detail = {"records_per_s_by_slice": _slice_rates(
        stop.ticks, t_open, ctx.seconds, batch),
        "window_losses": [window_events[0]["loss"],
                          window_events[-1]["loss"]] if window_events
        else [],
        # first and last reading of the window, by expert layer: the
        # routing drifts towards the experts held (PERF.md)
        "assignments_held": (
            obs["expert_counters"]["assignments_held"][:1]
            + obs["expert_counters"]["assignments_held"][-1:])}

    # free the program's state, then let the reference follow the steps
    del opt, dataset, samples, model, stop.opt
    gc.collect()
    checks = _compare(ctx, ref, cfg, p0_host, p1, p3, losses, rows, tokens)
    return {"attempted": steps, "failed": failed,
            "end_to_end": {"train_records_per_s": steps * batch / wall},
            "obs": obs, "checks": checks, "detail": detail}


def _expert_counters(step_events):
    """{'assignments_held' | 'expert_max': [[one number per expert layer]
    per step whose event carries the taps]}, from ``DroplessMoE``'s
    counters as the step events have them (the taps' cadence)."""
    out = {"assignments_held": [], "expert_max": []}
    for e in step_events:
        taps = e.get("taps") or {}
        for name, rows in out.items():
            keys = sorted((k for k in taps if k.startswith(name + "/")),
                          key=lambda k: int(k.rsplit("/", 1)[1]))
            if keys:
                rows.append([taps[k] for k in keys])
    return out


def _program_text(opt, model, batch, seq_len):
    """The compiled step's text, in a traced run only (as
    ``runners/train.py``: through the optimizer's step builder, served from
    the compile cache)."""
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
        shape((batch, seq_len), jnp.float32),
        shape((batch, seq_len), jnp.float32), shape((), jnp.float32),
        like(jax.random.PRNGKey(0)), opt._lr_scales_arg)
    return ProgramText(lowered.compile().as_text())


def _compare(ctx, ref, cfg, p0_host, p1, p3, losses, rows, tokens):
    """The reference's three steps against the program's (``runners/
    train.py compare_numbers``): each step's loss, the first gradient as
    the optimizer gets it (worked out from the parameters after one step),
    the parameters' change after three."""
    opt_cfg = cfg["optimizer"]
    lr, wd = opt_cfg["learning_rate"], opt_cfg["weight_decay"]
    keep = 1.0 - opt_cfg["dampening"]
    # program: v1 = (p0 - p1) / lr = (1 - dampening) * (g + wd * p0)
    g1 = {k: {a: (p0_host[k][a] - p1[k][a]) / (lr * keep)
              - wd * p0_host[k][a] for a in p1[k]} for k in p1}
    del p1
    reference = reference_steps(ref, cfg, p0_host, rows, tokens, ctx.traffic)
    if ctx.traced:
        _routing_detail(ref, cfg, p0_host, tokens[rows[0][0]], ctx.traffic)
    from benchmark import check
    for row in check.leaf_gap_table(check.leaf_norms(g1),
                                    check.leaf_norms(reference[1]), top=12):
        print(f"check detail: grad1 {row[0]} gap {row[1]:.4g} norm "
              f"{row[2]:.6g} reference {row[3]:.6g}", file=sys.stderr)
    return compare_numbers(ctx.limits, p0_host, reference, (losses, g1, p3))


def _routing_detail(ref, cfg, p0_host, sequence, mix):
    """For PERF.md: how many (token, expert layer) choices move when the
    operands upstream of the router are rounded to bfloat16, as the
    program's are: the reference's routing against its own with bf16
    operands, on the first sequence of the first step."""
    import jax
    import jax.numpy as jnp
    choose = jax.jit(lambda p, ids, quant: ref.routing_choices(
        p, ids, cfg, quant, query_chunk=mix.get("reference_query_chunk")),
        static_argnums=(2,))
    params = jax.tree_util.tree_map(jnp.asarray, p0_host)
    ids = jnp.asarray(sequence[:-1])
    plain, rounded = choose(params, ids, None), choose(params, ids, "bf16")
    moved = [float(jnp.mean(jnp.any(jnp.sort(a, -1) != jnp.sort(b, -1),
                                    axis=-1)))
             for a, b in zip(plain, rounded)]
    print("check detail: share of (token, layer) expert choices that "
          f"bf16 operands move, by expert layer: {moved}", file=sys.stderr)


def reference_steps(ref, cfg, p0_host, rows, tokens, mix, quant=None,
                    fault=None, restarts=(0, 1), rows_used=None):
    """Three steps of the reference from ``p0_host``: returns (losses, the
    first step's gradient, the parameters after the third), on the host.
    ``restarts``: the steps (0-based) at which the momentum starts from
    zero, as in every ``optimize()`` call.  ``rows_used`` (< the batch)
    leaves the other sequences of every batch out: the half-batch fault."""
    import jax
    import jax.numpy as jnp

    block_grad = ref.make_block_grad(
        cfg, quant, fault, query_chunk=mix.get("reference_query_chunk"),
        remat=bool(mix.get("reference_remat")))
    tmap = jax.tree_util.tree_map
    add = jax.jit(lambda a, b: tmap(jnp.add, a, b), donate_argnums=(0,))
    opt_cfg = cfg["optimizer"]

    def finish(params, velocity, grad_sum, used):
        grads = tmap(lambda a: a / used, grad_sum)
        if velocity is None:
            velocity = tmap(jnp.zeros_like, params)
        return (grads,) + ref.sgd_update(params, velocity, grads, opt_cfg)

    finish_fresh = jax.jit(lambda p, g, used: finish(p, None, g, used),
                           donate_argnums=(0, 1), static_argnums=(2,))
    finish_on = jax.jit(finish, donate_argnums=(0, 1, 2),
                        static_argnums=(3,))

    params = tmap(jnp.asarray, p0_host)
    losses, g1, velocity_host = [], None, None
    for k, ids in enumerate(rows):
        used = ids[:rows_used] if rows_used else ids
        total, grad_sum = 0.0, None
        for r in used:
            loss, g = block_grad(params, jnp.asarray(tokens[r, :-1]),
                                 jnp.asarray(tokens[r, 1:]))
            total += float(loss)
            grad_sum = g if grad_sum is None else add(grad_sum, g)
            del g
        if k in restarts:
            grads, params, velocity = finish_fresh(params, grad_sum,
                                                   len(used))
        else:
            grads, params, velocity = finish_on(
                params, tmap(jnp.asarray, velocity_host), grad_sum,
                len(used))
        del grad_sum
        losses.append(total / len(used))
        if k == 0:
            g1 = _host(grads)
        del grads
        # the momentum waits on the host while the next step's blocks run
        velocity_host = (_host(velocity) if k + 1 < len(rows)
                         and k + 1 not in restarts else None)
        del velocity
    return losses, g1, _host(params)


def variant_numbers(cell, config, seed, what, sizes=None):
    """The check's numbers with the reference put in the program's place,
    no program run: ``control`` computes it with fp8 operands,
    ``half_batch`` leaves half of every batch out, ``full_window`` gives
    the window layers every earlier key, ``capacity`` drops the tokens over
    a capacity factor of 1.25.  Same weights and sequences as a run of
    ``seed``; the rows are the first batches in storage order."""
    import jax

    from benchmark.harness import apply_sizes
    mix, cfg, limits = apply_sizes(cell, config, sizes)
    ref = load_reference(cfg)
    batch, s32 = mix["sequences_per_step"], traffic.seed32(seed)
    p0_host = _host(jax.jit(lambda k: ref.init_params(k, cfg))(
        jax.random.PRNGKey(s32)))
    tokens = token_records(mix, seed, cfg["vocab_size"])
    rows = [np.arange(k * batch, (k + 1) * batch) for k in range(3)]
    args = (ref, cfg, p0_host, rows, tokens, mix)
    reference = reference_steps(*args)
    if what == "control":
        other = reference_steps(*args, quant="fp8")
    elif what == "half_batch":
        other = reference_steps(*args, rows_used=batch // 2)
    elif what in ("full_window", "capacity"):
        other = reference_steps(*args, fault=what)
    else:
        raise ValueError(f"unknown variant {what!r}")
    return compare_numbers(limits, p0_host, reference, other)

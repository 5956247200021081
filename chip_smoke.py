"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, in ONE process, through the entry points a
user calls, at the full width of models the repo supports (weights random,
from ``--seed``; data synthetic, from the same seed):

  python chip_smoke.py            # one chip (what the driver runs)
  python chip_smoke.py --chips 4  # the paths that exist only across chips

One chip:
  train   ``Optimizer(model, dataset, criterion).optimize()`` — the real
          LocalOptimizer loop with its prefetch, taps and executable cache —
          on Inception-v1 (1000 classes, batch 128, 3x224x224) and on the
          Bi-LSTM text classifier (batch 128, T=500, embed 200, hidden 128),
          bf16-compute policy.  Checks: finite loss, lower at the last step
          than at the first, parameters on a TPU device, and for the Bi-LSTM
          a Mosaic kernel (``tpu_custom_call``) inside the compiled step.
  serve   a ``ServeEngine`` on the trained Inception-v1 answering ``submit``
          calls (checked against a direct forward at the served bucket
          shape), and a paged ``ContinuousDecoder`` on ``TransformerLM``
          d_model 1024 / 4 heads / FFN 4096 / 6 layers / vocab 4096 —
          token for token against serial ``lm_decode``, once with the XLA
          attention path and once with the paged-attention and spec-verify
          Mosaic kernels on.

Four chips (``--chips 4``; only the cross-chip paths and what they are
compared with): data-parallel ``Optimizer`` on a ``distributed=True``
dataset over a 4-device ``data`` mesh against the same steps on one chip;
the tensor-parallel decoder over a 4-wide ``model`` axis, kernels off and
on; four in-process decode replicas, one per chip, behind the router.

Every phase prints one JSON line of what it saw (smoke timings, not
benchmark results).  The LAST line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Anything else — no TPU, a phase that raises, an array on a non-TPU device,
a kernel that did not compile into the step, a token that differs — ends the
run non-zero with ``"ok": false``.  No phase is skipped.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
import traceback

import numpy as np

#: the sizes the driver's run uses; ``run()`` takes another dict so a CPU
#: rehearsal (tests/test_chip_smoke.py) can drive the same code at toy sizes
FULL = {
    "inception": {"batch": 128, "classes": 1000, "steps": 10, "lr": 0.1},
    "bilstm": {"batch": 128, "seq": 500, "embed": 200, "hidden": 128,
               "classes": 20, "steps": 8, "lr": 0.05},
    "serve": {"max_batch": 8, "requests": 11},
    "lm": {"vocab": 4096, "d_model": 1024, "heads": 4, "layers": 6,
           "hidden": 4096, "prompt": 256, "words": 32, "slots": 8,
           "prompts": 10, "page": 16, "spec_k": 4},
    # four-chip phase: fewer decode requests; the replicas' model keeps the
    # width and cuts depth, because every chip compiles its own programs
    "tp_prompts": 4, "replica_layers": 2, "replica_requests": 8,
}


def say(**fields):
    """One JSON line per fact worth keeping (never the last line)."""
    print(json.dumps(fields, default=str), flush=True)


class CompileMeter:
    """Backend-compile seconds and persistent-cache hits/misses, read from
    jax's own monitoring events — so each phase reports what it compiled
    and whether the compile cache served it."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


@contextlib.contextmanager
def phase(name, meter, **facts):
    """Time a phase, attribute compile seconds and cache traffic to it,
    and print its line — only if the body did not raise."""
    out = dict(facts)
    c0, h0, m0 = meter.snapshot()
    t0 = time.perf_counter()
    yield out
    c1, h1, m1 = meter.snapshot()
    wall = time.perf_counter() - t0
    say(phase=name, wall_s=round(wall, 2), compile_s=round(c1 - c0, 2),
        run_s=round(wall - (c1 - c0), 2), cache_hits=h1 - h0,
        cache_misses=m1 - m0, **out)


def require(cond, message):
    if not cond:
        raise AssertionError(message)


def on_platform(tree, platform):
    """Every array leaf of ``tree`` lives on ``platform`` devices only."""
    import jax
    kinds = {d.platform for leaf in jax.tree_util.tree_leaves(tree)
             if hasattr(leaf, "devices") for d in leaf.devices()}
    return kinds == {platform}


def leaf_devices(tree):
    import jax
    return sorted({str(d) for leaf in jax.tree_util.tree_leaves(tree)
                   if hasattr(leaf, "devices") for d in leaf.devices()})


# ---------------------------------------------------------------------------
# training through the front door
# ---------------------------------------------------------------------------

def spy_first_step(opt):
    """Make ``opt`` record, from the real loop's own dispatches, the
    compiled text of its step program (first dispatch) and where the
    carried parameters and the batch live (second dispatch: by then the
    parameters are the step's own outputs).  Returns the dict the facts
    land in.  The loop is untouched: the spy lowers and compiles the same
    jitted function with the same arguments — the loop's dispatch then
    reads that program back from the compile cache."""
    seen = {}
    build = opt._build_step

    def build_and_spy():
        step = build()

        def spied(params, net_state, opt_state, x, *rest):
            if "text" not in seen:
                seen["text"] = step.jitted.lower(
                    params, net_state, opt_state, x,
                    *rest).compile().as_text()
            elif "params_on" not in seen:
                seen.update(
                    params_on=leaf_devices(params),
                    batch_on=leaf_devices(x),
                    batch_shard=list(x.addressable_shards[0].data.shape))
            return step(params, net_state, opt_state, x, *rest)

        spied.fn_key = step.fn_key
        return spied

    opt._build_step = build_and_spy
    return seen


def train(model, samples, cfg, distributed=False, **optimizer_kwargs):
    """A few iterations of ``Optimizer(...).optimize()``; returns
    (per-step losses, what the first step saw, optimizer)."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.transformer import SampleToBatch
    from bigdl_tpu.obs import events
    from bigdl_tpu.optim import Optimizer, SGD, max_iteration
    from bigdl_tpu.utils.table import T

    dataset = (DataSet.array(samples, distributed=distributed)
               >> SampleToBatch(cfg["batch"], drop_last=True))
    opt = Optimizer(model, dataset, nn.ClassNLLCriterion(),
                    optim_method=SGD(),
                    state=T(learningRate=cfg["lr"], momentum=0.9),
                    end_trigger=max_iteration(cfg["steps"]),
                    **optimizer_kwargs)
    first_step = spy_first_step(opt)
    log = events.configure(None)        # fresh in-memory ring per run
    opt.optimize()
    losses = [e["loss"] for e in log.ring_events() if e["type"] == "step"]
    require(len(losses) == cfg["steps"],
            f"expected {cfg['steps']} step events, saw {len(losses)}")
    require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    require(losses[-1] < losses[0],
            f"loss did not fall over {cfg['steps']} steps: {losses}")
    return losses, first_step, opt


def image_samples(rs, cfg, shape):
    """One batch worth of class-separable synthetic images (the set
    repeats every iteration, so a working step must lower its loss)."""
    from bigdl_tpu.dataset import Sample
    labels = rs.randint(1, cfg["classes"] + 1, cfg["batch"])
    return [Sample(rs.randn(*shape).astype(np.float32) + 0.1 * (c % 7),
                   np.asarray([float(c)])) for c in labels]


def phase_train_inception(size, platform, meter, seed):
    from bigdl_tpu.models.inception import Inception_v1
    from bigdl_tpu.utils.random import set_seed

    cfg = size["inception"]
    set_seed(seed)
    model = Inception_v1(class_num=cfg["classes"])
    samples = image_samples(np.random.RandomState(seed), cfg, (3, 224, 224))
    with phase("train_inception_v1", meter, batch=cfg["batch"],
               input=[3, 224, 224], classes=cfg["classes"]) as out:
        losses, _, _ = train(model, samples, cfg)
        require(on_platform(model.params(), platform),
                f"Inception params not on {platform}: "
                f"{leaf_devices(model.params())}")
        out.update(steps=len(losses), losses=[round(v, 4) for v in losses],
                   params_on=leaf_devices(model.params()))
    return model


def phase_train_bilstm(size, platform, meter, seed):
    from bigdl_tpu.dataset import Sample
    from bigdl_tpu.models.textclassifier import TextClassifierBiLSTM
    from bigdl_tpu.utils.random import set_seed

    cfg = size["bilstm"]
    set_seed(seed)
    model = TextClassifierBiLSTM(cfg["classes"], cfg["embed"], cfg["hidden"])
    rs = np.random.RandomState(seed)
    means = rs.randn(cfg["classes"], cfg["embed"]).astype(np.float32)
    samples = []
    for i in range(cfg["batch"]):
        c = i % cfg["classes"]
        doc = rs.randn(cfg["seq"], cfg["embed"]).astype(np.float32) * 0.5
        samples.append(Sample(doc + means[c], np.asarray([c + 1.0])))
    with phase("train_bilstm", meter, batch=cfg["batch"], seq=cfg["seq"],
               embed=cfg["embed"], hidden=cfg["hidden"]) as out:
        losses, first_step, _ = train(model, samples, cfg)
        require(on_platform(model.params(), platform),
                f"Bi-LSTM params not on {platform}")
        kernels = first_step["text"].count("tpu_custom_call")
        # on the chip the default Bi-LSTM path IS the Mosaic kernel pair;
        # the lax.scan path must not stand in for it unnoticed
        require(platform != "tpu" or kernels >= 2,
                "the compiled Bi-LSTM step holds no Mosaic kernel "
                f"(tpu_custom_call x{kernels}): the scan path ran instead")
        out.update(steps=len(losses), losses=[round(v, 4) for v in losses],
                   mosaic_kernels_in_step=kernels)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def phase_serve_inception(model, size, platform, meter, seed):
    """A ServeEngine on the trained model: every answer equals a direct
    forward of the same rows at the bucket shape that served them."""
    import jax
    from bigdl_tpu.nn.module import Context
    from bigdl_tpu.serve import ServeEngine, bucketing

    cfg = size["serve"]
    rows = np.random.RandomState(seed + 1).randn(
        cfg["requests"], 3, 224, 224).astype(np.float32)
    with phase("serve_inception_v1", meter, max_batch=cfg["max_batch"],
               requests=cfg["requests"]) as out:
        with ServeEngine(model, max_batch=cfg["max_batch"],
                         max_wait_ms=500,
                         input_shape=(3, 224, 224)) as engine:
            require(on_platform(engine._weights, platform),
                    f"engine weights not on {platform}")
            t0 = time.perf_counter()
            served = np.stack([f.result(timeout=600)
                               for f in engine.submit_many(rows)])
            answer_s = time.perf_counter() - t0
            stats = engine.stats()
        @jax.jit
        def forward(params, state, x):
            y, _ = model.apply(params, x, state, Context(
                training=False, key=jax.random.PRNGKey(0)))
            return y

        # batches close on size (the wait is long), so the rows were
        # served in chunks of max_batch, each padded to its bucket
        want = []
        for i in range(0, len(rows), cfg["max_batch"]):
            chunk = rows[i:i + cfg["max_batch"]]
            padded, n = bucketing.pad_rows(chunk, bucketing.bucket_for(
                len(chunk), cfg["max_batch"]))
            want.append(np.asarray(
                forward(model.params(), model.state(), padded))[:n])
        want = np.concatenate(want)
        require(served.shape == want.shape and np.isfinite(served).all(),
                f"bad served outputs: shape {served.shape}")
        err = float(np.abs(served - want).max())
        # two separately compiled bf16 programs: agreement, not identity
        require(err <= 2e-2, f"served rows differ from a direct forward by "
                             f"{err} (log-prob units)")
        out.update(answer_s=round(answer_s, 3), max_abs_err=err,
                   completed=stats["completed"], failed=stats["failed"],
                   buckets=stats.get("bucket_hits"))
        require(stats["completed"] == len(rows) and stats["failed"] == 0,
                f"engine stats: {stats}")


def build_lm(cfg, seed, layers=None):
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils.random import set_seed

    set_seed(seed)
    return TransformerLM(vocab_size=cfg["vocab"], d_model=cfg["d_model"],
                         n_heads=cfg["heads"],
                         n_layers=layers or cfg["layers"],
                         hidden=cfg["hidden"], dropout=0.0)


def lm_prompts(cfg, seed, n):
    rs = np.random.RandomState(seed + 2)
    return rs.randint(0, cfg["vocab"], (n, cfg["prompt"])).tolist()


@contextlib.contextmanager
def attention_kernels(on):
    """The two decode kernels' module switches, restored on exit."""
    from bigdl_tpu.models import transformer as tf
    before = tf._PALLAS_PAGED_ATTN, tf._PALLAS_SPEC_VERIFY
    tf._PALLAS_PAGED_ATTN = tf._PALLAS_SPEC_VERIFY = bool(on)
    try:
        yield
    finally:
        tf._PALLAS_PAGED_ATTN, tf._PALLAS_SPEC_VERIFY = before


def decode(lm, prompts, cfg, platform, spec_k=0, **decoder_kwargs):
    """All ``prompts`` through one paged ContinuousDecoder; returns
    (token rows, decoder stats, seconds spent decoding)."""
    from bigdl_tpu.serve.decode import ContinuousDecoder

    n_pos = cfg["prompt"] + cfg["words"]
    n_pos += -n_pos % cfg["page"]
    dec = ContinuousDecoder(lm, max_slots=cfg["slots"], n_pos=n_pos,
                            paged=True, page_size=cfg["page"],
                            prefix_cache=False, spec_k=spec_k,
                            kv_quant="off", **decoder_kwargs)
    try:
        require(on_platform(dec._caches, platform),
                f"KV pool not on {platform}: {leaf_devices(dec._caches)}")
        t0 = time.perf_counter()
        futures = [dec.submit(p, cfg["words"]) for p in prompts]
        dec.run()
        rows = [f.result(timeout=600) for f in futures]
        return rows, dec.stats(), time.perf_counter() - t0
    finally:
        dec.close()


def same_tokens(rows, want, what):
    bad = [i for i, (a, b) in enumerate(zip(rows, want)) if a != b]
    require(len(rows) == len(want) and not bad,
            f"{what}: rows {bad} differ from serial lm_decode, e.g. "
            f"{rows[bad[0]][-8:] if bad else None} vs "
            f"{want[bad[0]][-8:] if bad else None}")


def phase_decode(size, platform, meter, seed):
    """Paged continuous decode vs serial ``lm_decode``: XLA attention,
    then the Mosaic paged-attention kernel (S=1), then the self-
    speculative decoder whose verify window runs the S=k+1 kernel."""
    from bigdl_tpu.models.transformer import lm_decode

    cfg = size["lm"]
    lm = build_lm(cfg, seed)
    prompts = lm_prompts(cfg, seed, cfg["prompts"])
    geometry = {k: cfg[k] for k in ("vocab", "d_model", "heads", "layers",
                                    "hidden", "prompt", "words", "slots")}
    with phase("lm_decode_serial_reference", meter, **geometry) as out:
        want = lm_decode(lm, prompts, cfg["words"], greedy=True)
        out.update(rows=len(want), tokens=len(want) * cfg["words"])
    runs = (("decode_paged_xla", False, 0),
            ("decode_paged_attention_kernel", True, 0),
            ("decode_spec_verify_kernel", True, cfg["spec_k"]))
    for name, kernels, spec_k in runs:
        with phase(name, meter, kernels=kernels, spec_k=spec_k) as out, \
                attention_kernels(kernels):
            rows, stats, seconds = decode(lm, prompts, cfg, platform,
                                          spec_k=spec_k)
            same_tokens(rows, want, name)
            out.update(parity="token-identical", rows=len(rows),
                       tokens=len(rows) * cfg["words"],
                       decode_s=round(seconds, 3), steps=stats.get("steps"))


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_data_parallel(size, devices, meter, seed):
    """Inception-v1 over a ``data`` mesh of ``devices`` (DistriOptimizer,
    picked by the ``distributed=True`` dataset) against the same steps on
    one chip from the same seed."""
    from bigdl_tpu.models.inception import Inception_v1
    from bigdl_tpu.parallel.mesh import data_parallel_mesh
    from bigdl_tpu.utils.random import set_seed

    cfg = size["inception"]
    n = len(devices)
    results = {}
    for name, distributed in (("one_chip", False), ("data_parallel", True)):
        set_seed(seed)
        model = Inception_v1(class_num=cfg["classes"])
        samples = image_samples(np.random.RandomState(seed), cfg,
                                (3, 224, 224))
        with phase("train_inception_v1_" + name, meter,
                   batch=cfg["batch"]) as out:
            mesh = {"mesh": data_parallel_mesh(devices)} if distributed \
                else {}
            losses, first_step, opt = train(model, samples, cfg,
                                            distributed=distributed, **mesh)
            text = first_step.pop("text")
            out.update(losses=[round(v, 4) for v in losses],
                       optimizer=type(opt).__name__, **first_step)
            if distributed:
                require(dict(opt.mesh.shape) == {"data": n},
                        f"mesh is {dict(opt.mesh.shape)}")
                require(len(first_step["params_on"]) == n
                        and len(first_step["batch_on"]) == n,
                        f"step arguments are not on {n} devices: "
                        f"{first_step}")
                require(first_step["batch_shard"][0] == cfg["batch"] // n,
                        "batch is not split evenly over the data axis")
                require("all-reduce" in text,
                        "the data-parallel step holds no all-reduce")
                out.update(mesh=dict(opt.mesh.shape),
                           all_reduces_in_step=text.count("all-reduce("))
        results[name] = losses
    a, b = np.asarray(results["one_chip"]), np.asarray(results["data_parallel"])
    gap = float(np.abs(a - b).max() / np.abs(a).max())
    say(phase="data_parallel_vs_one_chip", max_rel_loss_gap=gap)
    # bf16 compute, different reduction order across 4 shards
    require(gap <= 0.02, f"loss trajectories diverge: {a} vs {b}")


def phase_tensor_parallel_decode(size, devices, platform, meter, seed):
    from bigdl_tpu.models.transformer import lm_decode
    from bigdl_tpu.parallel.mesh import hybrid_mesh

    cfg = size["lm"]
    lm = build_lm(cfg, seed)
    prompts = lm_prompts(cfg, seed, size["tp_prompts"])
    with phase("lm_decode_serial_reference", meter) as out:
        want = lm_decode(lm, prompts, cfg["words"], greedy=True)
        out.update(rows=len(want))
    mesh = hybrid_mesh(dp=1, mp=len(devices), devices=devices)
    for kernels in (False, True):
        with phase("decode_tensor_parallel", meter, kernels=kernels,
                   mesh=dict(mesh.shape)) as out, attention_kernels(kernels):
            rows, stats, seconds = decode(lm, prompts, cfg, platform,
                                          mesh=mesh)
            same_tokens(rows, want, f"tensor-parallel kernels={kernels}")
            out.update(parity="token-identical", rows=len(rows),
                       decode_s=round(seconds, 3))


def phase_replicas(size, devices, platform, meter, seed):
    """One in-process decode replica per chip behind the fleet router."""
    from bigdl_tpu.models.transformer import lm_decode
    from bigdl_tpu.serve.fleet import DecodeFleet

    cfg = size["lm"]
    lm = build_lm(cfg, seed, layers=size["replica_layers"])
    prompts = lm_prompts(cfg, seed, size["replica_requests"])
    want = lm_decode(lm, prompts, cfg["words"], greedy=True)
    n_pos = cfg["prompt"] + cfg["words"]
    n_pos += -n_pos % cfg["page"]
    n = len(devices)
    with phase("decode_replicas", meter, replicas=n,
               layers=size["replica_layers"]) as out:
        fleet = DecodeFleet(lm, n_decode=n, n_prefill=0, affinity=False,
                            max_slots=cfg["slots"], n_pos=n_pos, paged=True,
                            page_size=cfg["page"], kv_quant="off")
        try:
            placed = {r.name: str(r.decoder.device) for r in fleet.replicas}
            holds = {r.name: leaf_devices(r.decoder._caches)
                     for r in fleet.replicas}
            require(len(set(placed.values())) == n,
                    f"replicas share devices: {placed}")
            require(all(holds[k] == [placed[k]] for k in placed),
                    f"a replica's KV pool is not on its device: {holds}")
            require(all(on_platform(r.decoder._caches, platform)
                        for r in fleet.replicas), "replica not on the chip")
            futures = [fleet.submit(p, cfg["words"]) for p in prompts]
            rows = [f.result(timeout=600) for f in futures]
            same_tokens(rows, want, "replicas")
            served = {r.name: r.stats().get("retired") for r in fleet.replicas}
            out.update(parity="token-identical", replica_device=placed,
                       retired_per_replica=served)
        finally:
            fleet.close()


# ---------------------------------------------------------------------------

def say_host_costs():
    """What a dispatch and a host sync cost on this machine (smoke
    timings): a chain of tiny jitted steps, synced once at the end with
    ``block_until_ready``, then synced every step by a device->host copy."""
    import jax
    import jax.numpy as jnp

    tick = jax.jit(lambda v: v + 1)
    v = tick(jnp.zeros((), jnp.float32))
    float(v)
    t0 = time.perf_counter()
    for _ in range(200):
        v = tick(v)
    v.block_until_ready()
    dispatch_us = (time.perf_counter() - t0) / 200 * 1e6
    t0 = time.perf_counter()
    for _ in range(50):
        v = tick(v)
        float(v)
    sync_us = (time.perf_counter() - t0) / 50 * 1e6
    say(phase="host_costs", dispatch_us=round(dispatch_us, 1),
        dispatch_plus_host_sync_us=round(sync_us, 1))


def run(size, chips, platform, seed):
    """Every phase, in order; raises on the first failure.  Returns the
    device triple for the last line."""
    import importlib.metadata

    import jax
    import jaxlib

    from bigdl_tpu import native
    from bigdl_tpu import tensor as bt
    from bigdl_tpu.utils.engine import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    require(device["platform"] == platform,
            f"jax runs on {device['platform']}, not {platform}: no chip, "
            f"no smoke")
    require(len(devices) == chips,
            f"asked for {chips} chip(s), jax sees {len(devices)}")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    say(phase="start", jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu,
        platform_version=devices[0].client.platform_version.split("\n")[0],
        device=device, compile_cache=cache_dir, seed=seed,
        # the C++ host ops are built from hostops.cpp at first use; without
        # g++ the numpy implementations answer instead
        hostops="libhostops.so" if native.is_loaded() else "numpy")
    meter = CompileMeter()

    say_host_costs()

    bt.set_policy(bt.BF16_COMPUTE)      # matmuls/convs in bf16 on the MXU
    if chips == 1:
        inception = phase_train_inception(size, platform, meter, seed)
        phase_train_bilstm(size, platform, meter, seed)
        phase_serve_inception(inception, size, platform, meter, seed)
    else:
        phase_data_parallel(size, devices, meter, seed)
    # decode parity is an exact, token-for-token claim between two
    # differently shaped programs: run both sides in full f32
    bt.set_policy(bt.FP32)
    jax.config.update("jax_default_matmul_precision", "highest")
    if chips == 1:
        phase_decode(size, platform, meter, seed)
    else:
        phase_tensor_parallel_decode(size, devices, platform, meter, seed)
        phase_replicas(size, devices, platform, meter, seed)
    seconds, hits, misses = meter.snapshot()
    say(phase="total", compile_s=round(seconds, 1), cache_hits=hits,
        cache_misses=misses)
    return device


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=21)
    args = parser.parse_args(argv)
    try:
        device = run(FULL, args.chips, "tpu", args.seed)
    except BaseException as e:      # report, then fail: never exit 0
        traceback.print_exc()
        print(json.dumps({"ok": False,
                          "error": f"{type(e).__name__}: {e}"[:400]}),
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark: the five BASELINE.md configs plus the transformer-encoder
flagship, like the reference's DistriOptimizerPerf CLI
(models/utils/DistriOptimizerPerf.scala:41-138: synthetic data,
multi-model `-m` flag, default batch 128).

Prints ONE JSON line (driver contract): the headline metric is the
Inception-v1 config; ``detail.configs`` carries all six entries
(LeNet-5/MNIST, VGG-16/CIFAR-10, Inception-v1/ImageNet, Bi-LSTM text
classifier, ResNet-50/ImageNet, Transformer encoder), each with step ms,
records/s, MFU and the same-run measured matmul roofline.

The reference publishes no numbers (BASELINE.md), so ``vs_baseline`` is
reported against the BASELINE.json north-star bar of 0.4 MFU:
vs_baseline = achieved_MFU / 0.4 (>1.0 beats the target).  MFU uses XLA's
own per-step FLOP count from compiled cost analysis and the chip's
datasheet peak for the dtype in use.

Usage: python bench.py [substring]   # e.g. `python bench.py lenet`
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


_HERE = os.path.dirname(os.path.abspath(__file__))


def _raw_step(model, criterion):
    """The un-jitted per-step train function shared by make_step (one
    dispatch per step) and make_chunk_step (scanned device-side loop)."""
    import jax
    from bigdl_tpu.nn.module import Context
    from bigdl_tpu.optim.optim_method import SGD

    method = SGD()
    hyper = {"lr": 0.01, "momentum": 0.9, "dampening": 0.0,
             "weight_decay": 0.0001, "nesterov": False}

    def train_step(params, net_state, opt_state, x, y, key):
        def loss_fn(p):
            out, ns = model.apply(p, x, net_state,
                                  Context(training=True, key=key))
            return criterion.apply_loss(out, y), ns
        (loss, ns), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        new_params, new_opt = method.update(grads, opt_state, params, hyper)
        return new_params, ns, new_opt, loss

    params, net_state = model.params(), model.state()
    opt_state = method.init_state(params)
    return train_step, params, net_state, opt_state


def make_step(model, criterion):
    import jax
    train_step, params, net_state, opt_state = _raw_step(model, criterion)
    step = jax.jit(train_step, donate_argnums=(0, 1, 2))
    return step, params, net_state, opt_state


def make_chunk_step(model, criterion, n_steps):
    """A device-side training loop: ONE dispatch runs ``n_steps`` train
    steps via lax.scan, each consuming a DISTINCT minibatch from a
    stacked (n_steps, B, ...) device array — the TPU-native host-loop
    pattern (the optimizer exposes it as set_iterations_per_dispatch).
    Small models are dispatch-bound per call; amortizing the fixed
    per-dispatch cost over n_steps recovers the device-limited rate."""
    import jax
    from jax import lax

    train_step, params, net_state, opt_state = _raw_step(model, criterion)

    def one(carry, xyk):
        x, y, key = xyk
        p, ns, o, loss = train_step(*carry, x, y, key)
        return (p, ns, o), loss

    def chunk(params, net_state, opt_state, xs, ys, key):
        keys = jax.random.split(key, n_steps)
        (params, net_state, opt_state), losses = lax.scan(
            one, (params, net_state, opt_state), (xs, ys, keys))
        return params, net_state, opt_state, losses[-1]

    step = jax.jit(chunk, donate_argnums=(0, 1, 2))
    return step, params, net_state, opt_state


def bench_config(build, records_per_batch, warmup=3, iters=10, windows=3,
                 flops_override=None, steps_per_dispatch=8):
    """Returns (records/s, step_ms, mfu, flops_per_step, loss, band,
    fetch_ms_per_step).

    Trains with the device-side loop (``steps_per_dispatch`` scanned
    steps per dispatch over DISTINCT stacked minibatches) — what a real
    prefetching training loop on this hardware does; the per-dispatch
    cost otherwise dominates the small configs.  ``mfu`` is None on the
    CPU (no datasheet peak); an unknown accelerator kind raises.
    ``fetch_ms_per_step``
    is the host-side batch staging + H2D wall amortized per scanned step
    — the work the training loops' prefetch pipeline
    (``dataset/prefetch.py``) overlaps with compute."""
    import jax
    import jax.numpy as jnp

    model, criterion, x, y = build()
    n = steps_per_dispatch
    # distinct batch per scanned step: vary the shared synthetic batch
    # with a cheap per-step perturbation (content does not affect timing;
    # training semantics stay honest — every step sees different data)
    rs = np.random.RandomState(7)
    xh = np.asarray(x)
    xs = jnp.stack([jnp.asarray(xh * (1.0 + 0.01 * rs.randn()), x.dtype)
                    for _ in range(n)])
    ys = jnp.stack([y] * n)
    step, params, net_state, opt_state = make_chunk_step(model, criterion, n)
    from bigdl_tpu.utils.random import RNG
    key = RNG.next_key()  # honors the bench's rbg device-PRNG selection
    from bigdl_tpu.obs import ledger as cost_ledger
    if flops_override is not None:
        flops = float(flops_override)
    else:
        # XLA cost analysis counts a lax.scan body ONCE, so the chunk's
        # number is already the per-step count.  The probe resolves
        # through the shared CostLedger — ONE cost code path with the
        # live train_mfu gauge and tools/profile_step.py.  (None = the
        # ledger is switched off, BIGDL_LEDGER=0: no flops, no MFU.)
        entry = cost_ledger.get().capture_compiled(
            ("bench_chunk", records_per_batch, n),
            step.lower(params, net_state, opt_state, xs, ys,
                       key).compile())
        flops = entry.flops if entry is not None else float("nan")
    for _ in range(warmup):
        params, net_state, opt_state, loss = step(
            params, net_state, opt_state, xs, ys, key)
    float(loss)  # device->host copy of the last loss: waits for the chain
    # fetch/train split evidence: steady-state HOST staging cost per step
    # (the work dataset/prefetch.py overlaps) — measured POST-warmup and
    # host-side only, so no first-call tracing rides the number
    t_fetch = time.perf_counter()
    np.stack([xh * (1.0 + 0.01 * rs.randn()) for _ in range(n)])
    fetch_ms = (time.perf_counter() - t_fetch) * 1e3 / n

    # best-of-N timing windows (each window syncs once at the end); the
    # band below keeps the worst window visible next to the best
    dts = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            params, net_state, opt_state, loss = step(
                params, net_state, opt_state, xs, ys, key)
        last = float(loss)
        dts.append((time.perf_counter() - t0) / (iters * n))
    dt = min(dts)
    # the SAME denominator the live train_mfu gauge divides by
    peak = cost_ledger.device_peak_flops(jax.devices()[0])
    mfu = (flops / dt) / peak if peak and np.isfinite(flops) else None
    # window band: [best, worst] step ms across the timing windows — the
    # artifact's own evidence of run-to-run spread (VERDICT r4 weak 4)
    band = (round(min(dts) * 1e3, 3), round(max(dts) * 1e3, 3))
    return (records_per_batch / dt, dt * 1e3, mfu, flops, last, band,
            fetch_ms)


def measured_roofline():
    """Achievable bf16 matmul TF/s on THIS chip right now (8192^3
    chained) — contextualizes MFU when the runtime can't reach the
    datasheet peak.  A DEVICE-CLOCK measurement
    (tools/profile_step.measure_matmul_roofline: kernel durations from a
    jax.profiler trace), so it shares a clock domain with the per-op
    profiles.  There is no wall-clock stand-in: a probe that cannot run
    fails the config that asked for it."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bigdl_profile_step", os.path.join(_HERE, "tools",
                                           "profile_step.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.measure_matmul_roofline()


def configs():
    import jax.numpy as jnp
    import bigdl_tpu.nn as nn

    rs = np.random.RandomState(0)

    def imgs(batch, c, h, w, nclass):
        x = jnp.asarray(rs.randn(batch, c, h, w), jnp.float32)
        y = jnp.asarray(rs.randint(1, nclass + 1, (batch,)))
        return x, y

    def lenet():
        from bigdl_tpu.models.lenet import LeNet5
        # bs256, NOT 512: XLA's TPU conv emitter compile time explodes
        # superlinearly in batch for LeNet's tiny channel counts
        # (measured: 15s @128, 56s @256, >280s @512 — the round-2 bench
        # timeout was exactly this).  256 keeps the chip saturated and
        # compiles inside the per-config budget.
        x, y = imgs(256, 1, 28, 28, 10)
        return LeNet5(class_num=10), nn.ClassNLLCriterion(), x, y

    def vgg16_cifar():
        from bigdl_tpu.models.vgg import VggForCifar10
        x, y = imgs(128, 3, 32, 32, 10)
        return VggForCifar10(class_num=10), nn.ClassNLLCriterion(), x, y

    def inception():
        from bigdl_tpu.models.inception import Inception_v1
        x, y = imgs(128, 3, 224, 224, 1000)
        return Inception_v1(class_num=1000), nn.ClassNLLCriterion(), x, y

    def bilstm():
        from bigdl_tpu.models.textclassifier import TextClassifierBiLSTM
        batch, t, e = 128, 500, 200
        x = jnp.asarray(rs.randn(batch, t, e), jnp.float32)
        y = jnp.asarray(rs.randint(1, 21, (batch,)))
        return (TextClassifierBiLSTM(20, e, hidden_size=128),
                nn.ClassNLLCriterion(), x, y)

    def bilstm_flops():
        # XLA cost analysis counts a scan body ONCE, so recurrent models
        # need the analytic count: per direction per step one
        # (B, D+H) x (D+H, 4H) gemm; x2 directions, xT steps, x3 for
        # fwd + data-grad + weight-grad.
        batch, t, e, h = 128, 500, 200, 128
        return 3 * 2 * 2 * batch * t * (e + h) * 4 * h

    def resnet50():
        from bigdl_tpu.models.resnet import ResNet
        x, y = imgs(64, 3, 224, 224, 1000)
        return ResNet(depth=50, class_num=1000), nn.ClassNLLCriterion(), x, y

    def transformer():
        # the attention-family flagship (beyond the reference's model zoo):
        # GPT-2-medium-class encoder geometry chosen for the MXU — d_model
        # 1024 contractions and d_head 256 (this XLA's batched gemms run
        # 4-7x slower at K<=128, PERF_NOTES round 4).  Measured 0.55
        # datasheet MFU on v5e (matmuls at 92-94% of roofline,
        # PROFILE_transformer.md) — past the >=0.4 north-star bar,
        # evidence the compute path is emitter-bound on convs, not
        # framework-bound
        from bigdl_tpu.models.transformer import TransformerClassifier
        batch, t, d = 16, 512, 1024
        x = jnp.asarray(rs.randn(batch, t, d), jnp.float32)
        y = jnp.asarray(rs.randint(1, 21, (batch,)))
        return (TransformerClassifier(class_num=20, d_model=d, n_heads=4,
                                      n_layers=6, hidden=4096),
                nn.ClassNLLCriterion(), x, y)

    # (name, build, records_per_batch, unit, analytic_flops_or_None,
    #  steps_per_dispatch) — small/latency-bound configs amortize more
    # steps per dispatch (measured: LeNet n=32 2.9x over n=8, VGG +18%);
    # the big configs stay at 8 to bound the stacked-batch HBM footprint
    return [
        ("LeNet-5 bs256 (MNIST, local)", lenet, 256, "images/sec", None, 32),
        ("VGG-16 bs128 (CIFAR-10)", vgg16_cifar, 128, "images/sec", None, 32),
        ("Inception-v1 bs128 (ImageNet sync-SGD)", inception, 128,
         "images/sec", None, 8),
        ("Bi-LSTM bs128 T500 (text classifier)", bilstm, 128 * 500,
         "tokens/sec", bilstm_flops(), 8),
        ("ResNet-50 bs64 (ImageNet streaming cfg)", resnet50, 64,
         "images/sec", None, 8),
        ("Transformer-enc bs16 T512 d1024 (attention family)", transformer,
         16 * 512, "tokens/sec", None, 8),
    ]


def bench_eval(build, records_per_batch, warmup=2, iters=10, windows=3):
    """Forward-only evaluation throughput + top1/top5 on the synthetic
    batch — the reference logs validation records/s
    (LocalOptimizer.scala:231-233); this closes the measurement-apparatus
    contract for the eval path."""
    import jax
    from bigdl_tpu.nn.module import Context
    from bigdl_tpu.optim.validation import Top1Accuracy, Top5Accuracy

    model, criterion, x, y = build()
    params, net_state = model.params(), model.state()

    @jax.jit
    def fwd(p, s, xb):
        out, _ = model.apply(p, xb, s,
                             Context(training=False,
                                     key=jax.random.PRNGKey(0)))
        return out
    for _ in range(warmup):
        out = fwd(params, net_state, x)
    np.asarray(out[0, 0])  # device->host copy = hard sync
    dts = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fwd(params, net_state, x)
        np.asarray(out[0, 0])
        dts.append((time.perf_counter() - t0) / iters)
    dt = min(dts)
    top1 = Top1Accuracy()(out, y)
    top5 = Top5Accuracy()(out, y)
    return {"records_per_sec": round(records_per_batch / dt, 2),
            "step_time_ms": round(dt * 1e3, 3),
            "top1": round(top1.result()[0], 4),
            "top5": round(top5.result()[0], 4)}


def run_one(only: str):
    """Measure the configs matching ``only`` in THIS process and print one
    JSON line per config (subprocess mode).  Anything that raises ends
    the process non-zero: the parent reports the config as failed."""
    import jax

    from bigdl_tpu import tensor as bt
    from bigdl_tpu.utils.engine import enable_compile_cache
    from bigdl_tpu.utils.random import set_device_prng, set_seed

    enable_compile_cache()
    set_seed(1)
    bt.set_policy(bt.BF16_COMPUTE)  # matmuls/convs in bf16 on the MXU
    # hardware RngBitGenerator for dropout masks: threefry mask math was
    # 15.7% of the VGG-CIFAR step's device time (round-5 A/B; same win
    # class as the reference's MKL-VSL RNG over Torch's MT)
    set_device_prng("rbg")
    device_kind = jax.devices()[0].device_kind

    if only == "--roofline":
        print(json.dumps({"roofline_tflops": round(measured_roofline(), 1),
                          "device": device_kind}))
        return
    for name, build, recs, unit, aflops, n_disp in configs():
        if only.lower() not in name.lower():
            continue
        rps, ms, mfu, flops, loss, band, fetch_ms = bench_config(
            build, recs, flops_override=aflops, steps_per_dispatch=n_disp)
        from bigdl_tpu.dataset import prefetch as _pf
        entry = {
            "config": name, "unit": unit, "value": round(rps, 2),
            "step_time_ms": round(ms, 3),
            "step_time_ms_band": list(band),
            # fetch/train split: host batch-staging wall per step (the
            # train side is step_time_ms above) — the work the training
            # loops' prefetch pipeline hides (depth = BIGDL_PREFETCH
            # double-buffer)
            "fetch_ms_per_step": round(fetch_ms, 3),
            "prefetch_depth": _pf.depth() if _pf.enabled() else 0,
            "mfu": None if mfu is None else round(mfu, 4),
            "step_tflops": round(flops / (ms / 1e3) / 1e12, 1)
            if np.isfinite(flops) else None,
            "flops_per_step": flops, "loss": loss,
            "device": device_kind,
        }
        # the entry goes out as soon as it is measured: the parent keeps
        # whatever a child printed before it failed
        print(json.dumps(entry), flush=True)
        # mirror the measurement into the obs event stream
        # (docs/observability.md): with BIGDL_OBS_DIR set, a bench run
        # leaves the same machine-readable trail as training — a no-op
        # in-memory ring otherwise
        from bigdl_tpu.obs import events as obs_events
        obs_events.emit("phase", name=f"bench/{name}",
                        seconds=ms / 1e3, step=0,
                        records_per_sec=round(rps, 2),
                        mfu=entry["mfu"], device=device_kind)
        if "Inception" in name:
            # the headline config also carries the eval apparatus and,
            # in this same warm process, the matmul roofline probe
            ev = bench_eval(build, recs)
            ev["config"] = name.replace("sync-SGD", "eval forward")
            ev["unit"] = "images/sec"
            print(json.dumps({"eval": ev}), flush=True)
            print(json.dumps({"roofline_tflops":
                              round(measured_roofline(), 1),
                              "device": device_kind}), flush=True)


_BENCH_DEADLINE = time.monotonic() + float(
    os.environ.get("BIGDL_BENCH_DEADLINE_S", 18 * 60))


def _subprocess_json(arg, timeout_s):
    """Run ``python bench.py <arg>`` ONCE with a hard timeout and return
    ``(entries, error)``: the JSON lines the child printed, and None or
    the reason the config counts as failed (non-zero exit, timeout, no
    output, global deadline ``BIGDL_BENCH_DEADLINE_S`` reached).  No
    retry: a config that fails once has failed.  Lines printed before a
    failure are still returned — they were measured.

    The parent process never imports jax and runs one child at a time:
    a chip belongs to one process, and that is what lets each child
    have it."""
    import subprocess
    budget = _BENCH_DEADLINE - time.monotonic()
    if budget <= 30:
        return [], "bench deadline reached before it could start"
    try:
        out = subprocess.run(
            [sys.executable, "-u", os.path.abspath(__file__), arg],
            capture_output=True, text=True,
            timeout=min(timeout_s, budget))
        stdout, err = out.stdout, None
        if out.returncode != 0:
            err = "exit code %d: %s" % (out.returncode,
                                        out.stderr.strip()[-500:])
    except subprocess.TimeoutExpired as e:
        stdout = e.stdout or b""
        if isinstance(stdout, bytes):
            stdout = stdout.decode(errors="replace")
        err = "timed out after %.0fs" % min(timeout_s, budget)
    entries = [json.loads(l) for l in stdout.splitlines()
               if l.startswith("{")]
    if err is None and not entries:
        err = "printed no result"
    return entries, err


def _summary_line(entries, primary, roof, eval_entry=None):
    """The driver-contract JSON line for the headline config
    (``primary``, a measured entry) and whatever else has been measured
    so far.  Printed after EVERY config once the headline exists (the
    driver takes the LAST line), so a mid-run kill still reports the
    completed configs.  A run whose headline failed prints no summary at
    all — there is no result to report.

    FENCED (VERDICT r5 weak 1): the driver captures only the last
    ~2000 bytes of stdout, and round 5's summary — which inlined every
    full config entry plus the eval block — outgrew that window, so
    BENCH_r05.json shipped ``parsed: null``.  The summary carries only
    the headline keys plus a COMPACT per-config digest
    (config/value/mfu) and a trimmed eval; the full per-config detail
    (bands, flops, losses) lives in the per-config lines main() re-emits
    just above.  tests/test_bench_contract.py asserts a fully-populated
    summary stays under 2000 bytes."""
    mfu = primary.get("mfu")
    detail = {
        "step_time_ms": primary["step_time_ms"],
        "mfu": mfu,
        # None = not measured (the probe line never arrived)
        "measured_matmul_roofline_tflops": roof,
        "device": primary.get("device"),
        # digest only — full entries are their own stdout lines
        "configs": [{"config": e.get("config"), "value": e.get("value"),
                     "mfu": e.get("mfu")} for e in entries],
    }
    if eval_entry is not None:
        detail["eval"] = {k: eval_entry[k] for k in
                          ("records_per_sec", "step_time_ms", "top1",
                           "top5") if k in eval_entry}
    return json.dumps({
        "metric": "images/sec/chip (Inception-v1 bs128 sync-SGD train)",
        "value": primary["value"],
        "unit": "images/sec",
        # achieved MFU over the BASELINE.json 0.4 bar; None without MFU
        "vs_baseline": None if mfu is None else round(mfu / 0.4, 4),
        "detail": detail,
    })


def main():
    if len(sys.argv) > 1:
        run_one(sys.argv[1])
        return

    entries = []
    primary = None
    eval_entry = None
    roof = None
    failed = {}
    # headline (Inception) FIRST so a driver kill at any point still
    # leaves the number that matters on stdout
    for key in ("inception", "resnet", "bi-lstm", "transformer", "lenet",
                "vgg-16"):
        t0 = time.monotonic()
        print("benching: %s" % key, file=sys.stderr, flush=True)
        got, err = _subprocess_json(key, timeout_s=300)
        print("%s %s in %.0fs" % (key, "FAILED" if err else "done",
                                  time.monotonic() - t0),
              file=sys.stderr, flush=True)
        if err:
            failed[key] = err
        for entry in got:
            if "roofline_tflops" in entry:
                roof = entry["roofline_tflops"]
                continue
            if "eval" in entry:
                eval_entry = entry["eval"]
                print(json.dumps(entry), flush=True)   # full eval detail
                continue
            entries.append(entry)
            # re-emit the FULL per-config entry as its own stdout line:
            # the fenced summary below carries only a digest of it
            print(json.dumps(entry), flush=True)
            if "Inception" in entry["config"]:
                primary = entry
        if primary is not None:
            print(_summary_line(entries, primary, roof, eval_entry),
                  flush=True)
    if failed:
        # a failed config is not a result: name it and exit non-zero
        for key, err in failed.items():
            print("bench config %r failed: %s" % (key, err),
                  file=sys.stderr, flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()

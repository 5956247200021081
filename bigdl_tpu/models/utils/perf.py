"""Synthetic-data throughput harnesses — the LocalOptimizerPerf /
DistriOptimizerPerf CLIs (ref models/utils/DistriOptimizerPerf.scala:41-138,
LocalOptimizerPerf.scala).

Usage:
  python -m bigdl_tpu.models.utils.perf --model inception_v1 -b 128 -i 20
  python -m bigdl_tpu.models.utils.perf --model vgg16 -b 64 --distributed

Flags mirror the reference's scopt options: --batchSize/-b, --iteration/-i,
--model/-m (alexnet | alexnetowt | googlenet_v1 | inception_v1 |
googlenet_v2 | inception_v2 | vgg16 | vgg19 | lenet5), --dataType
(float | bf16 compute).
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


MODELS = {}


def _register():
    from bigdl_tpu.models.alexnet import AlexNet, AlexNet_OWT
    from bigdl_tpu.models.inception import Inception_v1, Inception_v2
    from bigdl_tpu.models.vgg import Vgg_16, Vgg_19
    from bigdl_tpu.models.lenet import LeNet5
    MODELS.update({
        "alexnet": (lambda: AlexNet(1000), (3, 227, 227), 1000),
        "alexnetowt": (lambda: AlexNet_OWT(1000), (3, 224, 224), 1000),
        "googlenet_v1": (lambda: Inception_v1(1000), (3, 224, 224), 1000),
        "inception_v1": (lambda: Inception_v1(1000), (3, 224, 224), 1000),
        "googlenet_v2": (lambda: Inception_v2(1000), (3, 224, 224), 1000),
        "inception_v2": (lambda: Inception_v2(1000), (3, 224, 224), 1000),
        "vgg16": (lambda: Vgg_16(1000), (3, 224, 224), 1000),
        "vgg19": (lambda: Vgg_19(1000), (3, 224, 224), 1000),
        "lenet5": (lambda: LeNet5(10), (1, 28, 28), 10),
    })


def run_perf(model_name: str, batch_size: int, iterations: int,
             warmup: int = 3, distributed: bool = False,
             data_type: str = "bf16", iters_per_dispatch: int = 1) -> dict:
    """``iters_per_dispatch > 1`` uses the device-side training loop
    (n scanned steps per dispatch over distinct stacked minibatches, the
    set_iterations_per_dispatch feature) — where a step's device work is
    shorter than a host dispatch this reports the device-limited rate."""
    import jax
    import jax.numpy as jnp
    import bigdl_tpu.nn as nn
    from bigdl_tpu import tensor as bt
    from bigdl_tpu.nn.module import Context
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.utils.random import set_seed

    _register()
    set_seed(1)
    if data_type == "bf16":
        bt.set_policy(bt.BF16_COMPUTE)
    else:
        bt.set_policy(bt.FP32)
    build, shape, n_classes = MODELS[model_name]
    model = build()
    criterion = nn.ClassNLLCriterion()
    method = SGD()
    # copy before the donating jit step — donate_argnums would otherwise
    # leave the live module holding deleted buffers (same guard as
    # LocalOptimizer/DistriOptimizer)
    params = jax.tree_util.tree_map(jnp.copy, model.params())
    net_state = jax.tree_util.tree_map(jnp.copy, model.state())
    opt_state = method.init_state(params)
    hyper = {"lr": 0.01, "momentum": 0.9, "dampening": 0.0,
             "weight_decay": 0.0, "nesterov": False}

    def train_step(params, net_state, opt_state, x, y, key):
        def loss_fn(p):
            out, ns = model.apply(p, x, net_state, Context(training=True, key=key))
            return criterion.apply_loss(out, y), ns

        (loss, ns), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        new_params, new_opt = method.update(grads, opt_state, params, hyper)
        return new_params, ns, new_opt, loss

    rs = np.random.RandomState(0)
    n_disp = max(1, int(iters_per_dispatch))
    if n_disp > 1:
        from jax import lax
        per_step = train_step

        def train_step(params, net_state, opt_state, xs, ys, key):
            keys = jax.random.split(key, n_disp)

            def body(carry, xyk):
                p, ns, o = carry
                bx, by, k = xyk
                p, ns, o, loss = per_step(p, ns, o, bx, by, k)
                return (p, ns, o), loss

            (params, net_state, opt_state), losses = lax.scan(
                body, (params, net_state, opt_state), (xs, ys, keys))
            return params, net_state, opt_state, losses[-1]

        x = jnp.asarray(rs.randn(n_disp, batch_size, *shape), jnp.float32)
        y = jnp.asarray(rs.randint(1, n_classes + 1, (n_disp, batch_size)))
    else:
        x = jnp.asarray(rs.randn(batch_size, *shape), jnp.float32)
        y = jnp.asarray(rs.randint(1, n_classes + 1, (batch_size,)))
    key = jax.random.PRNGKey(0)

    if distributed:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from bigdl_tpu.parallel.mesh import data_parallel_mesh
        mesh = data_parallel_mesh()
        rep = NamedSharding(mesh, P())
        data_s = NamedSharding(
            mesh, P(None, "data") if n_disp > 1 else P("data"))
        reps = lambda tree: jax.tree_util.tree_map(lambda _: rep, tree)
        step = jax.jit(train_step,
                       in_shardings=(reps(params), reps(net_state),
                                     reps(opt_state), data_s, data_s, rep),
                       out_shardings=(reps(params), reps(net_state),
                                      reps(opt_state), rep),
                       donate_argnums=(0, 1, 2))
        x = jax.device_put(x, data_s)
        y = jax.device_put(y, data_s)
    else:
        step = jax.jit(train_step, donate_argnums=(0, 1, 2))

    compile_t0 = time.perf_counter()
    out = step(params, net_state, opt_state, x, y, key)
    float(out[3])  # device->host copy: waits for the step
    compile_time = time.perf_counter() - compile_t0
    params, net_state, opt_state, _ = out

    loss = out[3]
    for _ in range(warmup - 1):
        params, net_state, opt_state, loss = step(params, net_state, opt_state, x, y, key)
    float(loss)

    t0 = time.perf_counter()
    for _ in range(iterations):
        params, net_state, opt_state, loss = step(params, net_state, opt_state, x, y, key)
    last_loss = float(loss)  # syncs the sequential step chain
    dt = (time.perf_counter() - t0) / (iterations * n_disp)

    return {
        "model": model_name,
        "batch_size": batch_size,
        "iters_per_dispatch": n_disp,
        "distributed": distributed,
        "devices": jax.device_count() if distributed else 1,
        "step_time_ms": round(dt * 1e3, 3),
        "throughput_records_per_sec": round(batch_size / dt, 2),
        "compile_time_s": round(compile_time, 2),
        "loss": last_loss,
    }


def main(argv=None, force_distributed=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", "-m", default="inception_v1")
    p.add_argument("--batchSize", "-b", type=int, default=128)
    p.add_argument("--iteration", "-i", type=int, default=10)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--dataType", choices=["float", "bf16"], default="bf16")
    p.add_argument("--iterationsPerDispatch", type=int, default=1,
                   help="device-side loop: n scanned steps per dispatch")
    p.add_argument("--distributed", action="store_true")
    args = p.parse_args(argv)
    if force_distributed is not None and args.distributed != force_distributed:
        p.error("--distributed conflicts with this entry point; use "
                "`python -m bigdl_tpu.models.utils.perf --distributed` instead")
    distributed = (force_distributed if force_distributed is not None
                   else args.distributed)
    from bigdl_tpu.utils.engine import enable_compile_cache
    enable_compile_cache()
    result = run_perf(args.model, args.batchSize, args.iteration,
                      args.warmup, distributed, args.dataType,
                      iters_per_dispatch=args.iterationsPerDispatch)
    print(json.dumps(result))


if __name__ == "__main__":
    main()

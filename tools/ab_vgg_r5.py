"""Round-5 VGG-CIFAR campaign A/B on the bench's scanned device-side
loop (8 steps/dispatch): baseline vs rbg dropout keys vs batch size.

Within one process, interleaved windows, per-variant min — the
wall-clock comparison that run-to-run host noise allows (PERF_NOTES).

Usage: python tools/ab_vgg_r5.py
"""
import os as _os, sys as _sys
_REPO = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
_sys.path.insert(0, _REPO)
import time

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    import bench
    from bigdl_tpu import tensor as bt
    from bigdl_tpu import nn
    from bigdl_tpu.utils.random import set_seed

    from bigdl_tpu.utils.engine import enable_compile_cache
    enable_compile_cache()
    bt.set_policy(bt.BF16_COMPUTE)
    N = 8

    def build(batch):
        from bigdl_tpu.models.vgg import VggForCifar10
        set_seed(1)
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(batch, 3, 32, 32), jnp.float32)
        y = jnp.asarray(rs.randint(1, 11, (batch,)))
        return VggForCifar10(class_num=10), nn.ClassNLLCriterion(), x, y

    variants = []
    for batch in (128, 256):
        for impl in ("threefry2x32", "rbg"):
            jax.config.update("jax_default_prng_impl", impl)
            model, criterion, x, y = build(batch)
            rs = np.random.RandomState(7)
            xs = jnp.stack([jnp.asarray(np.asarray(x) * (1 + 0.01 * rs.randn()),
                                        x.dtype) for _ in range(N)])
            ys = jnp.stack([y] * N)
            step, params, net_state, opt_state = bench.make_chunk_step(
                model, criterion, N)
            key = jax.random.PRNGKey(0)
            name = f"bs{batch} {impl}"
            t0 = time.perf_counter()
            for _ in range(3):
                params, net_state, opt_state, loss = step(
                    params, net_state, opt_state, xs, ys, key)
            float(loss)
            print(f"compile+3 {name}: {time.perf_counter()-t0:.1f}s",
                  flush=True)
            variants.append([name, step,
                             [params, net_state, opt_state, xs, ys, key],
                             batch, []])
    jax.config.update("jax_default_prng_impl", "threefry2x32")

    for _ in range(5):
        for v in variants:
            name, step, st, batch, times = v
            t0 = time.perf_counter()
            for _ in range(4):   # 4 dispatches x N steps
                st[0], st[1], st[2], loss = step(st[0], st[1], st[2],
                                                 st[3], st[4], st[5])
            float(loss)
            times.append((time.perf_counter() - t0) / (4 * N) * 1e3)
    for name, step, st, batch, times in variants:
        best = min(times)
        print(f"{name}: min {best:.3f} ms/step  {batch/best*1e3:,.0f} img/s"
              f"  (all: {['%.3f' % m for m in times]})", flush=True)


if __name__ == "__main__":
    main()

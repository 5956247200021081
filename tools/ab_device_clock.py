"""Device-clock A/B of bench chunk-step variants: total device-busy
us/step per variant from jax.profiler traces (immune to host wall-clock
noise; the comparison used for every round-4/5 perf decision).

Usage: python tools/ab_device_clock.py vgg_cifar 128 [variant ...]
Variants:
  base          defaults
  rbg           hardware RngBitGenerator dropout keys
  pallas_pool   round-6 Mosaic maxpool kernel pair (nn/pooling.py
                _PALLAS_POOL — argmax fwd + gather bwd)
  pallas_lrn    round-6 fused LRN kernel pair (SpatialCrossMapLRN._PALLAS
                — stored-z residual backward)
  pallas_winops pallas_pool + pallas_lrn together (the Inception case)
  blockt4/blockt8
                multi-timestep recurrence blocking (recurrent._BLOCK_T)
  paged_attn    round-7 Mosaic paged-attention decode kernel
                (models/transformer._PALLAS_PAGED_ATTN — in-kernel
                page walk + online softmax + fused int8 dequant)
  spec_verify   round-7 fused speculative (k+1)-window verify kernel
                (transformer._PALLAS_SPEC_VERIFY)
  paged_decode  paged_attn + spec_verify together
The round-6 adoption A/Bs (run when a chip is attached):
  python tools/ab_device_clock.py inception 128 base pallas_pool \
      pallas_lrn pallas_winops
  python tools/ab_device_clock.py bilstm 128 base blockt4 blockt8
The round-7 decode-kernel A/Bs live on the DECODE harness — this
chunk-step instrument never runs the paged decode path, so the
device-clock comparison is the sweep's wall clock and
decode_model_flops_util gauge with the kernel column flipped:
  python tools/bench_serve.py --decode-sweep --kv-quant int8 --check
  python tools/bench_serve.py --decode-sweep --kv-quant int8 --check \
      --attn-kernel paged
  python tools/bench_serve.py --decode-sweep --kv-quant int8 --check \
      --attn-kernel paged+spec
(the `paged_attn`/`spec_verify`/`paged_decode` variants above flip the
same flags for any harness that drives serve/decode.py through this
module)

The ISSUE-4 host-pipeline change (prefetch-to-device + cadenced sync) is
invisible to this device-clock instrument by construction — its staged
on-chip A/B is the WALL-clock loop comparison:
  python tools/ab_host_pipeline.py lenet 256 40
  python tools/ab_host_pipeline.py inception 128 20
"""
import os as _os, sys as _sys
_REPO = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
_sys.path.insert(0, _REPO); _sys.path.insert(0, _os.path.join(_REPO, "tools"))
import shutil
import time

import numpy as np


def build_chunk(model_name, batch, impl, n=8):
    import jax
    import jax.numpy as jnp
    import bench
    from bigdl_tpu import nn
    from bigdl_tpu.utils.random import set_seed

    jax.config.update("jax_default_prng_impl", impl)
    set_seed(1)
    rs = np.random.RandomState(0)
    if model_name == "vgg_cifar":
        from bigdl_tpu.models.vgg import VggForCifar10
        model = VggForCifar10(class_num=10)
        xshape, nclass = (batch, 3, 32, 32), 10
    elif model_name == "inception":
        from bigdl_tpu.models.inception import Inception_v1
        model = Inception_v1(class_num=1000)
        xshape, nclass = (batch, 3, 224, 224), 1000
    elif model_name == "resnet50":
        from bigdl_tpu.models.resnet import ResNet
        model = ResNet(depth=50, class_num=1000)
        xshape, nclass = (batch, 3, 224, 224), 1000
    elif model_name == "lenet":
        from bigdl_tpu.models.lenet import LeNet5
        model = LeNet5(class_num=10)
        xshape, nclass = (batch, 1, 28, 28), 10
    elif model_name == "bilstm":
        from bigdl_tpu.models.textclassifier import TextClassifierBiLSTM
        model = TextClassifierBiLSTM(20, 200, hidden_size=128)
        xshape, nclass = (batch, 500, 200), 20
    elif model_name == "transformer":
        from bigdl_tpu.models.transformer import TransformerClassifier
        model = TransformerClassifier(class_num=20, d_model=1024,
                                      n_heads=4, n_layers=6, hidden=4096)
        xshape, nclass = (batch, 512, 1024), 20
    else:
        raise SystemExit("unknown model " + model_name)
    x = jnp.asarray(rs.randn(*xshape), jnp.float32)
    y = jnp.asarray(rs.randint(1, nclass + 1, (batch,)))
    xs = jnp.stack([x * (1 + 0.01 * rs.randn()) for _ in range(n)])
    ys = jnp.stack([y] * n)
    criterion = nn.ClassNLLCriterion()
    step, params, net_state, opt_state = bench.make_chunk_step(
        model, criterion, n)
    key = jax.random.PRNGKey(0)
    return step, [params, net_state, opt_state, xs, ys, key]


def device_us_per_step(step, st, n=8, dispatches=4):
    from profile_step import _trace_device_ops
    for _ in range(3):
        st[0], st[1], st[2], loss = step(st[0], st[1], st[2], st[3], st[4],
                                         st[5])
    float(loss)

    def thunk():
        loss = None
        for _ in range(dispatches):
            st[0], st[1], st[2], loss = step(st[0], st[1], st[2], st[3],
                                             st[4], st[5])
        return loss

    per_op, tmpdir = _trace_device_ops(thunk, lambda l: float(l))
    shutil.rmtree(tmpdir, ignore_errors=True)
    # the scan compiles to a while op whose trace row CONTAINS its body's
    # rows — summing both double-counts; kernel time = non-while rows
    kernel_us = sum(t for nm, t in per_op.items()
                    if not nm.startswith("while"))
    return kernel_us / (n * dispatches), per_op


def _apply_variant(name):
    """Set the module flags for ``name``; returns an undo callable."""
    from bigdl_tpu import nn
    from bigdl_tpu.models import transformer
    from bigdl_tpu.nn import pooling, recurrent
    old = (pooling._PALLAS_POOL, nn.SpatialCrossMapLRN._PALLAS,
           recurrent._BLOCK_T, transformer._PALLAS_PAGED_ATTN,
           transformer._PALLAS_SPEC_VERIFY)
    if name in ("pallas_pool", "pallas_winops"):
        pooling._PALLAS_POOL = True
    if name in ("pallas_lrn", "pallas_winops"):
        nn.SpatialCrossMapLRN._PALLAS = True
    if name.startswith("blockt"):
        recurrent._BLOCK_T = int(name[len("blockt"):])
    if name in ("paged_attn", "paged_decode"):
        transformer._PALLAS_PAGED_ATTN = True
    if name in ("spec_verify", "paged_decode"):
        transformer._PALLAS_SPEC_VERIFY = True

    def undo():
        (pooling._PALLAS_POOL, nn.SpatialCrossMapLRN._PALLAS,
         recurrent._BLOCK_T, transformer._PALLAS_PAGED_ATTN,
         transformer._PALLAS_SPEC_VERIFY) = old
    return undo


def main():
    from bigdl_tpu import tensor as bt
    from bigdl_tpu.utils.engine import enable_compile_cache
    enable_compile_cache()
    bt.set_policy(getattr(bt, _os.environ.get("BIGDL_POLICY", "BF16_COMPUTE")))
    model_name = _sys.argv[1] if len(_sys.argv) > 1 else "vgg_cifar"
    batch = int(_sys.argv[2]) if len(_sys.argv) > 2 else 128
    variants = _sys.argv[3:] or ["base", "rbg"]
    import jax
    for name in variants:
        impl = "rbg" if name == "rbg" else "threefry2x32"
        t0 = time.perf_counter()
        jax.config.update("jax_default_prng_impl", impl)
        undo = _apply_variant(name)
        try:
            step, st = build_chunk(model_name, batch, impl)
            us, per_op = device_us_per_step(step, st)
        finally:
            undo()
        print(f"{model_name} bs{batch} {name}: device-busy "
              f"{us/1e3:.3f} ms/step  (setup {time.perf_counter()-t0:.0f}s)",
              flush=True)


if __name__ == "__main__":
    main()

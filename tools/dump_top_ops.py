"""Dump top device ops of a bench chunk-step variant (round-5 tooling)."""
import os as _os, sys as _sys
_REPO = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
_sys.path.insert(0, _REPO); _sys.path.insert(0, _os.path.join(_REPO, "tools"))

def main():
    import jax
    from bigdl_tpu import tensor as bt
    from ab_device_clock import build_chunk, device_us_per_step
    from bigdl_tpu.utils.engine import enable_compile_cache
    enable_compile_cache()
    bt.set_policy(getattr(bt, _os.environ.get("BIGDL_POLICY", "BF16_COMPUTE")))
    model_name = _sys.argv[1] if len(_sys.argv) > 1 else "vgg_cifar"
    batch = int(_sys.argv[2]) if len(_sys.argv) > 2 else 128
    impl = _sys.argv[3] if len(_sys.argv) > 3 else "rbg"
    topn = int(_sys.argv[4]) if len(_sys.argv) > 4 else 25
    jax.config.update("jax_default_prng_impl", impl)
    step, st = build_chunk(model_name, batch, impl)
    us, per_op = device_us_per_step(step, st)
    print(f"{model_name} bs{batch} {impl}: device-busy {us/1e3:.3f} ms/step")
    total = sum(per_op.values())
    for name, t in per_op.most_common(topn):
        print(f"  {t/32/1e3:8.4f} ms/step {100*t/total:5.1f}%  {name}")

if __name__ == "__main__":
    main()

"""Device-clock A/B of a module flag on a full bench chunk step.

Usage: python tools/ab_flag.py MODEL BATCH MODULE ATTR
e.g.:  python tools/ab_flag.py resnet50 64 bigdl_tpu.nn.conv _DOT_1X1
"""
import os as _os, sys as _sys, importlib, time
_REPO = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
_sys.path.insert(0, _REPO); _sys.path.insert(0, _os.path.join(_REPO, "tools"))


def main():
    from bigdl_tpu import tensor as bt
    from ab_device_clock import build_chunk, device_us_per_step
    from bigdl_tpu.utils.engine import enable_compile_cache
    enable_compile_cache()
    bt.set_policy(getattr(bt, _os.environ.get("BIGDL_POLICY", "BF16_COMPUTE")))
    model_name, batch = _sys.argv[1], int(_sys.argv[2])
    mod, attr = importlib.import_module(_sys.argv[3]), _sys.argv[4]
    import jax
    impl = _os.environ.get("BIGDL_PRNG", "rbg") or "threefry2x32"
    jax.config.update("jax_default_prng_impl", impl)
    for value in (False, True, False, True):
        setattr(mod, attr, value)
        t0 = time.perf_counter()
        step, st = build_chunk(model_name, batch, impl)
        us, per_op = device_us_per_step(step, st)
        print(f"{model_name} bs{batch} {attr}={value}: device-busy "
              f"{us/1e3:.3f} ms/step (setup {time.perf_counter()-t0:.0f}s)",
              flush=True)


if __name__ == "__main__":
    main()

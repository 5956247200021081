"""Wall-clock A/B of the training loop's HOST pipeline (ISSUE 4).

The device clock (tools/ab_device_clock.py) cannot see this change: the
prefetch pipeline and the cadenced host sync move work OFF the critical
path of the host loop, so the instrument is per-step WALL time of the
real ``LocalOptimizer.optimize`` loop over a real transformer-chain
dataset — the quantity a per-step blocking host sync and the serial
Transformer chain inflate (PERF_NOTES r1).

Staged for the on-chip run (host-side overlap is provable on CPU — see
tests/test_prefetch.py::TestOverlap — so adoption is not gated on it):

  python tools/ab_host_pipeline.py lenet 256 40 base prefetch_off \
      sync_every_step serial

Variants:
  base             prefetch on (depth 2) + cadenced sync (the defaults)
  prefetch_off     BIGDL_PREFETCH=0, cadenced sync
  sync_every_step  prefetch on, BIGDL_SYNC_EVERY_STEP=1
  serial           both off — the pre-ISSUE-4 loop
"""
import os as _os
import sys as _sys
_REPO = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
_sys.path.insert(0, _REPO)
import time

import numpy as np

VARIANTS = {
    "base": {},
    "prefetch_off": {"BIGDL_PREFETCH": "0"},
    "sync_every_step": {"BIGDL_SYNC_EVERY_STEP": "1"},
    "serial": {"BIGDL_PREFETCH": "0", "BIGDL_SYNC_EVERY_STEP": "1"},
}


def build_opt(model_name, batch):
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, ByteRecord
    from bigdl_tpu.dataset.image import (BytesToGreyImg, BytesToImg,
                                         HFlip, ImgNormalizer,
                                         ImgRdmCropper, ImgToBatch)
    from bigdl_tpu.optim import LocalOptimizer
    from bigdl_tpu.utils.random import set_seed
    from bigdl_tpu.utils.table import T

    set_seed(1)
    rs = np.random.RandomState(0)
    if model_name == "lenet":
        from bigdl_tpu.models.lenet import LeNet5
        recs = [ByteRecord(rs.randint(0, 255, 32 * 32, np.uint8).tobytes(),
                           float(rs.randint(1, 11)))
                for _ in range(batch * 4)]
        ds = (DataSet.array(recs) >> BytesToGreyImg(32, 32)
              >> ImgNormalizer(128.0, 128.0) >> ImgRdmCropper(28, 28)
              >> HFlip() >> ImgToBatch(batch))
        model = LeNet5(class_num=10)
    elif model_name == "inception":
        from bigdl_tpu.models.inception import Inception_v1
        try:
            import io
            from PIL import Image
            buf = io.BytesIO()
            Image.fromarray(rs.randint(0, 255, (256, 256, 3), np.uint8)
                            ).save(buf, format="JPEG")
            raw = buf.getvalue()
        except ImportError:
            raise SystemExit("inception A/B needs Pillow (JPEG decode is "
                             "the host load being measured)")
        recs = [ByteRecord(raw, float(rs.randint(1, 1001)))
                for _ in range(batch * 4)]
        ds = (DataSet.array(recs) >> BytesToImg(scale_to=256)
              >> ImgNormalizer((124.0, 117.0, 104.0), (59.0, 57.0, 57.0))
              >> ImgRdmCropper(224, 224) >> HFlip() >> ImgToBatch(batch))
        model = Inception_v1(class_num=1000)
    else:
        raise SystemExit(f"unknown model {model_name!r}")
    opt = LocalOptimizer(model, ds, nn.ClassNLLCriterion())
    opt.set_state(T(learningRate=0.05))
    return opt


def run_variant(model_name, batch, steps, name):
    from bigdl_tpu.optim import max_iteration
    env = VARIANTS[name]
    old = {k: _os.environ.get(k) for k in env}
    _os.environ.update(env)
    try:
        opt = build_opt(model_name, batch)
        opt.set_end_when(max_iteration(steps))
        t0 = time.perf_counter()
        opt.optimize()
        wall = time.perf_counter() - t0
    finally:
        for k, v in old.items():
            if v is None:
                _os.environ.pop(k, None)
            else:
                _os.environ[k] = v
    m = opt.metrics
    spans = {s: m.get("span: " + s) for s in
             ("data-load", "data-load/fetch", "h2d", "dispatch",
              "host-wait")}
    return wall, spans


def main():
    model_name = _sys.argv[1] if len(_sys.argv) > 1 else "lenet"
    batch = int(_sys.argv[2]) if len(_sys.argv) > 2 else 256
    steps = int(_sys.argv[3]) if len(_sys.argv) > 3 else 40
    variants = _sys.argv[4:] or ["base", "prefetch_off", "sync_every_step",
                                 "serial"]
    run_variant(model_name, batch, min(steps, 5), variants[0])  # warm
    print(f"{'variant':<16} {'wall_ms/step':>12}  span totals (s)")
    for name in variants:
        wall, spans = run_variant(model_name, batch, steps, name)
        detail = " ".join(f"{k}={v[0]:.3f}" for k, v in spans.items()
                          if v[1])
        print(f"{name:<16} {wall / steps * 1e3:>12.2f}  {detail}")


if __name__ == "__main__":
    main()

"""Same-process A/B of full train-step variants.

Wall clocks vary run to run (a one-chip machine shares its host's
cores), so only within-process comparisons are trustworthy.  This
builds the bench train
step under each flag combination and times them in interleaved windows
(A B A B A B), reporting the per-variant minimum.

Usage: python tools/ab_step.py [model] [batch]
"""
from __future__ import annotations

import os as _os
import sys as _sys

_REPO_ROOT = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _REPO_ROOT not in _sys.path:
    _sys.path.insert(0, _REPO_ROOT)  # run without an installed package

import sys
import time


def build(model_name, batch, s2d, lrn_stencil, sqrt_pow=True):
    import bigdl_tpu.nn.conv as convmod
    from bigdl_tpu.nn.normalization import SpatialCrossMapLRN
    convmod._S2D_STEM = s2d
    SpatialCrossMapLRN._STENCIL = lrn_stencil
    SpatialCrossMapLRN._SQRT_POW = sqrt_pow
    sys.path.insert(0, "tools")
    from profile_step import build_step
    return build_step(model_name, batch)


def time_window(step, state, iters=10):
    t0 = time.perf_counter()
    params, net_state, opt_state, x, y, key = state
    for _ in range(iters):
        params, net_state, opt_state, loss = step(
            params, net_state, opt_state, x, y, key)
    float(loss)
    return (time.perf_counter() - t0) / iters * 1e3, (
        params, net_state, opt_state, x, y, key)


def main():
    model_name = sys.argv[1] if len(sys.argv) > 1 else "inception"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 128
    variants = {}
    for s2d in (False, True):
        for st in (False, True):
            for sq in (False, True):
                variants["s2d=%d stencil=%d sqrt=%d" % (s2d, st, sq)] = dict(
                    s2d=s2d, lrn_stencil=st, sqrt_pow=sq)
    steps = {}
    for name, flags in variants.items():
        step, args = build(model_name, batch, **flags)
        params, net_state, opt_state, x, y, key = args
        for _ in range(3):
            params, net_state, opt_state, loss = step(
                params, net_state, opt_state, x, y, key)
        float(loss)
        steps[name] = (step, (params, net_state, opt_state, x, y, key))

    best = {name: float("inf") for name in variants}
    for _ in range(3):
        for name in variants:
            step, state = steps[name]
            dt, state = time_window(step, state)
            steps[name] = (step, state)
            best[name] = min(best[name], dt)
    for name, ms in best.items():
        print("%-28s %8.2f ms/step  %8.1f img/s" % (name, ms, batch / ms * 1e3))


if __name__ == "__main__":
    main()

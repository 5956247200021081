"""A/B microbenchmarks for maxpool-backward and LRN variants on the real
Inception-v1 shapes (one process, chained dispatches, hard sync).

Variants are timed as full forward+backward of a scalar loss so each
candidate pays its true residual/fusion cost.  Used to choose the
implementations in nn/pooling.py and nn/normalization.py; results are
recorded in PERF_NOTES.md.
"""
from __future__ import annotations

import os as _os
import sys as _sys

_REPO_ROOT = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _REPO_ROOT not in _sys.path:
    _sys.path.insert(0, _REPO_ROOT)  # run without an installed package

import sys
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax


def timeit_grad(grad_fn, x, iters=30):
    """ms per fwd+bwd, with all ``iters`` executions inside ONE dispatch
    (fori_loop chaining x through the gradient) so per-call dispatch
    cost cannot mask sub-ms device-time differences."""
    eps = jnp.asarray(1e-6, x.dtype)

    @jax.jit
    def chained(v):
        return lax.fori_loop(
            0, iters, lambda i, u: u - eps * grad_fn(u).astype(u.dtype), v)

    out = chained(x)
    float(jnp.sum(out.astype(jnp.float32)))  # hard sync: D2H of the result
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = chained(x)
        float(jnp.sum(out.astype(jnp.float32)))
        best = min(best, (time.perf_counter() - t0) / iters * 1e3)
    return best


# ---------------------------------------------------------------- maxpool

def sas_pool(x, window, strides, padding):
    """Baseline: reduce_window with XLA's default select-and-scatter VJP."""
    kh, kw = window
    dh, dw = strides
    return lax.reduce_window(
        x, -jnp.inf, lax.max,
        window_dimensions=(1, 1, kh, kw),
        window_strides=(1, 1, dh, dw),
        padding=((0, 0), (0, 0)) + padding)


def pool_cases(batch):
    # (shape, window, strides, padding) — every maxpool in Inception-v1
    return [
        ((batch, 64, 112, 112), (3, 3), (2, 2), ((0, 1), (0, 1))),
        ((batch, 192, 56, 56), (3, 3), (2, 2), ((0, 1), (0, 1))),
        ((batch, 256, 28, 28), (3, 3), (1, 1), ((1, 1), (1, 1))),
        ((batch, 480, 28, 28), (3, 3), (2, 2), ((0, 1), (0, 1))),
        ((batch, 480, 14, 14), (3, 3), (1, 1), ((1, 1), (1, 1))),
        ((batch, 512, 14, 14), (3, 3), (1, 1), ((1, 1), (1, 1))),
        ((batch, 832, 14, 14), (3, 3), (2, 2), ((0, 1), (0, 1))),
        ((batch, 832, 7, 7), (3, 3), (1, 1), ((1, 1), (1, 1))),
    ]


def run_pool_ab(batch=128, dtype=jnp.float32):
    from bigdl_tpu.nn.pooling import _max_pool2d
    rs = np.random.RandomState(0)
    print("%-28s %10s %10s" % ("maxpool case", "s&s ms", "stencil ms"))
    tot_a = tot_b = 0.0
    for shape, window, strides, padding in pool_cases(batch):
        x = jnp.asarray(np.maximum(rs.randn(*shape), 0), dtype)

        def loss_sas(v):
            return (sas_pool(v, window, strides, padding)
                    .astype(jnp.float32) ** 2).sum()

        def loss_stencil(v):
            return (_max_pool2d(v, window, strides, padding)
                    .astype(jnp.float32) ** 2).sum()

        ta = timeit_grad(jax.grad(loss_sas), x)
        tb = timeit_grad(jax.grad(loss_stencil), x)
        tot_a += ta
        tot_b += tb
        print("%-28s %10.3f %10.3f" % (
            "%s k%s s%s" % (shape, window, strides), ta, tb))
    print("%-28s %10.3f %10.3f" % ("TOTAL", tot_a, tot_b))


# -------------------------------------------------------------------- LRN

def lrn_reduce_window(x, size=5, alpha=0.0001, beta=0.75, k=1.0):
    lo = (size - 1) // 2
    hi = size - 1 - lo
    s = lax.reduce_window(
        x * x, 0.0, lax.add,
        window_dimensions=(1, size, 1, 1), window_strides=(1, 1, 1, 1),
        padding=((0, 0), (lo, hi), (0, 0), (0, 0)))
    denom = (k + (alpha / size) * s) ** beta
    return x / denom


def lrn_band_matmul(x, size=5, alpha=0.0001, beta=0.75, k=1.0):
    lo = (size - 1) // 2
    hi = size - 1 - lo
    b, c, h, w = x.shape
    band = np.zeros((c, c), np.float32)
    for d in range(c):
        band[d, max(0, d - lo):min(c, d + hi + 1)] = 1.0
    sq = (x * x).reshape(b, c, h * w)
    s = jnp.einsum("dc,bcs->bds", jnp.asarray(band, x.dtype), sq,
                   preferred_element_type=jnp.float32)
    s = s.astype(x.dtype).reshape(b, c, h, w)
    denom = (k + (alpha / size) * s) ** beta
    return x / denom


def lrn_stencil(x, size=5, alpha=0.0001, beta=0.75, k=1.0):
    lo = (size - 1) // 2
    hi = size - 1 - lo
    sq = x * x
    sqp = jnp.pad(sq, ((0, 0), (lo, hi), (0, 0), (0, 0)))
    c = x.shape[1]
    s = sum(lax.slice_in_dim(sqp, t, t + c, axis=1) for t in range(size))
    denom = (k + (alpha / size) * s) ** beta
    return x / denom


def lrn_stencil_sqrt(x, size=5, alpha=0.0001, beta=0.75, k=1.0):
    lo = (size - 1) // 2
    hi = size - 1 - lo
    sq = x * x
    sqp = jnp.pad(sq, ((0, 0), (lo, hi), (0, 0), (0, 0)))
    c = x.shape[1]
    s = sum(lax.slice_in_dim(sqp, t, t + c, axis=1) for t in range(size))
    z = k + (alpha / size) * s
    if beta == 0.75:
        denom = jnp.sqrt(jnp.sqrt(z)) ** 3  # z^(3/4) without exp/log
    else:
        denom = z ** beta
    return x / denom


def run_lrn_ab(batch=128, dtype=jnp.float32):
    rs = np.random.RandomState(0)
    cases = [((batch, 64, 56, 56),), ((batch, 192, 28, 28),)]
    variants = [("reduce_window", lrn_reduce_window),
                ("band_matmul", lrn_band_matmul),
                ("stencil_pow", lrn_stencil),
                ("stencil_sqrt", lrn_stencil_sqrt)]
    print("%-22s" % "LRN case" + "".join("%15s" % n for n, _ in variants))
    for (shape,) in cases:
        x = jnp.asarray(rs.randn(*shape), dtype)
        row = "%-22s" % str(shape)
        for name, fn in variants:
            def loss(v, fn=fn):
                return (fn(v).astype(jnp.float32) ** 2).sum()
            row += "%15.3f" % timeit_grad(jax.grad(loss), x)
        print(row)




# ------------------------------------------------- shifted-slices maxpool

def shift_pool(x, window, strides, padding):
    """Maxpool as a folded maximum over kh*kw strided shifted slices —
    pure eltwise ops the fuser can handle, no reduce_window/select-and-
    scatter emitter.  Autodiff backward = chain of eltwise select grads."""
    kh, kw = window
    dh, dw = strides
    (plh, phh), (plw, phw) = padding
    neg = jnp.asarray(-jnp.inf, x.dtype)
    xp = jnp.pad(x, ((0, 0), (0, 0), (plh, phh), (plw, phw)),
                 constant_values=neg)
    b, c, hp, wp = xp.shape
    oh = (hp - kh) // dh + 1
    ow = (wp - kw) // dw + 1
    y = None
    for i in range(kh):
        for j in range(kw):
            s = lax.slice(xp, (0, 0, i, j),
                          (b, c, i + (oh - 1) * dh + 1, j + (ow - 1) * dw + 1),
                          (1, 1, dh, dw))
            y = s if y is None else jnp.maximum(y, s)
    return y


def run_pool_variant_ab(candidate, label, batch=128, dtype=jnp.float32):
    """A/B an alternative maxpool implementation vs the shipped
    reduce_window/select-and-scatter path on every Inception pool shape.

    NOTE (round 3): this chained-fori_loop harness serializes on its
    dependency chain (~280 GB/s ceiling vs 662+ GB/s isolated), so treat
    small deltas as noise — use tools/profile_step._trace_device_ops for
    sub-ms decisions (PERF_NOTES "Round-3 MFU attack")."""
    rs = np.random.RandomState(0)
    from bigdl_tpu.nn.pooling import _max_pool2d
    print("%-34s %10s %10s" % ("maxpool case", "s&s ms", label + " ms"))
    tot_a = tot_b = 0.0
    for shape, window, strides, padding in pool_cases(batch):
        x = jnp.asarray(np.maximum(rs.randn(*shape), 0), dtype)

        def loss_sas(v):
            return (_max_pool2d(v, window, strides, padding)
                    .astype(jnp.float32) ** 2).sum()

        def loss_cand(v):
            return (candidate(v, window, strides, padding)
                    .astype(jnp.float32) ** 2).sum()

        ta = timeit_grad(jax.grad(loss_sas), x)
        tb = timeit_grad(jax.grad(loss_cand), x)
        tot_a += ta
        tot_b += tb
        print("%-34s %10.3f %10.3f" % (
            "%s k%s s%s" % (shape, window, strides), ta, tb))
    print("%-34s %10.3f %10.3f" % ("TOTAL", tot_a, tot_b))


def sep_pool(x, window, strides, padding):
    """Separable maxpool: 1-D row-window max then 1-D column-window max.
    max is associative so the result is exact; each pass gives the
    emitter a tiny 1-D window, and the VJP becomes two 1-D
    select-and-scatters."""
    kh, kw = window
    dh, dw = strides
    (plh, phh), (plw, phw) = padding
    y = lax.reduce_window(
        x, -jnp.inf, lax.max,
        window_dimensions=(1, 1, 1, kw), window_strides=(1, 1, 1, dw),
        padding=((0, 0), (0, 0), (0, 0), (plw, phw)))
    return lax.reduce_window(
        y, -jnp.inf, lax.max,
        window_dimensions=(1, 1, kh, 1), window_strides=(1, 1, dh, 1),
        padding=((0, 0), (0, 0), (plh, phh), (0, 0)))


def run_shift_ab(batch=128, dtype=jnp.float32):
    run_pool_variant_ab(shift_pool, "shift", batch, dtype)


def run_sep_ab(batch=128, dtype=jnp.float32):
    run_pool_variant_ab(sep_pool, "sep", batch, dtype)


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    dtype = jnp.bfloat16 if (len(sys.argv) > 2 and sys.argv[2] == "bf16") else jnp.float32
    if which in ("pool", "all"):
        run_pool_ab(dtype=dtype)
    if which in ("lrn", "all"):
        run_lrn_ab(dtype=dtype)
    if which in ("shift", "all"):
        run_shift_ab(dtype=dtype)
    if which in ("sep", "all"):
        run_sep_ab(dtype=dtype)

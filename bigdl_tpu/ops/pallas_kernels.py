"""Pallas TPU kernels (see /opt/skills/guides/pallas_guide.md).

The device-side hot loops of the reference's native layer (mkl.c vector
math / axpy / scal) compile through XLA; Pallas covers the cases where
hand-fusion still wins (the recurrence kernels) or is a candidate waiting
for its measurement (max pool, LRN, the decode attention kernels).

On non-TPU backends the kernels run through the Pallas interpreter
(``interpret=True``) so tests exercise the same code path on the CPU mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _on_tpu() -> bool:
    """True on a TPU backend, False on the CPU (where callers take the
    Pallas interpreter or their XLA reference path).  Any other backend
    is an error: these are Mosaic TPU kernels, and quietly taking the
    interpreter there would hide which device did the work."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas TPU kernels cannot run on the {backend!r} backend")
    return backend == "tpu"


def _interpreted(flag) -> bool:
    """Whether a kernel that ``flag`` switched on runs through the Pallas
    interpreter: only when the flag asks for it (``"interpret"``) or the
    backend is the CPU."""
    return flag == "interpret" or not _on_tpu()


def _out_struct(shape, dtype, *operands):
    """``out_shape`` entry for a ``pallas_call``: carries the union of
    its operands' varying mesh axes, which ``jax.shard_map`` requires
    of a kernel traced under ``check_vma=True`` (empty outside one)."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


# ---------------------------------------------------------------- LRN
#
# Cross-channel LRN (y = x / (k + alpha/n * sum_win x^2)^beta) costs
# ~5.6 ms of the Inception-v1 step through XLA (channel-window
# reduce_window + the backward's mul/div fusions, PROFILE_inception.md
# round 3).  Unlike the maxpool case, LRN maps PERFECTLY onto Mosaic's
# (sublane, lane) model: collapse HW onto lanes and put C on sublanes —
# the size-5 channel window becomes five unit-stride sublane slices, no
# lane padding waste, no strided slicing.  Forward and the closed-form
# backward
#   dx = dy z^-b - (2 a b / n) x * sum_win(dy x z^(-b-1))
# are each ONE pass over the block (backward recomputes z from x).


def _lrn_zpow(sq_sum, size, alpha, beta, k):
    z = k + (alpha / size) * sq_sum
    return z, _lrn_pow(z, beta)


def _lrn_win_sum(v, size, adjoint=False):
    """Sum over the size-window centred on each channel (sublane dim 0 of
    a (C, T) block), zero padding.  ``adjoint=True`` sums over the
    TRANSPOSED window (pad (hi, lo) instead of (lo, hi)) — required in
    the backward for even sizes, where the window is asymmetric."""
    lo = (size - 1) // 2
    hi = size - 1 - lo
    if adjoint:
        lo, hi = hi, lo
    c = v.shape[0]
    vp = jnp.pad(v, ((lo, hi), (0, 0)))
    acc = None
    for s in range(size):
        sl = lax.slice(vp, (s, 0), (s + c, v.shape[1]))
        acc = sl if acc is None else acc + sl
    return acc


def _lrn_pow(z, beta):
    """z^beta from an already-computed z (no window sum)."""
    if beta == 0.75:
        zb = jnp.sqrt(jnp.sqrt(z))
        return zb * zb * zb
    return z ** beta


def _lrn_fwd_kernel(x_ref, y_ref, *, size, alpha, beta, k):
    """Primal-only forward: no residual writes (validation/inference)."""
    x = x_ref[0].astype(jnp.float32)        # (C, T)
    _, zpow = _lrn_zpow(_lrn_win_sum(x * x, size), size, alpha, beta, k)
    y_ref[0] = (x / zpow).astype(y_ref.dtype)


def _lrn_fwd_res_kernel(x_ref, y_ref, z_ref, *, size, alpha, beta, k):
    """Forward under AD: the square-window running sum z stays in VMEM
    between computing y and being stored as the VJP residual — the
    backward never recomputes the window sum of x^2 (round 6; the
    round-3 kernel recomputed z from x in the backward)."""
    x = x_ref[0].astype(jnp.float32)        # (C, T)
    z, zpow = _lrn_zpow(_lrn_win_sum(x * x, size), size, alpha, beta, k)
    y_ref[0] = (x / zpow).astype(y_ref.dtype)
    z_ref[0] = z


def _lrn_bwd_kernel(x_ref, z_ref, g_ref, dx_ref, *, size, alpha, beta, k):
    """Analytic VJP from the STORED z: one adjoint window sum over
    u = g x z^(-beta-1); the only window pass in the whole backward."""
    x = x_ref[0].astype(jnp.float32)
    z = z_ref[0]
    g = g_ref[0].astype(jnp.float32)
    zpow = _lrn_pow(z, beta)
    u = g * x / (zpow * z)                  # dy x z^(-b-1)
    dx = (g / zpow - (2.0 * alpha * beta / size) * x
          * _lrn_win_sum(u, size, adjoint=True))
    dx_ref[0] = dx.astype(dx_ref.dtype)


def _lrn_call(kernel, args, out_shapes, size, alpha, beta, k,
              interpret=False):
    """``out_shapes``: list of dtypes for (1, c, t)-blocked outputs; the
    first is the primary (y or dx), any extra ride along (z residual)."""
    x = args[0]
    n, c, h, w = x.shape
    hw = h * w
    # lane tile: a multiple of 128, sized so one (C, T) f32 block stays
    # near 0.8 MB whatever C is — the backward holds four such blocks
    # double-buffered plus its temporaries inside the 16 MB scoped VMEM
    # (a whole 192 x 3136 image per block was refused by the compiler)
    t = min(-(-hw // 128) * 128, max(128, 204800 // c // 128 * 128))
    # ragged final block is safe: the channel window never crosses lanes,
    # so out-of-bounds lanes compute garbage that the store drops
    flat = [a.reshape(n, c, hw) for a in args]
    spec = pl.BlockSpec((1, c, t), lambda i, j: (i, 0, j),
                        memory_space=pltpu.VMEM)
    multi = len(out_shapes) > 1
    out = pl.pallas_call(
        functools.partial(kernel, size=size, alpha=alpha, beta=beta, k=k),
        grid=(n, -(-hw // t)),
        in_specs=[spec] * len(flat),
        out_specs=[spec] * len(out_shapes) if multi else spec,
        out_shape=([_out_struct((n, c, hw), d, *args) for d in out_shapes]
                   if multi else _out_struct((n, c, hw), out_shapes[0],
                                             *args)),
        interpret=interpret,
    )(*flat)
    if multi:
        return [o.reshape(n, c, h, w) for o in out]
    return out.reshape(n, c, h, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def lrn_channel(x, size, alpha, beta, k, interpret=False):
    """Fused cross-channel LRN with a hand-written one-pass backward.
    NCHW, any H*W — ragged lane blocks are safe because the channel
    window never crosses lanes (out-of-bounds lanes are dropped on
    store).  Under AD the forward additionally stores z (the k +
    alpha/n * window-sum-of-squares denominator base) so the backward
    is a single pass with ONE adjoint window sum; a no-grad forward
    skips the z writes entirely."""
    return _lrn_call(_lrn_fwd_kernel, (x,), [x.dtype], size, alpha, beta,
                     k, interpret)


def _lrn_vjp_fwd(x, size, alpha, beta, k, interpret=False):
    y, z = _lrn_call(_lrn_fwd_res_kernel, (x,), [x.dtype, jnp.float32],
                     size, alpha, beta, k, interpret)
    return y, (x, z)


def _lrn_vjp_bwd(size, alpha, beta, k, interpret, res, g):
    x, z = res
    return (_lrn_call(_lrn_bwd_kernel, (x, z, g), [x.dtype], size, alpha,
                      beta, k, interpret),)


lrn_channel.defvjp(_lrn_vjp_fwd, _lrn_vjp_bwd)


# ---------------------------------------------------- bidirectional LSTM
#
# The Bi-LSTM flagship's recurrence as TWO whole-sequence Pallas kernels
# (forward + hand-derived backward), direction-batched like
# Recurrent._apply_fused_lstm's scan body.  h/c (and in the backward,
# dh/dc and the dWh accumulator) stay resident in VMEM scratch across
# all T grid steps — the "gates + carry in VMEM" formulation.
#
# This is the first measured Mosaic WIN on this chip (round 5, v5e,
# device clock, B128 T500 H128): forward 1.071 -> 0.527 ms vs lax.scan
# (bit-exact), fwd+bwd 5.0 -> 2.15 ms vs the scan's autodiff (grads
# equal to ~1e-4 rel, f32 accumulation order).  Every previous Pallas
# candidate here lost to the XLA emitter (PERF_NOTES rounds 2-5:
# flash attention, maxpool, LRN stencil, fused SGD, a single-direction
# step-per-grid-step LSTM scan) — the recurrence wins because the emitter's while-loop
# carries per-step overhead the sequential grid amortizes, not because
# Mosaic beats XLA on the math.


def _bilstm_fwd_body(zx_ref, wht_ref, h_ref, c_ref, h_scr, c_scr):
    """One grid step = ``block_t`` timesteps, BOTH directions; zx already
    holds the hoisted input projection + bias.  The h/c carry stays in
    VMEM scratch across the whole block (and across blocks); the
    recurrent gemms stay serial — the sequential dependency is real —
    but the per-grid-step overhead amortizes over the block and the
    zx/h streams move in block_t-sized DMAs.  ``c_ref is None`` =
    primal-only call: the cell-state stack is a VJP residual, so a
    no-grad forward skips its HBM writes entirely."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)
        c_scr[...] = jnp.zeros_like(c_scr)

    hdim = h_scr.shape[-1]
    for tt in range(zx_ref.shape[0]):    # static block_t timesteps
        for d in range(h_scr.shape[0]):  # static direction count (1 or 2)
            z = zx_ref[tt, d].astype(jnp.float32) + jnp.dot(
                h_scr[d].astype(wht_ref.dtype), wht_ref[d],
                preferred_element_type=jnp.float32)
            i = jax.nn.sigmoid(z[:, :hdim])
            f = jax.nn.sigmoid(z[:, hdim:2 * hdim])
            g = jnp.tanh(z[:, 2 * hdim:3 * hdim])
            o = jax.nn.sigmoid(z[:, 3 * hdim:])
            c_new = f * c_scr[d] + i * g
            h_new = o * jnp.tanh(c_new)
            h_scr[d] = h_new
            c_scr[d] = c_new
            h_ref[tt, d] = h_new
            if c_ref is not None:
                c_ref[tt, d] = c_new


def _bilstm_fwd_kernel(zx_ref, wht_ref, h_ref, c_ref, h_scr, c_scr):
    _bilstm_fwd_body(zx_ref, wht_ref, h_ref, c_ref, h_scr, c_scr)


def _bilstm_fwd_kernel_primal(zx_ref, wht_ref, h_ref, h_scr, c_scr):
    _bilstm_fwd_body(zx_ref, wht_ref, h_ref, None, h_scr, c_scr)


def _bilstm_bwd_kernel(zx_ref, hprev_ref, c_ref, cprev_ref, g_ref,
                       wht_ref, dzx_ref, dwh_ref, dh_scr, dc_scr, dwh_scr):
    """Reverse-time block: recompute the gates from zx_t + h_{t-1} @ Wh,
    fold the carried (dh, dc) and each step's output cotangent into
    dzx_t, accumulate dWh.  hprev/cprev arrive PRE-SHIFTED (index t
    holds step t-1's value, zeros at t=0).  The dWh accumulation is the
    one gemm the serial chain does NOT constrain: it batches over the
    whole block as ONE (H, block_t*B) x (block_t*B, 4H) contraction."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        dc_scr[...] = jnp.zeros_like(dc_scr)
        dwh_scr[...] = jnp.zeros_like(dwh_scr)

    hdim = dh_scr.shape[-1]
    kt = zx_ref.shape[0]
    for d in range(dh_scr.shape[0]):
        dzs, hprevs = [], []
        for tt in reversed(range(kt)):   # reverse time WITHIN the block
            hprev = hprev_ref[tt, d]
            z = zx_ref[tt, d].astype(jnp.float32) + jnp.dot(
                hprev.astype(wht_ref.dtype), wht_ref[d],
                preferred_element_type=jnp.float32)
            i = jax.nn.sigmoid(z[:, :hdim])
            f = jax.nn.sigmoid(z[:, hdim:2 * hdim])
            g = jnp.tanh(z[:, 2 * hdim:3 * hdim])
            o = jax.nn.sigmoid(z[:, 3 * hdim:])
            tc = jnp.tanh(c_ref[tt, d])
            dh_total = g_ref[tt, d] + dh_scr[d]
            dc_total = dc_scr[d] + dh_total * o * (1.0 - tc * tc)
            dz = jnp.concatenate([
                dc_total * g * i * (1.0 - i),
                dc_total * cprev_ref[tt, d] * f * (1.0 - f),
                dc_total * i * (1.0 - g * g),
                dh_total * tc * o * (1.0 - o),
            ], axis=-1)
            dzx_ref[tt, d] = dz
            dh_scr[d] = jnp.dot(dz.astype(wht_ref.dtype), wht_ref[d].T,
                                preferred_element_type=jnp.float32)
            dc_scr[d] = dc_total * f
            dzs.append(dz)
            hprevs.append(hprev)
        if kt == 1:
            dwh_scr[d] += jnp.dot(hprevs[0].T, dzs[0],
                                  preferred_element_type=jnp.float32)
        else:
            dwh_scr[d] += jnp.dot(
                jnp.concatenate(hprevs, axis=0).T,
                jnp.concatenate(dzs, axis=0),
                preferred_element_type=jnp.float32)
    dwh_ref[...] = dwh_scr[...]


def _shift_prev(xs):
    """xs[t] -> xs[t-1] along axis 0, zeros at t=0 (initial h/c)."""
    return jnp.concatenate([jnp.zeros_like(xs[:1]), xs[:-1]], axis=0)


def _pad_time(xs, block_t):
    """Zero-pad the time axis to a multiple of ``block_t``.

    Trailing zero steps are harmless in BOTH directions: the forward's
    padded steps run after every real step (their garbage h/c never
    feeds a real output), and the reverse-time backward starts at them
    with zero cotangents, so every dz/dWh contribution they produce is
    exactly zero and the carries reaching the real steps are the same
    zeros an unpadded kernel initializes with."""
    t = xs.shape[0]
    tp = -(-t // block_t) * block_t
    if tp == t:
        return xs
    return jnp.concatenate(
        [xs, jnp.zeros((tp - t,) + xs.shape[1:], xs.dtype)], axis=0)


@functools.partial(jax.jit, static_argnames=("interpret", "with_c",
                                             "block_t"))
def _bilstm_fwd_call(zx, wht, interpret=False, with_c=True, block_t=1):
    t, nd, b, h4 = zx.shape
    h = h4 // 4
    kt = block_t
    out_spec = pl.BlockSpec((kt, nd, b, h), lambda i: (i, 0, 0, 0),
                            memory_space=pltpu.VMEM)
    out_shape = _out_struct((t, nd, b, h), jnp.float32, zx, wht)
    return pl.pallas_call(
        _bilstm_fwd_kernel if with_c else _bilstm_fwd_kernel_primal,
        grid=(t // kt,),
        in_specs=[
            pl.BlockSpec((kt, nd, b, h4), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((nd, h, h4), lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[out_spec, out_spec] if with_c else out_spec,
        out_shape=[out_shape, out_shape] if with_c else out_shape,
        scratch_shapes=[pltpu.VMEM((nd, b, h), jnp.float32),
                        pltpu.VMEM((nd, b, h), jnp.float32)],
        interpret=interpret,
    )(zx, wht)


@functools.partial(jax.jit, static_argnames=("interpret", "block_t"))
def _bilstm_bwd_call(zx, wht, hs, cs, gout, interpret=False, block_t=1):
    t, nd, b, h4 = zx.shape
    h = h4 // 4
    kt = block_t
    nblk = t // kt
    rev = lambda i: (nblk - 1 - i, 0, 0, 0)
    return pl.pallas_call(
        _bilstm_bwd_kernel,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((kt, nd, b, h4), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((kt, nd, b, h), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((kt, nd, b, h), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((kt, nd, b, h), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((kt, nd, b, h), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((nd, h, h4), lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((kt, nd, b, h4), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((nd, h, h4), lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[_out_struct((t, nd, b, h4), jnp.float32, zx, wht, gout),
                   _out_struct((nd, h, h4), jnp.float32, zx, wht, gout)],
        scratch_shapes=[pltpu.VMEM((nd, b, h), jnp.float32),
                        pltpu.VMEM((nd, b, h), jnp.float32),
                        pltpu.VMEM((nd, h, h4), jnp.float32)],
        interpret=interpret,
    )(zx, _shift_prev(hs), cs, _shift_prev(cs), gout, wht)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def bilstm_recurrence(zx, wht, interpret=False, block_t=1):
    """Direction-batched LSTM recurrence: zx (T, D, B, 4H) hoisted input
    projection (+bias) with D directions (1 = plain Recurrent, 2 =
    BiRecurrent), wht (D, H, 4H) recurrent weights; returns the h stack
    (T, D, B, H) f32.  Same math as the lax.scan body in
    Recurrent._apply_fused_lstm (forward bit-exact; gradients equal up
    to f32 accumulation order).  ``block_t`` > 1 processes that many
    timesteps per grid step (round-6 multi-timestep blocking; the time
    axis is zero-padded to a multiple — see _pad_time for why that is
    exact)."""
    # primal-only: skip the c-stack output — it is a VJP residual, and
    # a no-grad forward (validation/inference) should not pay its HBM
    # writes (~65 MB at the flagship shapes)
    t = zx.shape[0]
    hs = _bilstm_fwd_call(_pad_time(zx, block_t), wht,
                          interpret=interpret, with_c=False,
                          block_t=block_t)
    return hs[:t]


def _bilstm_vjp_fwd(zx, wht, interpret=False, block_t=1):
    t = zx.shape[0]
    zxp = _pad_time(zx, block_t)
    hs, cs = _bilstm_fwd_call(zxp, wht, interpret=interpret,
                              block_t=block_t)
    return hs[:t], (zxp, wht, hs, cs)


def _bilstm_vjp_bwd(interpret, block_t, res, gout):
    zxp, wht, hs, cs = res
    t = gout.shape[0]
    dzx, dwht = _bilstm_bwd_call(zxp, wht, hs, cs,
                                 _pad_time(gout.astype(jnp.float32),
                                           block_t),
                                 interpret=interpret, block_t=block_t)
    return dzx[:t].astype(zxp.dtype), dwht.astype(wht.dtype)


bilstm_recurrence.defvjp(_bilstm_vjp_fwd, _bilstm_vjp_bwd)


# ------------------------------------------------------------------- GRU
#
# Same sequential-grid/VMEM-carry structure as the LSTM pair, for the
# GRU cell (two recurrent gemms per step: the r/z gates and the
# r-gated candidate — GRUCell._step's math exactly, f32 like the cell).


def _gru_gates(zrz_t, zn_t, h, wrz_ref, wh_ref):
    hdim = h.shape[-1]
    rz = jax.nn.sigmoid(zrz_t + jnp.dot(
        h, wrz_ref, preferred_element_type=jnp.float32))
    r, z = rz[:, :hdim], rz[:, hdim:]
    n = jnp.tanh(zn_t + jnp.dot(
        r * h, wh_ref, preferred_element_type=jnp.float32))
    return r, z, n


def _gru_fwd_kernel(zrz_ref, zn_ref, wrz_ref, wh_ref, h_ref, h_scr):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    for tt in range(zrz_ref.shape[0]):   # static block_t timesteps
        for d in range(h_scr.shape[0]):
            h = h_scr[d]
            r, z, n = _gru_gates(zrz_ref[tt, d].astype(jnp.float32),
                                 zn_ref[tt, d].astype(jnp.float32),
                                 h, wrz_ref[d], wh_ref[d])
            h_new = (1.0 - z) * n + z * h
            h_scr[d] = h_new
            h_ref[tt, d] = h_new


def _gru_bwd_kernel(zrz_ref, zn_ref, hprev_ref, g_ref, wrz_ref, wh_ref,
                    dzrz_ref, dzn_ref, dwrz_ref, dwh_ref,
                    dh_scr, dwrz_scr, dwh_scr):
    """Reverse-time step: recompute r/z/n from the hoisted projections
    and h_{t-1} (pre-shifted), fold the carried dh and this step's
    output cotangent into dzrz_t/dzn_t, accumulate both weight grads."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        dwrz_scr[...] = jnp.zeros_like(dwrz_scr)
        dwh_scr[...] = jnp.zeros_like(dwh_scr)

    kt = zrz_ref.shape[0]
    for d in range(dh_scr.shape[0]):
        dzrzs, dns, hprevs, rhs = [], [], [], []
        for tt in reversed(range(kt)):   # reverse time WITHIN the block
            hprev = hprev_ref[tt, d]
            r, z, n = _gru_gates(zrz_ref[tt, d].astype(jnp.float32),
                                 zn_ref[tt, d].astype(jnp.float32),
                                 hprev, wrz_ref[d], wh_ref[d])
            dh_total = g_ref[tt, d] + dh_scr[d]
            dz = dh_total * (hprev - n)
            dn_pre = dh_total * (1.0 - z) * (1.0 - n * n)
            drh = jnp.dot(dn_pre, wh_ref[d].T,
                          preferred_element_type=jnp.float32)
            dr_pre = drh * hprev * r * (1.0 - r)
            dz_pre = dz * z * (1.0 - z)
            dzrz = jnp.concatenate([dr_pre, dz_pre], axis=-1)
            dzrz_ref[tt, d] = dzrz
            dzn_ref[tt, d] = dn_pre
            dh_scr[d] = (dh_total * z + drh * r
                         + jnp.dot(dzrz, wrz_ref[d].T,
                                   preferred_element_type=jnp.float32))
            dzrzs.append(dzrz)
            dns.append(dn_pre)
            hprevs.append(hprev)
            rhs.append(r * hprev)
        # both weight-grad gemms batch over the block (the serial chain
        # only constrains the dh carry above)
        cat = (lambda vs: vs[0] if kt == 1
               else jnp.concatenate(vs, axis=0))
        dwrz_scr[d] += jnp.dot(cat(hprevs).T, cat(dzrzs),
                               preferred_element_type=jnp.float32)
        dwh_scr[d] += jnp.dot(cat(rhs).T, cat(dns),
                              preferred_element_type=jnp.float32)
    dwrz_ref[...] = dwrz_scr[...]
    dwh_ref[...] = dwh_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret", "block_t"))
def _gru_fwd_call(zrz, zn, wrz, wh, interpret=False, block_t=1):
    t, nd, b, h2 = zrz.shape
    h = h2 // 2
    kt = block_t
    return pl.pallas_call(
        _gru_fwd_kernel,
        grid=(t // kt,),
        in_specs=[
            pl.BlockSpec((kt, nd, b, h2), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((kt, nd, b, h), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((nd, h, h2), lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((nd, h, h), lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((kt, nd, b, h), lambda i: (i, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=_out_struct((t, nd, b, h), jnp.float32, zrz, zn, wrz, wh),
        scratch_shapes=[pltpu.VMEM((nd, b, h), jnp.float32)],
        interpret=interpret,
    )(zrz, zn, wrz, wh)


@functools.partial(jax.jit, static_argnames=("interpret", "block_t"))
def _gru_bwd_call(zrz, zn, wrz, wh, hs, gout, interpret=False, block_t=1):
    t, nd, b, h2 = zrz.shape
    h = h2 // 2
    kt = block_t
    nblk = t // kt
    rev = lambda i: (nblk - 1 - i, 0, 0, 0)
    wspec2 = pl.BlockSpec((nd, h, h2), lambda i: (0, 0, 0),
                          memory_space=pltpu.VMEM)
    wspec1 = pl.BlockSpec((nd, h, h), lambda i: (0, 0, 0),
                          memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _gru_bwd_kernel,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((kt, nd, b, h2), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((kt, nd, b, h), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((kt, nd, b, h), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((kt, nd, b, h), rev, memory_space=pltpu.VMEM),
            wspec2,
            wspec1,
        ],
        out_specs=[
            pl.BlockSpec((kt, nd, b, h2), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((kt, nd, b, h), rev, memory_space=pltpu.VMEM),
            wspec2,
            wspec1,
        ],
        out_shape=[_out_struct(shape, jnp.float32, zrz, zn, wrz, wh, gout)
                   for shape in ((t, nd, b, h2), (t, nd, b, h),
                                 (nd, h, h2), (nd, h, h))],
        scratch_shapes=[pltpu.VMEM((nd, b, h), jnp.float32),
                        pltpu.VMEM((nd, h, h2), jnp.float32),
                        pltpu.VMEM((nd, h, h), jnp.float32)],
        interpret=interpret,
    )(zrz, zn, _shift_prev(hs), gout, wrz, wh)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def gru_recurrence(zrz, zn, wrz, wh, interpret=False, block_t=1):
    """GRU recurrence with VMEM-resident carry: zrz (T, D, B, 2H) and zn
    (T, D, B, H) hoisted input projections (+bias), wrz (D, H, 2H) and
    wh (D, H, H) recurrent weights, D directions in {1, 2}; returns the
    h stack (T, D, B, H) f32.  Same math as GRUCell._step under
    Recurrent's scan; backward recomputes the gates (residual = the h
    stack the forward writes anyway).  ``block_t`` > 1 = multi-timestep
    blocking (time axis zero-padded to a multiple, exact — _pad_time)."""
    t = zrz.shape[0]
    hs = _gru_fwd_call(_pad_time(zrz, block_t), _pad_time(zn, block_t),
                       wrz, wh, interpret=interpret, block_t=block_t)
    return hs[:t]


def _gru_vjp_fwd(zrz, zn, wrz, wh, interpret=False, block_t=1):
    t = zrz.shape[0]
    zrzp = _pad_time(zrz, block_t)
    znp = _pad_time(zn, block_t)
    hs = _gru_fwd_call(zrzp, znp, wrz, wh, interpret=interpret,
                       block_t=block_t)
    return hs[:t], (zrzp, znp, wrz, wh, hs)


def _gru_vjp_bwd(interpret, block_t, res, gout):
    zrzp, znp, wrz, wh, hs = res
    t = gout.shape[0]
    dzrz, dzn, dwrz, dwh = _gru_bwd_call(
        zrzp, znp, wrz, wh, hs,
        _pad_time(gout.astype(jnp.float32), block_t),
        interpret=interpret, block_t=block_t)
    return (dzrz[:t].astype(zrzp.dtype), dzn[:t].astype(znp.dtype),
            dwrz.astype(wrz.dtype), dwh.astype(wh.dtype))


gru_recurrence.defvjp(_gru_vjp_fwd, _gru_vjp_bwd)


# ------------------------------------------------------------ vanilla RNN
#
# h' = tanh(zx_t + h @ Wh) — the reference's own RnnCell (RNN.scala:28)
# through the same sequential-grid/VMEM-carry structure.  The backward
# needs no gate recompute at all: dz = dh_total * (1 - h_t^2) comes
# straight from the stored h stack.


def _rnn_fwd_kernel(zx_ref, wht_ref, h_ref, h_scr):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    for tt in range(zx_ref.shape[0]):    # static block_t timesteps
        for d in range(h_scr.shape[0]):
            z = zx_ref[tt, d].astype(jnp.float32) + jnp.dot(
                h_scr[d].astype(wht_ref.dtype), wht_ref[d],
                preferred_element_type=jnp.float32)
            h_new = jnp.tanh(z)
            h_scr[d] = h_new
            h_ref[tt, d] = h_new


def _rnn_bwd_kernel(h_ref, hprev_ref, g_ref, wht_ref, dzx_ref, dwh_ref,
                    dh_scr, dwh_scr):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        dwh_scr[...] = jnp.zeros_like(dwh_scr)

    kt = h_ref.shape[0]
    for d in range(dh_scr.shape[0]):
        dzs, hprevs = [], []
        for tt in reversed(range(kt)):   # reverse time WITHIN the block
            h_t = h_ref[tt, d]
            dz = (g_ref[tt, d] + dh_scr[d]) * (1.0 - h_t * h_t)
            dzx_ref[tt, d] = dz
            dh_scr[d] = jnp.dot(dz.astype(wht_ref.dtype), wht_ref[d].T,
                                preferred_element_type=jnp.float32)
            dzs.append(dz)
            hprevs.append(hprev_ref[tt, d])
        cat = (lambda vs: vs[0] if kt == 1
               else jnp.concatenate(vs, axis=0))
        # dWh batches over the block: ONE (H, kt*B) x (kt*B, H) gemm
        dwh_scr[d] += jnp.dot(cat(hprevs).T, cat(dzs),
                              preferred_element_type=jnp.float32)
    dwh_ref[...] = dwh_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret", "block_t"))
def _rnn_fwd_call(zx, wht, interpret=False, block_t=1):
    t, nd, b, h = zx.shape
    kt = block_t
    return pl.pallas_call(
        _rnn_fwd_kernel,
        grid=(t // kt,),
        in_specs=[
            pl.BlockSpec((kt, nd, b, h), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((nd, h, h), lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((kt, nd, b, h), lambda i: (i, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=_out_struct((t, nd, b, h), jnp.float32, zx, wht),
        scratch_shapes=[pltpu.VMEM((nd, b, h), jnp.float32)],
        interpret=interpret,
    )(zx, wht)


@functools.partial(jax.jit, static_argnames=("interpret", "block_t"))
def _rnn_bwd_call(wht, hs, gout, interpret=False, block_t=1):
    t, nd, b, h = hs.shape
    kt = block_t
    nblk = t // kt
    rev = lambda i: (nblk - 1 - i, 0, 0, 0)
    return pl.pallas_call(
        _rnn_bwd_kernel,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((kt, nd, b, h), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((kt, nd, b, h), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((kt, nd, b, h), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((nd, h, h), lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((kt, nd, b, h), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((nd, h, h), lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[_out_struct((t, nd, b, h), jnp.float32, wht, hs, gout),
                   _out_struct((nd, h, h), jnp.float32, wht, hs, gout)],
        scratch_shapes=[pltpu.VMEM((nd, b, h), jnp.float32),
                        pltpu.VMEM((nd, h, h), jnp.float32)],
        interpret=interpret,
    )(hs, _shift_prev(hs), gout, wht)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def rnn_recurrence(zx, wht, interpret=False, block_t=1):
    """Vanilla tanh-RNN recurrence with VMEM-resident carry: zx
    (T, D, B, H) hoisted input projection (+both biases), wht (D, H, H)
    recurrent weights, D directions in {1, 2}; returns the h stack
    (T, D, B, H) f32.  Same math as RnnCell._step with the default Tanh
    under Recurrent's scan.  ``block_t`` > 1 = multi-timestep blocking
    (time axis zero-padded to a multiple, exact — _pad_time)."""
    t = zx.shape[0]
    hs = _rnn_fwd_call(_pad_time(zx, block_t), wht, interpret=interpret,
                       block_t=block_t)
    return hs[:t]


def _rnn_vjp_fwd(zx, wht, interpret=False, block_t=1):
    t = zx.shape[0]
    hs = _rnn_fwd_call(_pad_time(zx, block_t), wht, interpret=interpret,
                       block_t=block_t)
    return hs[:t], (wht, hs)


def _rnn_vjp_bwd(interpret, block_t, res, gout):
    wht, hs = res
    t = gout.shape[0]
    dzx, dwht = _rnn_bwd_call(wht, hs,
                              _pad_time(gout.astype(jnp.float32), block_t),
                              interpret=interpret, block_t=block_t)
    return dzx[:t].astype(jnp.float32), dwht.astype(wht.dtype)


rnn_recurrence.defvjp(_rnn_vjp_fwd, _rnn_vjp_bwd)


# ------------------------------------------- Mosaic window maxpool (r6)
#
# Round-6 re-litigation of the round-3 pool rejections (ISSUE 2
# tentpole a) with the round-5 kernel skills.  What is different from
# the round-3 stride-1 kernel (deleted; PERF_NOTES round 3):
#
#   * layout: channels ride the 128-lane dim (NHWC inside the kernel, W
#     on sublanes) — Inception pools carry C=64..832, so the lanes are
#     full where the round-3 NCHW kernel padded W=7..28 up to 128
#     (its measured 4.6-18x bandwidth waste);
#   * strides: the H stride lives in the grid's block index maps and
#     the W stride in a phase-folded lane layout ((W/s, s*C) — phase r
#     = lane block r*C..(r+1)*C), so every in-kernel window tap is a
#     unit-stride sublane/lane slice — no strided slices and no
#     in-kernel reshape, the two Mosaic blockers round 3 hit;
#   * the forward stores the window ARGMAX (int32 tap index) and the
#     backward is a scatter-free gather over it: one read of (g,
#     argmax) per tap position instead of select_and_scatter's
#     compare-and-route over x.  Tie rule: FIRST max in row-major
#     window order — bit-identical to XLA select_and_scatter;
#   * VMEM-resident: each grid step owns BH output rows; the input rows
#     it shares with the next block arrive via a second (halo)
#     BlockSpec on the same operand, so worst-case read amplification
#     is 2x (vs kh/s_h x for a naive row-per-step grid).
#
# Adoption is gated on a device-clock A/B (nn/pooling.py _PALLAS_POOL,
# default OFF): every previous pool formulation lost to the XLA
# emitter on v5e (PERF_NOTES rounds 2-5), and this one must buy its
# place the same way.


def _mosaic_pool_geom(h, w, window, strides, pads):
    """Static geometry: output sizes, output-row block, padded frames."""
    kh, kw = window
    sh, sw = strides
    (plh, phh), (plw, phw) = pads
    oh = (h + plh + phh - kh) // sh + 1
    ow = (w + plw + phw - kw) // sw + 1
    bh = max(-(-kh // sh), 8)        # output rows per grid step
    nblk = -(-oh // bh)
    hp = (nblk + 1) * sh * bh        # main blocks + one halo block
    wq = ow + (kw - 1) // sw         # phase-folded sublane extent
    return oh, ow, bh, nblk, hp, wq


def _mosaic_mp_fwd_body(xm_ref, xh_ref, y_ref, a_ref, *, kh, kw, sh, sw,
                        c):
    bh = y_ref.shape[1]
    ow = y_ref.shape[2]
    xall = jnp.concatenate([xm_ref[0], xh_ref[0]],
                           axis=0).astype(jnp.float32)
    for lr in range(bh):             # static output rows in this block
        best, arg = None, None
        for i in range(kh):
            row = xall[sh * lr + i]  # (wq, sw*c) — static row index
            for j in range(kw):
                # phase fold: column s_w*ow + j = (sublane ow + j//s_w,
                # lane block j%s_w) — both unit-stride slices
                tap = lax.slice(row, (j // sw, (j % sw) * c),
                                (j // sw + ow, (j % sw) * c + c))
                if best is None:
                    best = tap
                    arg = jnp.zeros(tap.shape, jnp.int32)
                else:
                    m = tap > best   # strict >: FIRST max wins ties
                    best = jnp.where(m, tap, best)
                    arg = jnp.where(m, i * kw + j, arg)
        y_ref[0, lr] = best.astype(y_ref.dtype)
        if a_ref is not None:
            a_ref[0, lr] = arg


def _mosaic_mp_fwd_kernel(xm_ref, xh_ref, y_ref, a_ref, **kw_):
    _mosaic_mp_fwd_body(xm_ref, xh_ref, y_ref, a_ref, **kw_)


def _mosaic_mp_fwd_kernel_primal(xm_ref, xh_ref, y_ref, **kw_):
    _mosaic_mp_fwd_body(xm_ref, xh_ref, y_ref, None, **kw_)


def _mosaic_mp_bwd_kernel(gp_ref, ap_ref, gm_ref, am_ref, dx_ref, acc_ref,
                          *, kh, kw, sh, sw, c, bh, nblk):
    """Scatter-free gather: dx row-block <- sum over the stored argmax
    of the two g/a row-blocks whose windows can reach it (previous +
    main — the blocking guarantees no window spans further).  The f32
    accumulator is a VMEM scratch updated through static ref slices:
    Mosaic has no lowering for a value-level ``.at[].add``."""
    blk = pl.program_id(1)
    bi = dx_ref.shape[1]             # s_h * bh input rows per step
    ow = gm_ref.shape[2]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    # the prev spec clamps blk-1 to 0 and the main spec clamps blk to
    # nblk-1: a clamped (duplicate) block must contribute nothing
    valid = ((blk > 0).astype(jnp.float32),
             (blk < nblk).astype(jnp.float32))
    for b, (g_ref, a_ref) in enumerate(((gp_ref, ap_ref),
                                        (gm_ref, am_ref))):
        for lr in range(bh):
            g_row, a_row = None, None
            for i in range(kh):
                # input row (static): s_h*oh + i relative to this block
                hloc = sh * lr + i + sh * bh * (b - 1)
                if not 0 <= hloc < bi:
                    continue
                if g_row is None:    # load lazily: edge rows skip taps
                    g_row = g_ref[0, lr].astype(jnp.float32) * valid[b]
                    a_row = a_ref[0, lr]
                for j in range(kw):
                    contrib = g_row * (a_row == (i * kw + j)
                                       ).astype(jnp.float32)
                    acc_ref[hloc, j // sw:j // sw + ow,
                            (j % sw) * c:(j % sw) * c + c] += contrib
    dx_ref[0] = acc_ref[...].astype(dx_ref.dtype)


def _mosaic_mp_pack(x, window, strides, pads, fill):
    """NCHW -> the kernel's phase-folded NHWC frame (N, Hp, Wq, s_w*C),
    padded with ``fill`` (-inf for x, 0 for g — zero-padded cotangents
    make every out-of-range contribution vanish)."""
    n, c, h, w = x.shape
    (plh, _), (plw, _) = pads
    oh, ow, bh_, nblk, hp, wq = _mosaic_pool_geom(
        h, w, window, strides, pads)
    sw = strides[1]
    tw = wq * sw
    xt = jnp.transpose(x, (0, 2, 3, 1))
    xt = jnp.pad(xt, ((0, 0), (plh, max(0, hp - h - plh)),
                      (plw, max(0, tw - w - plw)), (0, 0)),
                 constant_values=fill)[:, :hp, :tw]
    return xt.reshape(n, hp, wq, sw * c)


@functools.partial(jax.jit, static_argnames=("window", "strides", "pads",
                                             "interpret", "with_argmax"))
def _mosaic_mp_fwd_call(x, window, strides, pads, interpret=False,
                        with_argmax=True):
    n, c, h, w = x.shape
    kh, kw = window
    sh, sw = strides
    oh, ow, bh, nblk, hp, wq = _mosaic_pool_geom(h, w, window, strides,
                                                 pads)
    xr = _mosaic_mp_pack(x, window, strides, pads, -jnp.inf)
    xspec = pl.BlockSpec((1, sh * bh, wq, sw * c),
                         lambda nn_, b: (nn_, b, 0, 0),
                         memory_space=pltpu.VMEM)
    halo = pl.BlockSpec((1, sh * bh, wq, sw * c),
                        lambda nn_, b: (nn_, b + 1, 0, 0),
                        memory_space=pltpu.VMEM)
    ospec = pl.BlockSpec((1, bh, ow, c), lambda nn_, b: (nn_, b, 0, 0),
                         memory_space=pltpu.VMEM)
    oshape = _out_struct((n, nblk * bh, ow, c), x.dtype, x)
    ashape = _out_struct((n, nblk * bh, ow, c), jnp.int32, x)
    body = functools.partial(
        _mosaic_mp_fwd_kernel if with_argmax
        else _mosaic_mp_fwd_kernel_primal,
        kh=kh, kw=kw, sh=sh, sw=sw, c=c)
    out = pl.pallas_call(
        body,
        grid=(n, nblk),
        in_specs=[xspec, halo],
        out_specs=[ospec, ospec] if with_argmax else ospec,
        out_shape=[oshape, ashape] if with_argmax else oshape,
        interpret=interpret,
    )(xr, xr)
    if with_argmax:
        yp, a = out
    else:
        yp, a = out, None
    y = jnp.transpose(yp[:, :oh], (0, 3, 1, 2))  # (N, C, OH, OW)
    return (y, a) if with_argmax else y


@functools.partial(jax.jit, static_argnames=("window", "strides", "pads",
                                             "xshape", "interpret"))
def _mosaic_mp_bwd_call(a, g, window, strides, pads, xshape,
                        interpret=False):
    n, c, h, w = xshape
    kh, kw = window
    sh, sw = strides
    (plh, _), (plw, _) = pads
    oh, ow, bh, nblk, hp, wq = _mosaic_pool_geom(h, w, window, strides,
                                                 pads)
    # cotangent into the padded output-row frame (zeros beyond OH)
    gt = jnp.transpose(g, (0, 2, 3, 1))
    gt = jnp.pad(gt, ((0, 0), (0, nblk * bh - oh), (0, 0), (0, 0)))
    prev = lambda nn_, b: (nn_, jnp.maximum(b - 1, 0), 0, 0)
    main = lambda nn_, b: (nn_, jnp.minimum(b, nblk - 1), 0, 0)
    gspec_p = pl.BlockSpec((1, bh, ow, c), prev, memory_space=pltpu.VMEM)
    gspec_m = pl.BlockSpec((1, bh, ow, c), main, memory_space=pltpu.VMEM)
    dspec = pl.BlockSpec((1, sh * bh, wq, sw * c),
                         lambda nn_, b: (nn_, b, 0, 0),
                         memory_space=pltpu.VMEM)
    dxp = pl.pallas_call(
        functools.partial(_mosaic_mp_bwd_kernel, kh=kh, kw=kw, sh=sh,
                          sw=sw, c=c, bh=bh, nblk=nblk),
        grid=(n, nblk + 1),
        in_specs=[gspec_p, gspec_p, gspec_m, gspec_m],
        out_specs=dspec,
        out_shape=_out_struct((n, hp, wq, sw * c), g.dtype, a, g),
        scratch_shapes=[pltpu.VMEM((sh * bh, wq, sw * c), jnp.float32)],
        interpret=interpret,
    )(gt, a, gt, a)
    # unfold phases, drop padding, back to NCHW
    dxw = dxp.reshape(n, hp, wq * sw, c)
    dxw = jnp.pad(dxw, ((0, 0), (0, 0),
                        (0, max(0, plw + w - wq * sw)), (0, 0)))
    dx = dxw[:, plh:plh + h, plw:plw + w]
    return jnp.transpose(dx, (0, 3, 1, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _mosaic_maxpool(x, window, strides, pads, xshape, interpret):
    return _mosaic_mp_fwd_call(x, window, strides, pads, interpret,
                               with_argmax=False)


def _mosaic_mp_vjp_fwd(x, window, strides, pads, xshape, interpret):
    y, a = _mosaic_mp_fwd_call(x, window, strides, pads, interpret,
                               with_argmax=True)
    return y, a                      # argmax is the ONLY residual


def _mosaic_mp_vjp_bwd(window, strides, pads, xshape, interpret, a, g):
    return (_mosaic_mp_bwd_call(a, g, window, strides, pads, xshape,
                                interpret),)


_mosaic_maxpool.defvjp(_mosaic_mp_vjp_fwd, _mosaic_mp_vjp_bwd)


def mosaic_maxpool2d(x, window, strides, pads, interpret=False):
    """NCHW maxpool through the round-6 Mosaic kernel pair: argmax-
    storing forward + scatter-free gather backward (replacing
    select_and_scatter).  ``window``/``strides`` any sizes (overlapping
    or not), ``pads`` = ((lo_h, hi_h), (lo_w, hi_w)) explicit.  Gradient
    tie rule: first max in row-major window order == XLA
    select_and_scatter.  A no-grad forward skips the argmax writes."""
    return _mosaic_maxpool(x, tuple(window), tuple(strides),
                           (tuple(pads[0]), tuple(pads[1])),
                           tuple(x.shape), interpret)


# ---------------------------------------------------------------------------
# Paged attention: in-kernel page-table walk + online softmax (round 7).
#
# The decode hot path (`models/transformer.py _lm_forward_window`)
# materializes a per-row gathered K/V view `kpool[li][ptab]` in HBM —
# and under int8 KV runs a separate `kvq.dequantize_view` pass — before
# plain-XLA attention.  This kernel is the vLLM PagedAttention design
# (Kwon et al., SOSP 2023) fused with FlashAttention streaming (Dao et
# al., 2022): the grid's innermost dimension IS the page walk, the
# slot→page table rides scalar prefetch so each page's BlockSpec index
# map resolves `phys = ptab[b, p]` before the DMA is issued (Mosaic
# double-buffers the HBM→VMEM page stream for free), and the softmax is
# the online running-max/denominator form so no (B, n_view) score or
# dequantized K/V tensor ever exists in HBM.  The int8 variant folds
# `kvq.dequantize_view` (q.astype(f32) * scale[..., None], scales
# indexed by the SAME phys coordinates as quant/kv.py) into the QK and
# PV loops.  A multi-query S = k+1 window is the same kernel — that is
# the speculative verify pass (`_PALLAS_SPEC_VERIFY`).
#
# Adoption gate (PR-2 discipline): default OFF via
# `models/transformer.py _PALLAS_PAGED_ATTN / _PALLAS_SPEC_VERIFY`; no
# chip verdict yet (ROADMAP C3 gives it) → the staged A/B is
# `tools/bench_serve.py --decode-sweep --attn-kernel`.  Equivalence
# vs the gathered-view reference is pinned in interpreter mode by
# tests/test_paged_attention.py.
# ---------------------------------------------------------------------------


def _paged_attn_kernel(ptab_ref, *refs, page_size, n_heads, scale,
                       quantized):
    """One (batch row b, page p) grid step, every head.

    Page p's K/V block (and scale rows when quantized) land in VMEM via
    the scalar-prefetch index map; scratch carries the flash-attention
    running state (m: row max, l: denominator, acc: unnormalized PV)
    across the sequential page walk.  Page 0 always holds position 0 and
    `pos >= 0`, so m is finite from the first page and the
    `exp(-inf - finite) = 0` identities keep the recurrence exact for
    fully-masked later pages (reserved-but-unwritten tail pages).

    Heads ride the lane dim (the caller flattens (H, hd) -> H*hd), so a
    head is a static lane slice — lane-tile aligned when hd % 128 == 0.
    """
    if quantized:
        (pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
         m_ref, l_ref, acc_ref) = refs
    else:
        pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        ks_ref = vs_ref = None
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    ws = q_ref.shape[1]
    hd = q_ref.shape[2] // n_heads
    t = p * page_size + lax.broadcasted_iota(jnp.int32, (ws, page_size), 1)
    live = t <= pos_ref[0]                             # pos column (S, 1)
    for h in range(n_heads):                           # static head loop
        lanes = slice(h * hd, (h + 1) * hd)
        q = q_ref[0, :, lanes].astype(jnp.float32)     # (S, hd)
        k = k_ref[0, :, lanes].astype(jnp.float32)     # (page_size, hd)
        v = v_ref[0, :, lanes].astype(jnp.float32)
        if quantized:
            # kvq.dequantize_view fused in-loop: int8 * per-(page-row,
            # head) scale, indexed by the same phys page the K/V DMA used.
            k = k * ks_ref[0, :, h:h + 1]
            v = v * vs_ref[0, :, h:h + 1]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        s = jnp.where(live, s, -jnp.inf)
        m_prev = m_ref[h]                              # (S, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        w = jnp.exp(s - m_new)                         # (S, page_size)
        l_ref[h] = l_ref[h] * alpha + w.sum(axis=1, keepdims=True)
        acc_ref[:, lanes] = acc_ref[:, lanes] * alpha + lax.dot_general(
            w, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(p == pl.num_programs(1) - 1)
    def _finish():
        for h in range(n_heads):
            lanes = slice(h * hd, (h + 1) * hd)
            o_ref[0, :, lanes] = acc_ref[:, lanes] / l_ref[h]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_attention_call(q, kpool, vpool, ptab, pos, kscale, vscale,
                          interpret):
    bsz, ws, n_heads, hd = q.shape
    n_pages, page_size = kpool.shape[:2]
    d = n_heads * hd
    quantized = kscale is not None
    # Mosaic's tiling rule: a block's last two dims are multiples of
    # (8, 128) or equal the array's.  Flattening (H, hd) -> H*hd (free:
    # the pool is contiguous) makes every block below a whole
    # (rows, H*hd) slab of its array, legal at any page size, window
    # and head geometry; pos rides as a (S, 1) column for the same reason.
    rowspec = pl.BlockSpec((1, ws, d), lambda b, p, pt: (b, 0, 0))
    kvspec = pl.BlockSpec((1, page_size, d),
                          lambda b, p, pt: (pt[b, p], 0, 0))
    sspec = pl.BlockSpec((1, page_size, n_heads),
                         lambda b, p, pt: (pt[b, p], 0, 0))
    in_specs = [
        pl.BlockSpec((1, ws, 1), lambda b, p, pt: (b, 0, 0)),       # pos
        rowspec, kvspec, kvspec,
    ]
    operands = [pos.astype(jnp.int32).reshape(bsz, ws, 1),
                q.reshape(bsz, ws, d),
                kpool.reshape(n_pages, page_size, d),
                vpool.reshape(n_pages, page_size, d)]
    if quantized:
        in_specs += [sspec, sspec]
        operands += [kscale, vscale]
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, page_size=page_size,
                          n_heads=n_heads, scale=1.0 / (hd ** 0.5),
                          quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, ptab.shape[1]),
            in_specs=in_specs,
            out_specs=rowspec,
            scratch_shapes=[pltpu.VMEM((n_heads, ws, 1), jnp.float32),
                            pltpu.VMEM((n_heads, ws, 1), jnp.float32),
                            pltpu.VMEM((ws, d), jnp.float32)]),
        out_shape=_out_struct((bsz, ws, d), jnp.float32, *operands),
        interpret=interpret,
    )(ptab.astype(jnp.int32), *operands)
    return out.reshape(bsz, ws, n_heads, hd)


def paged_attention(q, kpool, vpool, ptab, pos, kscale=None, vscale=None,
                    interpret=None):
    """Causal paged attention over a page-pooled KV cache, one layer.

    ``q`` (B, S, H, hd) f32 queries at absolute positions ``pos``
    (B, S) int32; ``kpool``/``vpool`` (n_pages, page_size, H, hd) the
    layer's physical page pool (f32/f16 slabs or int8 with
    ``kscale``/``vscale`` (n_pages, page_size, H) per-row/per-head
    scales from quant/kv.py); ``ptab`` (B, P) int32 the slot→page
    table.  Logical position t of row b lives at
    ``pool[ptab[b, t // page_size], t % page_size]``; keys with
    ``t <= pos`` attend (the gathered-view reference's causal mask).
    Rows whose window entry is dead must be masked by the CALLER (the
    decode step gates on ``valid`` downstream) — the kernel computes
    every (b, s) row.  Returns (B, S, H, hd) f32.
    """
    if interpret is None:
        interpret = not _on_tpu()
    return _paged_attention_call(q, kpool, vpool, ptab, pos, kscale,
                                 vscale, interpret)


def paged_spec_verify(q, kpool, vpool, ptab, pos, kscale=None, vscale=None,
                      interpret=None):
    """Speculative (k+1)-window verify pass: ``paged_attention`` with a
    multi-query window S = k+1 (draft tokens verified in one shot).  The
    window positions ``pos[:, j]`` are consecutive per row, so the page
    walk streams each page ONCE for all k+1 queries instead of rerunning
    gathered-view attention per window — the `_PALLAS_SPEC_VERIFY` hot
    path.  Same contract as ``paged_attention``.
    """
    if interpret is None:
        interpret = not _on_tpu()
    return _paged_attention_call(q, kpool, vpool, ptab, pos, kscale,
                                 vscale, interpret)

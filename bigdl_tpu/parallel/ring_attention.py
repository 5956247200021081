"""Ring attention — sequence-parallel exact attention over a mesh axis.

The reference's only sequence machinery is a serial truncated-BPTT loop
(Recurrent.scala, SURVEY.md §5.7 — no attention, no context parallelism).
For a TPU-native framework, long-context is first-class: this module
implements blockwise ring attention (Liu et al. ring-attention pattern):

- Q/K/V are sharded over a ``seq`` mesh axis: each device holds a
  contiguous sequence block of length T/P.
- Each device computes blockwise attention against its local K/V block,
  then rotates K/V around the ring with ``lax.ppermute`` (P-1 hops over
  ICI), maintaining a numerically-stable online softmax (running max m and
  normalizer l), so the full T x T attention is exact while HBM holds only
  T/P-sized blocks and communication overlaps compute around the ring.

``ring_attention`` is the shard_map-able collective function;
``ring_self_attention`` wraps it under a Mesh for (B, T, H, D) inputs.
Causal masking uses global block offsets derived from ``axis_index``.
"""
from __future__ import annotations

import contextlib
import contextvars
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map

from bigdl_tpu.nn.containers import kept
from bigdl_tpu.parallel.collectives import pvary
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _block_attn(q, k, v, q_off, k_off, causal, scale):
    """Scores for one (q-block, k-block) pair with online-softmax stats.

    q: (B, Tq, H, D), k/v: (B, Tk, H, D).  Returns (s_max, p_sum, pv)
    where p = exp(s - s_max) and masking is applied pre-softmax.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        qi = q_off + jnp.arange(tq)[:, None]
        ki = k_off + jnp.arange(tk)[None, :]
        s = jnp.where(qi >= ki, s, -jnp.inf)
    # the running max is a numerical shift only — softmax is invariant to
    # it, so it must be fully non-differentiable or the shift's gradient
    # paths (here vs the alpha/beta rescales in the ring step) would have
    # to cancel exactly; stop_gradient everywhere makes the grad exact
    m = lax.stop_gradient(s.max(axis=-1))       # (B, H, Tq)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)      # fully-masked rows stay 0
    l = p.sum(axis=-1)                          # (B, H, Tq)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    return m, l, pv


def ring_attention(q, k, v, axis_name: str = "seq", causal: bool = False):
    """Collective ring attention: call inside shard_map with q/k/v sequence-
    sharded over ``axis_name``.  Shapes per device: (B, T_local, H, D)."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    t_local = q.shape[1]
    q_off = idx * t_local

    fwd = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, hop):
        k_blk, v_blk, m, l, o = carry
        # k-block currently held came from rank (idx - hop) mod n
        src = (idx - hop) % n
        bm, bl, bpv = _block_attn(q, k_blk, v_blk, q_off, src * t_local,
                                  causal, scale)
        m_new = jnp.maximum(m, bm)
        alpha = jnp.exp(m - m_new)          # rescale old accumulators
        beta = jnp.exp(bm - m_new)          # rescale new block
        l = l * alpha + bl * beta
        o = o * alpha.transpose(0, 2, 1)[..., None] \
            + bpv * beta.transpose(0, 2, 1)[..., None]
        k_blk = lax.ppermute(k_blk, axis_name, fwd)
        v_blk = lax.ppermute(v_blk, axis_name, fwd)
        return (k_blk, v_blk, m_new, l, o), None

    b, _, h, d = q.shape
    # pvary: initial accumulators must carry the same varying type as the
    # operands (the ring axis, plus a batch axis under hybrid dp x sp)
    vary_axes = tuple(jax.typeof(q).vma or (axis_name,))
    m0 = pvary(jnp.full((b, h, t_local), -jnp.inf, jnp.float32), vary_axes)
    l0 = pvary(jnp.zeros((b, h, t_local), jnp.float32), vary_axes)
    o0 = pvary(jnp.zeros((b, t_local, h, d), jnp.float32), vary_axes)
    (k_f, v_f, m, l, o), _ = lax.scan(
        step, (k, v, m0, l0, o0), jnp.arange(n))
    l = jnp.maximum(l, 1e-20)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_self_attention(q, k, v, mesh: Mesh, axis_name: str = "seq",
                        causal: bool = False, batch_axis: str = None):
    """Host-level wrapper: shard (B, T, H, D) over ``axis_name`` and run the
    ring.  The jitted result composes with surrounding pjit computation.

    ``batch_axis``: also shard the batch dim (hybrid dp x sp) — each
    data-parallel group runs its own seq ring; without it a mesh that
    HAS a data axis would replicate (all-gather) the batch into every
    data slice."""
    spec = P(batch_axis, axis_name)
    f = shard_map(
        partial(ring_attention, axis_name=axis_name, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return f(q, k, v)


def full_attention(q, k, v, causal: bool = False):
    """Single-device reference implementation (for tests)."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# blockwise causal attention on one device: never holds T x T, and a window
# layer's work grows with T x W
# ---------------------------------------------------------------------------

def _pair_scores(q_blk, k_blk, q_off, k_off, window, scale):
    """Masked scores of one (query block, key block) pair with grouped
    heads.  q_blk: (B, Tq, Hk, G, D), k_blk: (B, Tk, Hk, D) -> float32
    (B, Hk, G, Tq, Tk), -inf where query i may not see key j (j > i, or
    j <= i - window)."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk,
                   preferred_element_type=jnp.float32) * scale
    qi = q_off + jnp.arange(q_blk.shape[1])[:, None]
    ki = k_off + jnp.arange(k_blk.shape[1])[None, :]
    seen = qi >= ki
    if window is not None:
        seen = seen & (qi - ki < window)
    return jnp.where(seen, s, -jnp.inf)


def _first_key_block(i, block, window):
    """The first key block that any query of query block ``i`` sees."""
    if window is None:
        return jnp.zeros((), jnp.int32)
    return jnp.maximum(i * block - (window - 1), 0) // block


def _blockwise_fwd(q, k, v, window, block):
    """q: (B, T, Hk, G, D), k: (B, T, Hk, D), v: (B, T, Hk, Dv), T a
    multiple of ``block``.  Returns (out (B, T, Hk, G, Dv) in float32,
    logsumexp (B, Hk, G, T))."""
    b, t, hk, g, d = q.shape
    dv = v.shape[-1]
    scale = 1.0 / (d ** 0.5)
    n = t // block

    def q_block(_, i):
        q_blk = lax.dynamic_slice_in_dim(q, i * block, block, axis=1)

        def k_block(j, carry):
            m, l, acc = carry
            k_blk = lax.dynamic_slice_in_dim(k, j * block, block, axis=1)
            v_blk = lax.dynamic_slice_in_dim(v, j * block, block, axis=1)
            s = _pair_scores(q_blk, k_blk, i * block, j * block, window,
                             scale)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # a window's first block may hold no key that a late query
            # of the block sees: its row is all -inf until a later block
            safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - safe[..., None])
            alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - safe, -jnp.inf))
            l = l * alpha + p.sum(axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v.dtype), v_blk,
                            preferred_element_type=jnp.float32)
            return m_new, l, acc * alpha[..., None] + pv

        m0 = jnp.full((b, hk, g, block), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, hk, g, block), jnp.float32)
        acc0 = jnp.zeros((b, hk, g, block, dv), jnp.float32)
        m, l, acc = lax.fori_loop(_first_key_block(i, block, window), i + 1,
                                  k_block, (m0, l0, acc0))
        out = acc / l[..., None]
        return None, (out, m + jnp.log(l))

    _, (out, lse) = lax.scan(q_block, None, jnp.arange(n))
    # (n, B, Hk, G, block, Dv) -> (B, T, Hk, G, Dv)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, t, hk, g, dv)
    lse = lse.transpose(1, 2, 3, 0, 4).reshape(b, hk, g, t)
    return out, lse


# What the backward's walk may fill of a v5e core's 128 MiB of VMEM; the
# rest is the operands' blocks'.  The chip's compiler decides what lives
# there; tests/test_chip_compile.py pins its decision at the cells' shapes.
_WALK_VMEM_BYTES = 112 << 20


def _walk_plan(b, t, block, hk, g, d, dv, window):
    """How wide the backward walks, from the operands' shapes alone: the
    key heads of one pass are the most (a divisor of ``hk``) whose float32
    accumulators fit ``_WALK_VMEM_BYTES`` beside one pair's score-sized
    arrays (as compiled, one in float32 and two in the operands' dtype are
    live at a time: 8 bytes a score).  The accumulators are dq's block,
    which the inner loop carries, and the pass's whole dk and dv, which it
    adds into by slice; where not even one head's dk and dv fit (a long
    sequence), dq's block alone is sized to fit and the slices go through
    HBM."""
    scores = b * g * block * block * 8
    dq_blk = b * block * g * d * 4
    dkv = b * t * (d + dv) * 4
    resident = scores + dq_blk + dkv <= _WALK_VMEM_BYTES
    fits = max(_WALK_VMEM_BYTES // (scores + dq_blk + dkv * resident), 1)
    heads = max(h for h in range(1, hk + 1) if hk % h == 0 and h <= fits)
    pairs = sum(i + 1 - (0 if window is None else
                         max(i * block - (window - 1), 0) // block)
                for i in range(t // block))     # _first_key_block's
    return {"heads_a_pass": heads * g, "key_heads_a_pass": heads,
            "passes": hk // heads, "pairs_a_pass": pairs,
            "carried": "dq", "carry_bytes": heads * dq_blk,
            "sliced_bytes": heads * dkv, "sliced_in_vmem": resident,
            # read and written at every pair of a pass
            "slice_bytes_a_pair": 2 * heads * b * block * (d + dv) * 4}


_walk_report = contextvars.ContextVar("attention_walk", default=None)


@contextlib.contextmanager
def walk_report():
    """The plans (``_walk_plan``) of the cores whose backward is traced
    inside the block, in the order traced (the optimizer's step logs them
    as its ``attention_walk`` event)."""
    cores = []
    token = _walk_report.set(cores)
    try:
        yield cores
    finally:
        _walk_report.reset(token)


@partial(jax.jit, static_argnums=(6, 7, 8))
def _blockwise_bwd(q, k, v, out, lse, dout, window, block, hp):
    """Every visible (query block, key block) pair once, the probabilities
    back from the saved logsumexp, ``hp`` key heads at a time
    (``_walk_plan``): narrow enough that the float32 accumulators stay in
    VMEM from a pass's first pair to its last.  dq is complete after a
    query block's inner loop, which carries it; dk and dv of the pass's
    heads are added into at the key block's rows.  Jitted, so that the
    three nested loops are traced once for all the cores of a step that
    share their shapes, and not again by the next ``optimize()`` call's
    step: a step's text inlines it."""
    b, t, hk, g, d = q.shape
    scale = 1.0 / (d ** 0.5)
    n = t // block
    delta = jnp.sum(dout * out, axis=-1).transpose(0, 2, 3, 1)  # float32
    cd = q.dtype
    dout = dout.astype(cd)

    def heads_from(_, h0):
        # block ``at`` of the sequence and the pass's heads in one slice:
        # no copy of a group's operands is ever made
        def cut(a, at, rows, heads):
            start, size = [0] * a.ndim, list(a.shape)
            start[rows], size[rows] = at * block, block
            start[heads], size[heads] = h0, hp
            return lax.dynamic_slice(a, start, size)

        def q_block(carry, i):
            q_blk, do_blk = cut(q, i, 1, 2), cut(dout, i, 1, 2)
            lse_blk, delta_blk = cut(lse, i, 3, 1), cut(delta, i, 3, 1)

            def k_block(j, inner):
                dq_blk, dk, dv = inner
                k_blk, v_blk = cut(k, j, 1, 2), cut(v, j, 1, 2)
                s = _pair_scores(q_blk, k_blk, i * block, j * block, window,
                                 scale)
                p = jnp.exp(s - lse_blk[..., None])
                dp = jnp.einsum("bqhgd,bkhd->bhgqk", do_blk, v_blk,
                                preferred_element_type=jnp.float32)
                ds = (p * (dp - delta_blk[..., None]) * scale).astype(cd)
                dv_j = jnp.einsum("bhgqk,bqhgd->bkhd", p.astype(cd), do_blk,
                                  preferred_element_type=jnp.float32)
                dk_j = jnp.einsum("bhgqk,bqhgd->bkhd", ds, q_blk,
                                  preferred_element_type=jnp.float32)
                dq_blk = dq_blk + jnp.einsum(
                    "bhgqk,bkhd->bqhgd", ds, k_blk,
                    preferred_element_type=jnp.float32)
                add = lambda full, part: lax.dynamic_update_slice_in_dim(
                    full, lax.dynamic_slice_in_dim(full, j * block, block, 1)
                    + part, j * block, axis=1)
                return dq_blk, add(dk, dk_j), add(dv, dv_j)

            dq0 = jnp.zeros((b, block, hp, g, d), jnp.float32)
            dq_blk, dk, dv = lax.fori_loop(
                _first_key_block(i, block, window), i + 1, k_block,
                (dq0,) + carry)
            return (dk, dv), dq_blk

        (dk, dv), dq = lax.scan(
            q_block, tuple(jnp.zeros((b, t, hp, a.shape[-1]), jnp.float32)
                           for a in (k, v)), jnp.arange(n))
        return None, (dq, dk, dv)

    # a loop of the program's, so that the step holds one pass's text
    _, (dq, dk, dv) = lax.scan(heads_from, None, jnp.arange(0, hk, hp))
    # (passes, n, B, block, heads, G, D) and (passes, B, T, heads, D)
    dq = dq.transpose(2, 1, 3, 0, 4, 5, 6).reshape(q.shape)
    dk, dv = (jnp.moveaxis(a, 0, 2).reshape(b, t, hk, -1) for a in (dk, dv))
    return dq, dk, dv


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _blockwise(q, k, v, window, block):
    return _blockwise_fwd(q, k, v, window, block)[0]


def _blockwise_vjp_fwd(q, k, v, window, block):
    out, lse = _blockwise_fwd(q, k, v, window, block)
    # a block loop to make, 1/T of its scores to hold: a Recompute around
    # the layer keeps them, and its recomputation runs no loop
    out, lse = kept(out, "attention_out"), kept(lse, "attention_lse")
    return out, (q, k, v, out, lse)


def _blockwise_vjp_bwd(window, block, res, dout):
    q, k, v, out, lse = res
    b, t, hk, g, d = q.shape
    plan = _walk_plan(b, t, block, hk, g, d, v.shape[-1], window)
    report = _walk_report.get()
    if report is not None:
        report.append(plan)
    dq, dk, dv = _blockwise_bwd(q, k, v, out, lse, dout, window, block,
                                plan["key_heads_a_pass"])
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_blockwise.defvjp(_blockwise_vjp_fwd, _blockwise_vjp_bwd)


def blockwise_attention(q, k, v, window=None, block=512):
    """Causal attention with grouped heads, block by block with an online
    softmax: no (T, T) array exists, forward or backward, and key blocks
    that no query of a block may see are skipped, so a window layer costs
    T x (window + block) and a full one T x T / 2.

    q: (B, T, Hq, D); k: (B, T, Hk, D); v: (B, T, Hk, Dv) with Hq a
    multiple of Hk (query head h reads key head h // (Hq // Hk)) and Dv a
    size of its own (a latent-attention head is 192 wide for the scores and
    128 for the values); the scores are scaled by 1/sqrt(D).  ``window``:
    query i sees keys i - window < j <= i (None: every j <= i).  Operands
    multiply in their own dtype and accumulate in float32; the softmax
    statistics are float32.  Returns float32 (B, T, Hq, Dv); the gradients
    come back in the operands' own shapes."""
    b, t, hq, d = q.shape
    hk, dv = k.shape[2], v.shape[3]
    block = min(block, t)
    pad = -t % block
    if pad:
        # padded keys lie after every real query; padded queries are cut
        widen = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q, k, v = widen(q), widen(k), widen(v)
    out = _blockwise(q.reshape(b, t + pad, hk, hq // hk, d), k, v, window,
                     block)
    return out.reshape(b, t + pad, hq, dv)[:, :t]

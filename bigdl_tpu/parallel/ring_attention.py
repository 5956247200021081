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

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map

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

"""Multi-head self-attention as a model-zoo module.

Absent in the reference (its only sequence machinery is the serial
truncated-BPTT Recurrent loop, SURVEY.md §5.7); first-class here because
long-context attention is the workload sequence/context parallelism
exists for.  The layer has TWO execution paths with identical math:

- single-device: full softmax attention (``parallel.ring_attention.
  full_attention``);
- sequence-parallel: when the trainer sets ``ctx.seq_mesh``
  (``DistriOptimizer(sequence_parallel=True)``), attention runs as the
  EXACT blockwise ring collective (``ring_self_attention``) — Q/K/V
  sequence blocks stay on their devices, K/V rotate around the ``seq``
  ring over ICI with an online softmax, and the batch dim rides a
  ``data`` axis when the mesh has one.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import TensorModule
from bigdl_tpu.nn import init as init_
from bigdl_tpu.nn.linear import dot32
from bigdl_tpu.nn.normalization import rms_norm
from bigdl_tpu.tensor import policy


class MultiHeadSelfAttention(TensorModule):
    """(B, T, D) -> (B, T, D) multi-head self-attention.

    Params: in-projections ``wq/wk/wv`` and out-projection ``wo`` (all
    (D, D)) with biases.  ``causal=True`` applies the autoregressive
    mask (identically in both execution paths).
    """

    #: quantized-serving declaration (bigdl_tpu/quant/weights.py): the
    #: projections multiply as x @ W, so the OUTPUT channels are the
    #: columns (axis 1) and inputs the rows (axis 0) — the transpose of
    #: Linear's layout.  Biases stay fp32.
    quant_spec = {"wq": (1, 0), "wk": (1, 0), "wv": (1, 0),
                  "wo": (1, 0)}

    def __init__(self, d_model: int, n_heads: int, causal: bool = False):
        super().__init__()
        if d_model % n_heads:
            raise ValueError(f"d_model ({d_model}) must divide by "
                             f"n_heads ({n_heads})")
        self.d_model = d_model
        self.n_heads = n_heads
        self.causal = causal
        self.reset()

    def reset(self):
        d = self.d_model
        for name in ("wq", "wk", "wv", "wo"):
            self._add_param(name, init_.default_linear((d, d), d))
            self._add_param(name.replace("w", "b"),
                            np.zeros((d,), np.float32))
        return self

    def _forward(self, P, x, S, ctx):
        from bigdl_tpu.parallel.ring_attention import (full_attention,
                                                       ring_self_attention)
        p = policy()
        b, t, d = x.shape
        h = self.n_heads
        hd = d // h

        def proj(w, bias):
            # stay in the policy compute dtype THROUGH the attention core:
            # the (B,H,T,T) score/probability tensors are pure bandwidth
            # (measured 22.8 -> ~14 ms/step on the bs32 T512 d512 L6
            # encoder, PERF_NOTES round 4), and the QK/AV contractions ride
            # the MXU at bf16 rate; softmax stats stay f32 inside
            # full_attention/ring_attention
            y = jnp.matmul(p.cast_compute(x), p.cast_compute(w))
            return (y + jnp.asarray(bias, y.dtype)).reshape(b, t, h, hd)

        q = proj(P["wq"], P["bq"])
        k = proj(P["wk"], P["bk"])
        v = proj(P["wv"], P["bv"])
        if ctx.seq_mesh is not None:
            batch_axis = ("data" if "data" in ctx.seq_mesh.axis_names
                          else None)
            o = ring_self_attention(q, k, v, ctx.seq_mesh, ctx.seq_axis,
                                    causal=self.causal,
                                    batch_axis=batch_axis)
        else:
            o = full_attention(q, k, v, causal=self.causal)
        o = o.reshape(b, t, d)
        y = jnp.matmul(p.cast_compute(o), p.cast_compute(P["wo"]))
        return y.astype(p.output_dtype) + P["bo"], None

    def __repr__(self):
        return (f"MultiHeadSelfAttention({self.d_model}, heads="
                f"{self.n_heads}{', causal' if self.causal else ''})")


class SinusoidalPositionalEncoding(TensorModule):
    """x + PE[:T] with the standard sin/cos table (parameter-free).

    No reference counterpart (its sequence order comes from recurrence);
    needed by the attention-family LM, whose attention is permutation-
    equivariant without it.  The table is built from the STATIC (T, D)
    of the traced input, so jit sees a constant."""

    def __init__(self, d_model: int, base: float = 10000.0):
        super().__init__()
        self.d_model = d_model
        self.base = base

    def table(self, t: int) -> np.ndarray:
        """The (t, d_model) sin/cos table — shared with the KV-cached
        decoder (models/transformer.lm_decode), which must add the exact
        same positions the training forward added."""
        d = self.d_model
        ang = np.arange(t)[:, None] * np.exp(
            np.arange(0, d, 2) * (-np.log(self.base) / d))
        pe = np.zeros((t, d), np.float32)
        pe[:, 0::2] = np.sin(ang)
        pe[:, 1::2] = np.cos(ang[:, :d // 2])
        return pe

    def _forward(self, P, x, S, ctx):
        t, d = x.shape[1], x.shape[2]
        if d != self.d_model:
            raise ValueError(f"input dim {d} != d_model {self.d_model}")
        return x + jnp.asarray(self.table(t), x.dtype), None

    def __repr__(self):
        return f"SinusoidalPositionalEncoding({self.d_model})"


def rotary(x, base: float = 10000.0):
    """Rotary positions on (B, T, H, D), position t = the index along T:
    the two halves of D rotate as pairs (i, i + D/2) by t * base^(-2i/D),
    the rotate-half convention.  float32."""
    t, d = x.shape[1], x.shape[-1]
    freq = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


class GroupedQueryAttention(TensorModule):
    """(B, T, D) -> (B, T, D) causal self-attention with grouped heads
    (``n_heads`` query heads share ``n_kv_heads`` key/value heads), an
    RMSNorm on every head's query and key (one weight vector each, shared
    by the heads), and bias-free projections.

    ``window``: query i sees keys i - window < j <= i (None: every key up
    to i).  ``rotary_base``: rotary positions on q and k, after their
    norms (None: no positions).  The core is ``parallel.ring_attention.
    blockwise_attention``: no (T, T) array, and a window layer skips the
    key blocks outside its window."""

    quant_spec = {"wq": (1, 0), "wk": (1, 0), "wv": (1, 0), "wo": (1, 0)}
    gated = False       # GatedGroupedQueryAttention: a sigmoid output gate

    def __init__(self, d_model: int, n_heads: int, n_kv_heads: int,
                 head_dim: int, window: int = None,
                 rotary_base: float = None, eps: float = 1e-5):
        super().__init__()
        if n_heads % n_kv_heads:
            raise ValueError(f"n_heads ({n_heads}) must divide by "
                             f"n_kv_heads ({n_kv_heads})")
        self.d_model = d_model
        self.n_heads = n_heads
        self.n_kv_heads = n_kv_heads
        self.head_dim = head_dim
        self.window = window
        self.rotary_base = rotary_base
        self.eps = eps
        self.block = 512        # rows of a query block and of a key block
        self.reset()

    def reset(self):
        d, hd = self.d_model, self.head_dim
        q_out, kv_out = self.n_heads * hd, self.n_kv_heads * hd
        shapes = [("wq", (d, q_out)), ("wk", (d, kv_out)),
                  ("wv", (d, kv_out))]
        if self.gated:
            shapes.append(("wg", (d, q_out)))
        for name, shape in shapes + [("wo", (q_out, d))]:
            self._add_param(name, init_.normal_on_device(shape))
        self._add_param("q_norm", np.ones((hd,), np.float32))
        self._add_param("k_norm", np.ones((hd,), np.float32))
        return self

    def _forward(self, P, x, S, ctx):
        from bigdl_tpu.parallel.ring_attention import blockwise_attention
        b, t, _ = x.shape
        hd = self.head_dim
        q = dot32(x, P["wq"]).reshape(b, t, self.n_heads, hd)
        k = dot32(x, P["wk"]).reshape(b, t, self.n_kv_heads, hd)
        v = dot32(x, P["wv"]).reshape(b, t, self.n_kv_heads, hd)
        q = rms_norm(q, P["q_norm"], self.eps)
        k = rms_norm(k, P["k_norm"], self.eps)
        if self.rotary_base is not None:
            q, k = rotary(q, self.rotary_base), rotary(k, self.rotary_base)
        cc = policy().cast_compute
        core = ("FullAttentionCore" if self.window is None
                else "WindowAttentionCore")
        with jax.named_scope(core):
            o = blockwise_attention(cc(q), cc(k), cc(v), self.window,
                                    self.block)
        o = o.reshape(b, t, -1)
        if self.gated:
            o = o * jax.nn.sigmoid(dot32(x, P["wg"]))
        return dot32(o, P["wo"]), None

    def __repr__(self):
        kind = "full" if self.window is None else f"window={self.window}"
        return (f"{type(self).__name__}({self.d_model}, heads="
                f"{self.n_heads}/{self.n_kv_heads}x{self.head_dim}, {kind})")


class GatedGroupedQueryAttention(GroupedQueryAttention):
    """:class:`GroupedQueryAttention` with a sigmoid gate on the joined
    heads before the output projection (``wg``, after ``wv``): the afmoe
    family's attention."""

    quant_spec = dict(GroupedQueryAttention.quant_spec, wg=(1, 0))
    gated = True


def rotary_interleaved(x, base: float = 10000.0):
    """Rotary positions on (B, T, H, D) whose pairs are neighbours: (2i,
    2i + 1) rotate by t * base^(-2i/D), position t = the index along T.
    Returns the rotated pairs with their first members in the first half of
    D and their second members in the second ([x'_0, x'_2, .. | x'_1,
    x'_3, ..]): the same fixed permutation of D for every caller, so a dot
    product of two results is that of the rotated vectors in their own
    order, and no pair is woven back: in that order the pairs are
    :func:`rotary`'s (i, i + D/2).  float32."""
    return rotary(jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1),
                  base)


class LatentAttention(TensorModule):
    """(B, T, D) -> (B, T, D) causal multi-head latent attention (the
    deepseek_v3 family's, without query compression): keys and values come
    out of one ``kv_lora_rank``-wide latent per token, a head's query and
    key are ``qk_nope_head_dim`` + ``qk_rope_head_dim`` wide, only the
    second part takes rotary positions (neighbouring pairs), the rotary key
    is made once per token and shared by every head, and a head's value is
    ``v_head_dim`` wide.  Bias-free, no gate, no q/k norms.

        q = x Wq                    -> heads of [q_nope | q_pe]
        x Wkv_a                     -> [c | k_pe];  c' = RMSNorm(c)
        c' Wkv_b                    -> heads of [k_nope | v]
        q_h = [q_nope_h | rot(q_pe_h)],  k_h = [k_nope_h | rot(k_pe)]
        out = concat_h(softmax(q_h . k_h / sqrt(nope + rope), causal) v_h) Wo

    The core is ``parallel.ring_attention.blockwise_attention`` (no (T, T)
    array), which takes the value head size from ``v``.  **The shared
    rotary key reaches it broadcast to every head**, joined to ``k_nope``
    in the compute dtype: one more (B, T, heads, rope) array a layer (67 MB
    in bfloat16 at 16,384 tokens x 32 heads x 64) against a second pair of
    operands through the core's loops and its backward; the broadcast's
    transpose sums the heads' gradients back into the one key."""

    quant_spec = {"wq": (1, 0), "wkv_a": (1, 0), "wkv_b": (1, 0),
                  "wo": (1, 0)}

    def __init__(self, d_model: int, n_heads: int, kv_lora_rank: int,
                 qk_nope_head_dim: int, qk_rope_head_dim: int,
                 v_head_dim: int, rotary_base: float = 10000.0,
                 eps: float = 1e-6):
        super().__init__()
        if qk_rope_head_dim % 2:
            raise ValueError(f"qk_rope_head_dim ({qk_rope_head_dim}) must "
                             "be even: rotary turns pairs")
        self.d_model = d_model
        self.n_heads = n_heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rotary_base = rotary_base
        self.eps = eps
        self.block = 512        # rows of a query block and of a key block
        self.reset()

    def reset(self):
        d, h, r = self.d_model, self.n_heads, self.kv_lora_rank
        nope, rope, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
        self._add_param("wq", init_.normal_on_device((d, h * (nope + rope))))
        self._add_param("wkv_a", init_.normal_on_device((d, r + rope)))
        self._add_param("kv_norm", np.ones((r,), np.float32))
        self._add_param("wkv_b", init_.normal_on_device((r, h * (nope + dv))))
        self._add_param("wo", init_.normal_on_device((h * dv, d)))
        return self

    def _forward(self, P, x, S, ctx):
        from bigdl_tpu.parallel.ring_attention import blockwise_attention
        b, t, _ = x.shape
        h, r, nope = self.n_heads, self.kv_lora_rank, self.qk_nope_head_dim
        cc = policy().cast_compute
        q = dot32(x, P["wq"]).reshape(b, t, h, -1)
        with jax.named_scope("LatentKV"):
            latent = dot32(x, P["wkv_a"])
            c = rms_norm(latent[..., :r], P["kv_norm"], self.eps)
            kv = dot32(c, P["wkv_b"]).reshape(b, t, h, -1)
            k_pe = rotary_interleaved(latent[..., None, r:],
                                      self.rotary_base)
            q_pe = rotary_interleaved(q[..., nope:], self.rotary_base)
            q = jnp.concatenate([cc(q[..., :nope]), cc(q_pe)], axis=-1)
            k = jnp.concatenate(
                [cc(kv[..., :nope]),
                 jnp.broadcast_to(cc(k_pe), (b, t, h, k_pe.shape[-1]))],
                axis=-1)
            v = cc(kv[..., nope:])
        with jax.named_scope("FullAttentionCore"):
            o = blockwise_attention(q, k, v, None, self.block)
        return dot32(o.reshape(b, t, -1), P["wo"]), None

    def __repr__(self):
        return (f"LatentAttention({self.d_model}, heads={self.n_heads}x"
                f"({self.qk_nope_head_dim}+{self.qk_rope_head_dim})/"
                f"{self.v_head_dim}, latent={self.kv_lora_rank})")

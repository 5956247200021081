"""Linear-algebra layers (SURVEY.md §2.3 "Linear-algebra layers"):
Linear, Bilinear, CMul, CAdd, Mul, Add, MulConstant, AddConstant, MM, MV,
Cosine, Euclidean, LookupTable.

Matmuls go through one dot chokepoint (``_dot``) with the bf16 compute
policy — the TPU-native equivalent of the reference's single-gemm design
(DenseTensorBLAS.gemm, DenseTensorBLAS.scala:70 → MKL vsgemm mkl.c:408),
where every layer funnels into one tuned kernel.  Here the kernel is the
MXU via XLA dot_general.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import Container, TensorModule, Module
from bigdl_tpu.nn import init as init_
from bigdl_tpu.tensor import policy
from bigdl_tpu.utils.table import Table


def _dot(a, b):
    """Single matmul chokepoint: cast per dtype policy (bf16 feeds the MXU;
    accumulation is f32 inside the MXU), output cast back."""
    p = policy()
    return jnp.matmul(p.cast_compute(a), p.cast_compute(b)).astype(p.output_dtype)


def dot32(a, b):
    """The chokepoint with a float32 result: operands in the policy's
    compute dtype, the accumulator handed back unrounded (``_dot`` rounds
    a bf16 product to bf16 before widening it)."""
    p = policy()
    return jnp.matmul(p.cast_compute(a), p.cast_compute(b),
                      preferred_element_type=jnp.float32)


class Linear(TensorModule):
    """y = x W^T + b (ref Linear.scala:~40, gemm path :103-136)."""

    #: quantized-serving declaration (bigdl_tpu/quant/weights.py):
    #: param name -> (output-channel axis, input-channel axis) of the
    #: leaf.  weight is (out, in).
    quant_spec = {"weight": (0, 1)}

    def __init__(self, input_size: int, output_size: int, with_bias: bool = True,
                 init_method: str = init_.Default):
        super().__init__()
        self.input_size = input_size
        self.output_size = output_size
        self.with_bias = with_bias
        self.init_method = init_method
        self.reset()

    def reset(self):
        if self.init_method == init_.Xavier:
            w = init_.xavier((self.output_size, self.input_size),
                             self.input_size, self.output_size)
            b = np.zeros((self.output_size,), np.float32)
        else:
            w = init_.default_linear((self.output_size, self.input_size),
                                     self.input_size)
            b = init_.default_linear((self.output_size,), self.input_size)
        self._add_param("weight", w)
        if self.with_bias:
            self._add_param("bias", b)
        return self

    def _forward(self, P, x, S, ctx):
        y = _dot(x, P["weight"].T)
        if self.with_bias:
            y = y + P["bias"]
        return y, None

    def __repr__(self):
        return f"Linear({self.input_size} -> {self.output_size})"


class GatedLinearUnit(TensorModule):
    """SwiGLU feed-forward, bias-free: (…, D) -> (…, D),
    ``(silu(x Wg) * (x Wu)) Wd`` with ``Wg``, ``Wu`` (D, H) and ``Wd``
    (H, D).  No counterpart in the reference."""

    def __init__(self, d_model: int, hidden: int):
        super().__init__()
        self.d_model = d_model
        self.hidden = hidden
        self.reset()

    def reset(self):
        d, h = self.d_model, self.hidden
        for name, shape in (("w_gate", (d, h)), ("w_up", (d, h)),
                            ("w_down", (h, d))):
            self._add_param(name, init_.normal_on_device(shape))
        return self

    def _forward(self, P, x, S, ctx):
        return swiglu(x, P["w_gate"], P["w_up"], P["w_down"]), None

    def __repr__(self):
        return f"GatedLinearUnit({self.d_model}, hidden={self.hidden})"


def swiglu(x, w_gate, w_up, w_down):
    return dot32(jax.nn.silu(dot32(x, w_gate)) * dot32(x, w_up), w_down)


class Bilinear(TensorModule):
    """y_k = x1^T W_k x2 + b_k over a Table(x1, x2) (ref Bilinear.scala)."""

    def __init__(self, input_size1: int, input_size2: int, output_size: int,
                 bias_res: bool = True):
        super().__init__()
        self.input_size1 = input_size1
        self.input_size2 = input_size2
        self.output_size = output_size
        self.bias_res = bias_res
        self.reset()

    def reset(self):
        stdv = 1.0 / np.sqrt(self.input_size1)
        self._add_param("weight", init_.uniform(
            (self.output_size, self.input_size1, self.input_size2), -stdv, stdv))
        if self.bias_res:
            self._add_param("bias", init_.uniform((self.output_size,), -stdv, stdv))
        return self

    def _forward(self, P, x, S, ctx):
        x1, x2 = x[1], x[2]
        # (n,i1) x (o,i1,i2) x (n,i2) -> (n,o)
        y = jnp.einsum("ni,oij,nj->no", x1, P["weight"], x2)
        if self.bias_res:
            y = y + P["bias"]
        return y, None


class CMul(TensorModule):
    """Learnable per-element scale, broadcast over batch (ref CMul.scala)."""

    def __init__(self, size):
        super().__init__()
        self.size = tuple(size)
        self.reset()

    def reset(self):
        n = int(np.prod(self.size))
        stdv = 1.0 / np.sqrt(n)
        self._add_param("weight", init_.uniform(self.size, -stdv, stdv))
        return self

    def _forward(self, P, x, S, ctx):
        return x * P["weight"], None


class CAdd(TensorModule):
    """Learnable per-element bias (ref CAdd.scala)."""

    def __init__(self, size):
        super().__init__()
        self.size = tuple(size)
        self.reset()

    def reset(self):
        n = int(np.prod(self.size))
        stdv = 1.0 / np.sqrt(n)
        self._add_param("bias", init_.uniform(self.size, -stdv, stdv))
        return self

    def _forward(self, P, x, S, ctx):
        return x + P["bias"], None


class Mul(TensorModule):
    """Single learnable scalar gain (ref Mul.scala)."""

    def __init__(self):
        super().__init__()
        self.reset()

    def reset(self):
        self._add_param("weight", init_.uniform((1,), -1.0, 1.0))
        return self

    def _forward(self, P, x, S, ctx):
        return x * P["weight"][0], None


class Add(TensorModule):
    """Learnable bias vector of ``input_size`` (ref Add.scala)."""

    def __init__(self, input_size: int, scalar: bool = False):
        super().__init__()
        self.input_size = 1 if scalar else input_size
        self.scalar = scalar
        self.reset()

    def reset(self):
        stdv = 1.0 / np.sqrt(self.input_size)
        self._add_param("bias", init_.uniform((self.input_size,), -stdv, stdv))
        return self

    def _forward(self, P, x, S, ctx):
        b = P["bias"]
        return (x + b[0], None) if self.scalar else (x + b, None)


class MulConstant(TensorModule):
    def __init__(self, scalar: float, inplace: bool = False):
        super().__init__()
        self.scalar = scalar

    def _forward(self, P, x, S, ctx):
        return x * self.scalar, None


class AddConstant(TensorModule):
    def __init__(self, constant_scalar: float, inplace: bool = False):
        super().__init__()
        self.constant_scalar = constant_scalar

    def _forward(self, P, x, S, ctx):
        return x + self.constant_scalar, None


class MM(Module):
    """Batch/plain matmul of Table(a, b) (ref MM.scala)."""

    def __init__(self, trans_a: bool = False, trans_b: bool = False):
        super().__init__()
        self.trans_a = trans_a
        self.trans_b = trans_b

    def _forward(self, P, x, S, ctx):
        a, b = x[1], x[2]
        if self.trans_a:
            a = jnp.swapaxes(a, -1, -2)
        if self.trans_b:
            b = jnp.swapaxes(b, -1, -2)
        return _dot(a, b), None


class MV(Module):
    """Matrix-vector product of Table(mat, vec), batched (ref MV.scala)."""

    def __init__(self, trans: bool = False):
        super().__init__()
        self.trans = trans

    def _forward(self, P, x, S, ctx):
        m, v = x[1], x[2]
        if self.trans:
            m = jnp.swapaxes(m, -1, -2)
        return jnp.einsum("...ij,...j->...i", m, v), None


class Cosine(TensorModule):
    """Cosine similarity to each of ``output_size`` learned prototypes
    (ref Cosine.scala)."""

    def __init__(self, input_size: int, output_size: int):
        super().__init__()
        self.input_size = input_size
        self.output_size = output_size
        self.reset()

    def reset(self):
        stdv = 1.0 / np.sqrt(self.input_size)
        self._add_param("weight", init_.uniform(
            (self.output_size, self.input_size), -stdv, stdv))
        return self

    def _forward(self, P, x, S, ctx):
        w = P["weight"]
        xn = x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-12)
        wn = w / (jnp.linalg.norm(w, axis=-1, keepdims=True) + 1e-12)
        return _dot(xn, wn.T), None


class Euclidean(TensorModule):
    """Euclidean distance to each learned prototype (ref Euclidean.scala)."""

    def __init__(self, input_size: int, output_size: int, fast_backward: bool = True):
        super().__init__()
        self.input_size = input_size
        self.output_size = output_size
        self.reset()

    def reset(self):
        stdv = 1.0 / np.sqrt(self.input_size)
        self._add_param("weight", init_.uniform(
            (self.input_size, self.output_size), -stdv, stdv))
        return self

    def _forward(self, P, x, S, ctx):
        w = P["weight"]  # (in, out)
        diff = x[..., :, None] - w[None, :, :]
        return jnp.linalg.norm(diff, axis=-2), None


class LookupTable(TensorModule):
    """Embedding lookup with optional max-norm renorm
    (ref LookupTable.scala:273).  Indices are 1-based, like Torch."""

    def __init__(self, n_index: int, n_output: int, padding_value: float = 0,
                 max_norm: float = None, norm_type: float = 2.0,
                 should_scale_grad_by_freq: bool = False,
                 init_std: float = None):
        super().__init__()
        self.n_index = n_index
        self.n_output = n_output
        self.padding_value = padding_value
        self.max_norm = max_norm
        self.norm_type = norm_type
        self.init_std = init_std
        self.reset()

    def reset(self):
        shape = (self.n_index, self.n_output)
        # Torch's N(0, 1) from the host stream, or N(0, init_std) drawn on
        # the device (a language model's table is large)
        self._add_param("weight", init_.normal(shape, 0, 1)
                        if self.init_std is None
                        else init_.normal_on_device(shape, self.init_std))
        return self

    def _forward(self, P, x, S, ctx):
        w = P["weight"]
        if self.max_norm is not None:
            norms = jnp.linalg.norm(w, ord=self.norm_type, axis=1, keepdims=True)
            scale = jnp.minimum(1.0, self.max_norm / (norms + 1e-7))
            w = w * scale
        idx = jnp.asarray(x, jnp.int32) - 1  # 1-based -> 0-based
        return jnp.take(w, idx, axis=0), None


class LmHead(TensorModule):
    """Untied, bias-free output head of a language model: (…, D) ->
    (…, V) log-probabilities, ``log_softmax(x W)`` with ``W`` (D, V); the
    logits and the softmax are float32."""

    def __init__(self, d_model: int, vocab_size: int):
        super().__init__()
        self.d_model = d_model
        self.vocab_size = vocab_size
        self.reset()

    def reset(self):
        self._add_param("weight", init_.normal_on_device(
            (self.d_model, self.vocab_size)))
        return self

    def _forward(self, P, x, S, ctx):
        return jax.nn.log_softmax(dot32(x, P["weight"]), axis=-1), None

    def __repr__(self):
        return f"LmHead({self.d_model} -> {self.vocab_size})"


class TiedLmHead(Container):
    """A language model whose output head is its embedding, transposed:
    (B, T) 1-based token ids -> (B, T, V) log-probabilities,
    ``log_softmax(body(E[ids]) E^T)``.  The one table ``weight`` (V, D) is
    this container's own parameter and ``body`` (hidden states to hidden
    states, the final norm included) its one child, so ``params()`` holds
    the table once: its gradient is the sum of the lookup's and the head's,
    and an optimizer keeps one state for it.  The lookup traces under the
    scope ``LookupTable`` and the head's product and softmax (float32
    logits) under ``LmHead``, as the untied modules' do."""

    def __init__(self, vocab_size: int, d_model: int, body: Module,
                 init_std: float = init_.LM_INIT_STD):
        super().__init__(body)
        self.vocab_size = vocab_size
        self.d_model = d_model
        self.init_std = init_std
        self._reset_table()

    def _reset_table(self):
        self._add_param("weight", init_.normal_on_device(
            (self.vocab_size, self.d_model), self.init_std))

    def reset(self):
        super().reset()
        self._reset_table()
        return self

    def apply(self, params, x, state, ctx):
        from bigdl_tpu.nn.containers import _child_apply
        table = params["~"]["weight"]
        with jax.named_scope("LookupTable"):
            h = jnp.take(table, jnp.asarray(x, jnp.int32) - 1, axis=0)
        h, body_state = _child_apply(self, 0, params, h, state, ctx)
        with jax.named_scope("LmHead"):
            logp = jax.nn.log_softmax(dot32(h, table.T), axis=-1)
        return logp, dict(state, **{"0": body_state})

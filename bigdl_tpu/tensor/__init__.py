"""Tensor substrate: dtype policy + Torch-semantics helpers over jnp.

The reference's 6.5k-LoC tensor package (tensor/Tensor.scala, DenseTensor,
DenseTensorMath, DenseTensorBLAS, TensorNumeric) dissolves into jnp arrays +
XLA.  What remains (per SURVEY.md §7 item 1) is:

- a dtype policy (the ``TensorNumeric[T]`` role: reference supports
  Float/Double, Tensor.scala:605; TPU-native default is float32 with a
  bfloat16 compute policy for the MXU);
- the handful of Torch-shape helpers the module API needs
  (narrow/select/view semantics).
"""
from __future__ import annotations

import jax.numpy as jnp

_DEFAULT_DTYPE = jnp.float32


def default_dtype():
    return _DEFAULT_DTYPE


def set_default_dtype(dtype):
    global _DEFAULT_DTYPE
    _DEFAULT_DTYPE = jnp.dtype(dtype)
    return _DEFAULT_DTYPE


class DTypePolicy:
    """Mixed-precision policy: params in ``param_dtype``, matmuls/convs in
    ``compute_dtype`` (bf16 feeds the MXU at full rate), accumulation/output
    in ``output_dtype``.  The reference's FP16 *wire* compression
    (parameters/FP16CompressedTensor.scala) becomes this compute policy —
    on TPU the cast happens on-chip, not on the network."""

    def __init__(self, param_dtype=jnp.float32, compute_dtype=jnp.float32,
                 output_dtype=jnp.float32):
        self.param_dtype = jnp.dtype(param_dtype)
        self.compute_dtype = jnp.dtype(compute_dtype)
        self.output_dtype = jnp.dtype(output_dtype)

    def cast_compute(self, x):
        return jnp.asarray(x, self.compute_dtype)

    def cast_output(self, x):
        return jnp.asarray(x, self.output_dtype)

    def narrows(self, x) -> bool:
        """Whether a bandwidth-bound operator (pool, LRN, a norm's apply)
        runs ``x`` in ``compute_dtype``: only a float32 array, only while a
        reduced-precision policy is active.  A float64 input under FP32 is
        never cut down and a bfloat16 one is never widened.  The caller
        casts, so the cast is booked to the operator's own file."""
        return (self.compute_dtype != jnp.float32
                and x.dtype == jnp.float32)


FP32 = DTypePolicy()
BF16_COMPUTE = DTypePolicy(compute_dtype=jnp.bfloat16)
# Full bf16 activation flow: conv/matmul OUTPUTS stay bf16, so every
# downstream buffer (pool windows, ReLU, BN apply, concat, LAYOUT copies)
# moves half the HBM bytes.  Params, gradients, BN statistics and the
# loss stay f32 (BN accumulates in f32 explicitly; LogSoftMax upcasts).
BF16_ACT = DTypePolicy(compute_dtype=jnp.bfloat16, output_dtype=jnp.bfloat16)

_POLICY = FP32


def policy() -> DTypePolicy:
    return _POLICY


def set_policy(p: DTypePolicy):
    global _POLICY
    _POLICY = p
    return p


# -- Torch-shape helpers (ref Tensor.scala narrow/select) -----------------

def narrow(x, dim: int, index: int, size: int):
    """Slice ``size`` elements along ``dim`` starting at 1-based ``index``."""
    start = index - 1
    sl = [slice(None)] * x.ndim
    sl[dim - 1] = slice(start, start + size)
    return x[tuple(sl)]


def select(x, dim: int, index: int):
    """Select 1-based ``index`` along 1-based ``dim``, dropping the dim."""
    sl = [slice(None)] * x.ndim
    sl[dim - 1] = index - 1
    return x[tuple(sl)]

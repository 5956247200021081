"""bigdl_tpu — a TPU-native deep learning framework.

A ground-up reimplementation of the capabilities of Intel BigDL v0.1.0
(reference: MikeTam1021/BigDL) designed for TPU hardware:

- Compute path: JAX/XLA (jnp ops compile onto the MXU; Pallas for custom
  kernels).  The reference's native MKL/JNI layer (native/mkl/src/main/c/jni/
  mkl.c) dissolves into XLA-compiled kernels.
- Module system: Torch-style ergonomics (`forward`/`backward`/`parameters`)
  over a pure functional core (`apply(params, input, state, ctx)`) so the
  same model object works eagerly AND under `jax.jit`/`pjit`.
- Distributed: `jax.sharding.Mesh` + collectives over ICI replace the
  reference's Spark BlockManager parameter all-reduce
  (parameters/AllReduceParameter.scala).

Package layout (mirrors the reference's package inventory, SURVEY.md §2):
  nn/        layer + criterion inventory  (ref: dl/.../bigdl/nn)
  tensor/    dtype policy + tensor helpers (ref: dl/.../bigdl/tensor)
  dataset/   DataSet/Transformer/Sample    (ref: dl/.../bigdl/dataset)
  optim/     Optimizer/OptimMethod/Trigger (ref: dl/.../bigdl/optim)
  parallel/  mesh, collectives, sharded training (ref: dl/.../bigdl/parameters)
  models/    LeNet/VGG/Inception/ResNet/... (ref: dl/.../bigdl/models)
  utils/     Engine, Table, File, RandomGenerator (ref: dl/.../bigdl/utils)
"""

__version__ = "0.1.0"

import os as _os

if _os.environ.get("BIGDL_CPU_MESH"):
    # virtual N-device CPU mesh for sharding tests without TPU hardware
    # (the reference's local-SparkContext multi-node test trick, SURVEY.md
    # §4; set by scripts/bigdl_tpu.sh --cpu-mesh N).  Must run before the
    # first backend touch; a no-op with a warning if jax already started.
    try:
        import jax as _jax
        _jax.config.update("jax_platforms", "cpu")
        _jax.config.update("jax_num_cpu_devices",
                           int(_os.environ["BIGDL_CPU_MESH"]))
    except (RuntimeError, ValueError) as _e:
        # backend already initialized, or a non-integer value
        import warnings as _warnings
        _warnings.warn(f"BIGDL_CPU_MESH ignored: {_e}")

from bigdl_tpu.utils.table import Table, T  # noqa: F401
from bigdl_tpu.utils.engine import Engine  # noqa: F401

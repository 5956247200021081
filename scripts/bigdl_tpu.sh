#!/usr/bin/env bash
# Env-pinning wrapper (the scripts/bigdl.sh role, ref scripts/bigdl.sh:
# exports the mandatory MKL envs and wraps any command).  The TPU-native
# equivalents: topology pins for the Engine, XLA compile cache, and the
# virtual CPU-mesh switch used for sharding tests on non-TPU hosts.
#
#   ./scripts/bigdl_tpu.sh [-n nodes] [-c cores] [--cpu-mesh N] -- cmd args...
#
# Examples:
#   ./scripts/bigdl_tpu.sh -- python examples/train_lenet.py -b 128
#   ./scripts/bigdl_tpu.sh --cpu-mesh 8 -- python -m pytest tests/test_distributed.py
set -euo pipefail

CPU_MESH=""
while [[ $# -gt 0 ]]; do
  case "$1" in
    -n) export BIGDL_NODE_NUMBER="$2"; shift 2 ;;
    -c) export BIGDL_CORE_NUMBER="$2"; shift 2 ;;
    --cpu-mesh) CPU_MESH="$2"; shift 2 ;;
    --) shift; break ;;
    *) echo "unknown flag $1 (use -n/-c/--cpu-mesh/--)" >&2; exit 2 ;;
  esac
done

# persistent XLA compile cache, for plain jax programs too: where the
# caller placed one it stays there, otherwise it is the checkout's
# .xla_cache — the path bigdl_tpu.utils.engine.enable_compile_cache uses
export JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-$(cd "$(dirname "$0")/.." && pwd)/.xla_cache}"

if [[ -n "$CPU_MESH" ]]; then
  # virtual device mesh on CPU — the reference's local-SparkContext
  # multi-node test trick (DistriOptimizerSpec, SURVEY.md §4).
  # BIGDL_CPU_MESH is honored by bigdl_tpu at import via jax.config; the
  # env vars below cover plain jax programs that never import it.
  export BIGDL_CPU_MESH="$CPU_MESH"
  export JAX_PLATFORMS=cpu
  export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=${CPU_MESH}"
fi

[[ $# -gt 0 ]] || { echo "no command given (usage: $0 [flags] -- cmd args...)" >&2; exit 2; }
exec "$@"

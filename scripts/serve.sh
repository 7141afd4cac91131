#!/usr/bin/env bash
# Serve launcher wrapper: one place that sets the process environment the
# vision-serving entry point needs, then execs the launcher module.
#
#   scripts/serve.sh --mesh 8 --requests 32 [any serve_vision flags...]
#
# The virtual-device count for CPU runs is taken from --mesh (jax reads
# XLA_FLAGS once at startup, so it must be exported before python imports
# jax; repro.launch.env is the canonical merge, used here via -c so the
# launcher process itself starts with the right environment).
set -euo pipefail

cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# pull the mesh size and multi-process topology out of the args (0 =
# single device, no flag; the coordinator trio is exported so worker
# children the caller spawns with this same script join the same mesh).
# An exported JAX_COMPILATION_CACHE_DIR reaches the environment shim too,
# so the persistence floors are zeroed before jax starts; unset, the
# launcher keeps its cache in <checkout>/.jax_cache
MESH=0
CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-}"
COORDINATOR="${JAX_COORDINATOR_ADDRESS:-}"
NUM_PROCESSES="${REPRO_NUM_PROCESSES:-}"
PROCESS_ID="${REPRO_PROCESS_ID:-}"
args=("$@")
for ((i = 0; i < ${#args[@]}; i++)); do
    if [[ "${args[$i]}" == "--mesh" && $((i + 1)) -lt ${#args[@]} ]]; then
        MESH="${args[$((i + 1))]}"
    fi
    if [[ "${args[$i]}" == "--coordinator" \
          && $((i + 1)) -lt ${#args[@]} ]]; then
        COORDINATOR="${args[$((i + 1))]}"
    fi
    if [[ "${args[$i]}" == "--num-processes" \
          && $((i + 1)) -lt ${#args[@]} ]]; then
        NUM_PROCESSES="${args[$((i + 1))]}"
    fi
    if [[ "${args[$i]}" == "--process-id" \
          && $((i + 1)) -lt ${#args[@]} ]]; then
        PROCESS_ID="${args[$((i + 1))]}"
    fi
done

eval "$(python - "$MESH" "$CACHE_DIR" "$COORDINATOR" "$NUM_PROCESSES" \
                 "$PROCESS_ID" <<'PY'
import os
import shlex
import sys

from repro.launch.env import configure

keys = ("XLA_FLAGS", "TF_CPP_MIN_LOG_LEVEL", "JAX_PLATFORMS",
        "JAX_PLATFORM_NAME", "LIBTPU_INIT_ARGS",
        "JAX_COMPILATION_CACHE_DIR",
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES",
        "JAX_COORDINATOR_ADDRESS", "REPRO_NUM_PROCESSES",
        "REPRO_PROCESS_ID")
seed = {k: os.environ[k] for k in keys if k in os.environ}
env = configure(int(sys.argv[1]),
                compilation_cache_dir=sys.argv[2] or None,
                coordinator_address=sys.argv[3] or None,
                num_processes=int(sys.argv[4]) if sys.argv[4] else None,
                process_id=int(sys.argv[5]) if sys.argv[5] else None,
                env=seed)
for k, v in env.items():
    print(f"export {k}={shlex.quote(v)}")
PY
)"

exec python -m repro.launch.serve_vision "$@"

# Host environment for benchmark runs — `source scripts/env.sh`.
#
# Pins the knobs that make wall-clock numbers comparable across hosts
# and runs; sourced by the CI bench invocations.  Everything is guarded
# so sourcing on a box without the optional pieces (tcmalloc, TPU
# runtime) is a no-op for that piece.

# Faster malloc for the host-side driver loops, when present.  The
# LD_PRELOAD is guarded: preloading a missing .so makes EVERY child
# process print a loader error.
for _tcm in /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
            /usr/lib/libtcmalloc.so.4; do
  if [ -e "$_tcm" ]; then
    export LD_PRELOAD="$_tcm"
    # silence tcmalloc's large-alloc reports for big ground-set arrays
    export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD=60000000000
    break
  fi
done
unset _tcm

# No TF/XLA chatter interleaved with the CSV rows the benches print.
export TF_CPP_MIN_LOG_LEVEL=4

# Deterministic dtypes: f64 stays off so every backend computes the
# same f32 program; the kernels opt into bf16 explicitly (precision=).
export JAX_ENABLE_X64=0
export JAX_DEFAULT_DTYPE_BITS=32

# Stable single-process host threading for the timing loops.
export OPENBLAS_NUM_THREADS="${OPENBLAS_NUM_THREADS:-1}"

# Forced host device count — APPEND-only and opt-in via
# REPRO_HOST_DEVICES so sourcing this never clobbers an XLA_FLAGS the
# caller already set (CI's distributed job pins its own
# --xla_force_host_platform_device_count at the job level).
if [ -n "${REPRO_HOST_DEVICES:-}" ]; then
  export XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=${REPRO_HOST_DEVICES}"
fi

# Serving environment for the PANN TPU stack. Source before launching:
#
#     source launch/env.sh
#     PYTHONPATH=src python -m repro.launch.serve --power_ladder 2,4,6 \
#         --backend packed --autotune ...
#
# Every knob is set with ${VAR:-default} so an explicitly exported value
# always wins. It sets no XLA_FLAGS: the installed libtpu aborts the process
# at start-up on the --xla_tpu_* and latency-hiding-scheduler flags given
# there. Nothing needs this file; chip_smoke.py runs without it.

# --- allocator -------------------------------------------------------------
# Serving engines hold N ladder variants resident; the default 75%
# preallocation plus the BFC allocator's growth policy fragments against
# the variant cache. Preallocate a fixed 85% once and keep the allocator
# platform-default (bfc) — deterministic footprint, no growth stalls.
export XLA_PYTHON_CLIENT_PREALLOCATE="${XLA_PYTHON_CLIENT_PREALLOCATE:-true}"
export XLA_PYTHON_CLIENT_MEM_FRACTION="${XLA_PYTHON_CLIENT_MEM_FRACTION:-0.85}"

# The autotune block cache and JAX's compile cache default to fixed,
# gitignored paths inside the checkout (.cache/, .jax_cache/); export
# REPRO_AUTOTUNE_CACHE / JAX_COMPILATION_CACHE_DIR to share them instead.

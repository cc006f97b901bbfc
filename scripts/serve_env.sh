# Canonical serving environment -- source this before any serve/bench
# launch:
#
#     source scripts/serve_env.sh
#     python -m repro.launch.serve --arch qwen2.5-1.5b --smoke \
#         --paged --prefix-sharing
#
# Rationale (idioms from production JAX serving stacks, see SNIPPETS.md):
#
# * tcmalloc -- glibc malloc stalls multi-GiB host allocations (weight
#   staging, checkpoint gathers); tcmalloc keeps them off the serving
#   hot path.  The preload is skipped when the library is absent, so
#   the script is safe to source on minimal containers.
# * XLA_FLAGS -- left as the caller set it.  On TPU, profiling runs may
#   add "--xla_step_marker_location=1" (step markers at the outer while
#   loop, so profile traces cut at dispatch boundaries, matching the
#   span tracer); CPU-only XLA builds reject the flag.
# * TF_CPP_MIN_LOG_LEVEL=4 -- silence the TF/XLA banner spam that
#   otherwise drowns the serve launcher's throughput lines.
# * Persistent XLA compilation cache: a relaunch (same config/buckets)
#   reuses compiled prefill/decode executables instead of re-tracing.
#   The launchers keep it in JAX_COMPILATION_CACHE_DIR when that is set
#   and in <repo>/.jax_cache otherwise (repro.launch.compile_cache).  The
#   serve launcher and bench-smoke report their steady-state compile
#   counters so a cold cache is visible (see BENCH_decode.json
#   "warm_start").

_TCMALLOC=/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4
if [ -e "${_TCMALLOC}" ]; then
    export LD_PRELOAD="${_TCMALLOC}"                  # faster malloc
    export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD=60000000000
fi
unset _TCMALLOC

export TF_CPP_MIN_LOG_LEVEL=4

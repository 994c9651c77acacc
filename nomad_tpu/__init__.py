"""nomad_tpu — a TPU-native workload-orchestration framework.

A brand-new framework with the capabilities of HashiCorp Nomad (studied at
/root/reference, surveyed in SURVEY.md), re-designed TPU-first: the host runs
a conventional control plane (state store, eval broker, plan applier, node
agents), while the scheduling math — constraint feasibility, bin-pack fit and
scoring, spread/affinity, preemption search, and plan-commit re-verification —
runs as batched JAX/XLA kernels over a device-resident cluster matrix.
"""

__version__ = "0.1.0"


def enable_compilation_cache() -> str:
    """Opt into JAX's persistent compilation cache; returns its directory.

    The scheduler's p99 budget assumes warm jit caches; the persistent cache
    makes that true across *processes* too (server restarts, test runs,
    bench warmup). Call before the first kernel invocation.

    One rule for every entry point: where ``JAX_COMPILATION_CACHE_DIR`` is
    set the directory is JAX's own business and nothing here overrides it;
    otherwise the cache lives at ``<checkout>/.jax_cache``, derived from
    this package's location so every process of one checkout shares it.
    """
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(checkout, ".jax_cache")
        )
    # The kernels' stage scopes (jax.named_scope) are metadata, which the
    # cache key leaves out by default: a profile would then be handed an
    # executable compiled before a scope existed, and name nothing.  Of the
    # metadata only the name stack goes into the key: with Python frames in
    # the locations too, every checkout path and every edit that shifts a
    # line above a kernel would start the cache from nothing.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir

"""JAX's persistent compilation cache where the program keeps it:
``$JAX_COMPILATION_CACHE_DIR`` when it is set, otherwise the fixed
``<checkout>/.jax_cache`` (``repro.utils.cache``), so only a cell's
first run in a checkout compiles. The benchmark adds one thing: eviction
is off. With it on, a cache entry written without its access-time file
stops every later write (seen on the TPU host), and the cache of a few
cells is small."""


def enable() -> str:
    import jax
    from repro.utils import cache
    path = cache.enable_compilation_cache()
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path

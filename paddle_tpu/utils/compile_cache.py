"""Where JAX's persistent compilation cache lives.

The directory is part of the cache key, so it must be the same string in
every process that should share compiled code: the one the environment
names, else a fixed place inside this checkout.  Never a temporary
directory, a pid or a time.
"""

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache():
    """Turn the persistent compilation cache on and return its
    directory.  Call before the first compile.

    With JAX_COMPILATION_CACHE_DIR set JAX reads it itself and nothing
    is set here; otherwise the cache goes to `<checkout>/.jax_cache`.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        # JAX's default keeps only compiles over a second; a start-up is
        # hundreds of smaller ones, and a warm start should redo none
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path

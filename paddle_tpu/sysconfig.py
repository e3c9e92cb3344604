"""Build-config introspection — reference python/paddle/sysconfig.py."""
import os

__all__ = ["get_include", "get_lib", "use_compile_cache"]


def get_include():
    return os.path.join(os.path.dirname(__file__), "include")


def get_lib():
    return os.path.join(os.path.dirname(__file__), "runtime", "lib")


def use_compile_cache():
    """Place JAX's persistent compilation cache; returns its directory.

    `JAX_COMPILATION_CACHE_DIR`, when set, is where JAX itself keeps the
    cache and nothing is set in code. Otherwise the cache is
    `<checkout>/.jax_cache`: the same absolute path on every run (the path
    is part of the cache key, so a directory that moves never hits). Call
    before the first compile.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path

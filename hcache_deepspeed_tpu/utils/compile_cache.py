"""Where the persistent XLA compilation cache lives.

One decision, taken by the two entry points that compile
(``hds.initialize`` and ``InferenceEngineV2``) and by nothing else: a
directory placed from outside through ``JAX_COMPILATION_CACHE_DIR`` is
left alone — JAX reads that variable itself — and otherwise the cache
sits at ``<checkout>/.jax_cache``. The path is part of the cache key
(a directory that moves never hits), so it is never built from a
temporary name, a pid or the time.
"""

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``.jax_cache`` beside the package (the repo root in a checkout)."""
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package), ".jax_cache")


def compile_cache_dir() -> str:
    """The directory in force: the one placed from outside, else the
    fixed one in the checkout."""
    return os.environ.get(CACHE_DIR_ENV) or default_cache_dir()


def ensure_compile_cache() -> str:
    """Make sure a persistent compilation cache is in force and return
    its directory. With ``JAX_COMPILATION_CACHE_DIR`` set nothing is set
    in code."""
    path = compile_cache_dir()
    if not os.environ.get(CACHE_DIR_ENV):
        import jax
        if jax.config.jax_compilation_cache_dir != path:
            jax.config.update("jax_compilation_cache_dir", path)
    return path

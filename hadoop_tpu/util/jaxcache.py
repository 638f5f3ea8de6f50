"""Where the persistent XLA compile cache lives.

Every process that initializes JAX for real work (a serving replica, a
trainer, the benches, ``chip_smoke.py``'s children) calls
:func:`configure_compile_cache` once before its first compile, so a
flagship-sized step that takes a minute to compile is compiled once per
machine, not once per process.

The operator places the cache from outside: when
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
module touches nothing. Unset, the cache goes to ``<checkout>/.jax_cache``
— a fixed path derived from the package location, because the directory
is part of what a later process must find again (never a temp dir, a pid
or a timestamp).
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def compile_cache_dir() -> str:
    """The directory in force (no jax import: launchers may ask)."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def configure_compile_cache() -> str:
    """Point jax at the persistent compile cache; returns the directory
    in force. Idempotent; call before the first compile."""
    if os.environ.get(ENV_VAR):
        return compile_cache_dir()
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # jax's default keeps only compiles that took >= 1 s, so a program
    # that compiles in about a second is written by whichever run it
    # happens to be slow in (measured: a warm chip_smoke run added 4
    # entries to the 20 of the cold one). Keep everything: a warm start
    # then writes nothing and skips the sub-second compiles too.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return DEFAULT_DIR

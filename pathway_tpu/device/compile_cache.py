"""The one persistent XLA compilation cache of this package.

A cold TPU process pays minutes of compiles (each encoder bucket, each
7B-wide prefill/decode program) that a later process can read back from
disk in seconds — but only if both name the same directory, because the
directory is what JAX looks entries up in.  So there is exactly one
place that decides it:

* ``JAX_COMPILATION_CACHE_DIR`` set: the operator placed the cache.  JAX
  reads that variable itself; this module sets **no** directory in code.
* unset: ``<checkout>/.jax_cache`` — derived from where this package
  lives, so every process of one checkout agrees on it (never a temp
  dir, a pid or a timestamp; it is listed in ``.gitignore``).

:func:`ensure_compile_cache` is called before the first compile by every
owner of compiled programs (``DeviceExecutor``; ``DecoderLM``, which
every ``GenerationScheduler`` is built on) and by the benchmarks.
"""

from __future__ import annotations

import os

import jax

__all__ = ["DEFAULT_CACHE_DIR", "ENV_VAR", "ensure_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def ensure_compile_cache() -> str:
    """Enable the persistent compilation cache; returns its directory.

    Idempotent and cheap: config writes only.  Every executable is
    cached, however small or quick to compile — a second process must
    add no entry for a shape the first one compiled, which a
    compile-time threshold would make depend on timing noise."""
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir

"""Where JAX's persistent compilation cache lives.

Every process that may compile for the chip calls ``configure()`` before its
first compile (``worker_main`` for workers granted ``TPU``, ``bench.py``).
The directory is part of the cache key, so it is never a temporary name, a
pid or a time: it is ``JAX_COMPILATION_CACHE_DIR`` where that is set (JAX
reads it itself; nothing here sets another), else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def cache_dir() -> str:
    """The directory ``configure()`` leaves JAX with. Imports no JAX."""
    checkout = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.environ.get(ENV_VAR) or os.path.join(checkout, ".jax_cache")


def configure() -> str:
    import jax

    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    # JAX leaves names and source lines out of the cache key, so a program
    # compiled before its operations were given ``jax.named_scope`` names is
    # found again without them (tests/test_platform.py shows it), and a
    # device trace is attributed by those names (``op_name``). With them in
    # the key, a cache shared with an older checkout gives an older program
    # only where it is the same. The price: an edit that moves the traced
    # lines compiles those programs once more (README, "Compile cache").
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path


def entry_count() -> int:
    try:
        return len(os.listdir(cache_dir()))
    except OSError:
        return 0

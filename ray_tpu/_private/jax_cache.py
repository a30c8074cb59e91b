"""Where JAX's persistent compilation cache lives.

Every process that may compile for the chip calls ``configure()`` before its
first compile (``worker_main`` for workers granted ``TPU``, the benchmark's
tools).
The directory is part of the cache key, so it is never a temporary name, a
pid or a time: it is ``JAX_COMPILATION_CACHE_DIR`` where that is set (JAX
reads it itself; nothing here sets another), else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Optional

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
    # JAX keeps only what took a second to compile. A serving start runs some
    # 75 programs under that (slices, updates, the samplers' scalars), and
    # compiling them again cost every warm start 8-9 s (PERF.md section 6,
    # PR 46): every program is kept, however short its compile.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


# ``bypassed`` nests and may be entered from several threads (one engine
# each): the flag is the process's, so the first one in turns it off and the
# last one out puts back what the first found.
_bypass_lock = threading.Lock()
_bypass_depth = 0
_bypass_was = True


def _set_cache_enabled(enabled: bool) -> bool:
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()  # JAX asks the flag once and remembers the answer
    return was


@contextlib.contextmanager
def bypassed():
    """Compile what runs inside without the persistent cache. For a program
    whose result has another device layout than the default (a relayout): in
    jax 0.9 such a program read back from the cache hands out the default
    layout, values intact, on the CPU as on a v5e
    (tests/test_platform.py shows it; PERF.md section 6, PR 29: the first run
    of a checkout held its weights head-major and every later one did not).
    A compile on another thread meanwhile only misses the cache."""
    global _bypass_depth, _bypass_was
    with _bypass_lock:
        if _bypass_depth == 0:
            _bypass_was = _set_cache_enabled(False)
        _bypass_depth += 1
    try:
        yield
    finally:
        with _bypass_lock:
            _bypass_depth -= 1
            if _bypass_depth == 0:
                _set_cache_enabled(_bypass_was)


def directory() -> Optional[str]:
    """Where JAX keeps what it compiles, or None where it keeps nothing: no
    directory is set (a process that neither called ``configure()`` nor was
    given ``JAX_COMPILATION_CACHE_DIR``), the cache is disabled, or the
    caller is inside ``bypassed()``."""
    import jax

    path = jax.config.jax_compilation_cache_dir
    return path if path and jax.config.jax_enable_compilation_cache else None


def forget_traces() -> bool:
    """Drop what JAX has traced and compiled in this process, where a
    compile cache is in use (``directory()``), and say whether it did. The
    cache's key holds the source lines of whichever call site traced a shared
    function first, so what a process has traced so far decides the key of
    what it compiles next (``llm/engine.py _warm_programs``); with no cache
    no key is looked up and nothing is dropped."""
    import jax

    if directory() is None:
        return False
    jax.clear_caches()
    return True


def entry_count() -> int:
    try:
        return len(os.listdir(cache_dir()))
    except OSError:
        return 0

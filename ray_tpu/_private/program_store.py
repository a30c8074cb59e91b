"""Compiled programs kept beside JAX's compile cache, so that a later process
of the same source, versions and configuration runs them with no trace and
no lowering.

JAX's persistent cache is keyed by the lowered module: a warm start still
traces and lowers every program in Python before it can ask for the
executable, and for a serving engine that is most of its start
(PERF.md section 6, PR 46). This store is keyed by what the program is made
from instead (``key``), which is known before anything is traced; it holds
``jax.experimental.serialize_executable``'s bytes with the result's tree.

The store obeys what the compile cache obeys: it lies in ``programs/`` under
``jax_compilation_cache_dir``, and where no directory is set (a process that
never called ``jax_cache.configure()``), the cache is disabled or the caller
is inside ``jax_cache.bypassed()``, ``directory()`` is None and nothing is read
or written. Clearing the compile cache's directory clears it.
"""

from __future__ import annotations

import contextlib
import functools
import hashlib
import json
import logging
import os
import pickle
import tempfile
from typing import Any, Optional

logger = logging.getLogger(__name__)

PACKAGE_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the flags that change what a function traces or compiles to
_JAX_FLAGS = ("jax_enable_x64", "jax_default_matmul_precision", "jax_default_prng_impl",
              "jax_threefry_partitionable", "jax_numpy_dtype_promotion")


def directory() -> Optional[str]:
    """Where programs are kept, or None where the compile cache is not in use."""
    from ray_tpu._private import jax_cache

    path = jax_cache.directory()
    return path and os.path.join(path, "programs")


@functools.lru_cache(maxsize=4)
def package_digest(root: str = PACKAGE_ROOT) -> str:
    """Every ``.py`` file under ``root``, by its path and bytes: an edit to
    any of them is another program as far as the store can know."""
    digest = hashlib.sha256()
    for folder, folders, files in os.walk(root):
        folders.sort()
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                digest.update(os.path.relpath(path, root).encode())
                with open(path, "rb") as f:
                    digest.update(hashlib.sha256(f.read()).digest())
    return digest.hexdigest()


@functools.lru_cache(maxsize=1)
def environment() -> dict:
    """What a program depends on beside its own arguments: the package's
    source, the libraries, the devices and the compiler's flags."""
    import jax
    import jaxlib

    devices = jax.devices()
    return {
        "package": package_digest(),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "runtime": devices[0].client.platform_version,
        "devices": [len(devices), jax.process_count(), devices[0].platform, devices[0].device_kind],
        "XLA_FLAGS": os.environ.get("XLA_FLAGS", ""),
        "LIBTPU_INIT_ARGS": os.environ.get("LIBTPU_INIT_ARGS", ""),
        "flags": {name: str(getattr(jax.config, name)) for name in _JAX_FLAGS},
    }


def _describe(leaf) -> list:
    """An argument as the compiler sees it, read off the value: nothing is traced."""
    import jax
    import numpy as np

    if isinstance(leaf, jax.Array):
        layout = getattr(getattr(leaf, "format", None), "layout", None)
        return [list(leaf.shape), str(leaf.dtype), bool(leaf.weak_type), str(leaf.sharding),
                list(layout.major_to_minor) if layout is not None else None]
    if isinstance(leaf, (np.ndarray, np.generic)):
        return [list(leaf.shape), str(leaf.dtype), False, None, None]
    return [type(leaf).__name__]  # a Python scalar: weakly typed, by its kind


def key(form: str, args: tuple, kwargs: dict, donate: tuple, context: Any) -> str:
    """The name a program is kept under: ``form`` (with its static arguments),
    each argument's place in the call, shape, dtype, sharding and device
    layout, the donated positions, ``context`` (whatever else shaped the
    program, as JSON) and ``environment()``."""
    import jax

    leaves = [[jax.tree_util.keystr(path), *_describe(leaf)]
              for path, leaf in jax.tree_util.tree_leaves_with_path((args, kwargs))]
    said = json.dumps([form, leaves, list(donate), context, environment()],
                      sort_keys=True, default=str)
    return hashlib.sha256(said.encode()).hexdigest()


def _path(folder: str, form: str, key_: str) -> str:
    safe = "".join(c if c.isalnum() or c in "._-" else "_" for c in form)
    return os.path.join(folder, f"{safe}-{key_[:40]}.bin")


def load(form: str, key_: str, device):
    """The program kept under ``key_``, loaded for ``device``; None where the
    store holds none. Raises what reading a damaged file raises."""
    from jax.experimental.serialize_executable import deserialize_and_load

    folder = directory()
    if folder is None:
        return None
    try:
        with open(_path(folder, form, key_), "rb") as f:
            blob = f.read()
    except FileNotFoundError:
        return None
    kept_key, payload, in_tree, out_tree = pickle.loads(blob)
    if kept_key != key_:
        raise ValueError(f"{form}: the file holds another program's key")
    return deserialize_and_load(payload, in_tree, out_tree, backend=device.client,
                                execution_devices=[device])


def save(form: str, key_: str, compiled) -> bool:
    """Keep ``compiled`` under ``key_`` (written beside its place and moved
    into it, so that a reader never sees half a file). False where the store
    is off or the program cannot be serialized, which is logged."""
    from jax.experimental.serialize_executable import serialize

    folder = directory()
    if folder is None:
        return False
    tmp = None
    try:
        blob = pickle.dumps((key_, *serialize(compiled)))
        os.makedirs(folder, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=folder, suffix=".tmp")
        with os.fdopen(fd, "wb") as f:
            f.write(blob)
        os.replace(tmp, _path(folder, form, key_))
        return True
    except Exception as e:  # noqa: BLE001 - the program runs all the same
        logger.warning("program %s is not kept for the next start: %s: %s",
                       form, type(e).__name__, e)
        if tmp is not None:
            with contextlib.suppress(OSError):
                os.unlink(tmp)
        return False

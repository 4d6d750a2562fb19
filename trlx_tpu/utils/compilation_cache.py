"""Persistent XLA compilation cache setup.

One place owns the jax cache knobs because the enablement check is latched:
``jax._src.compilation_cache.is_cache_used`` memoizes its answer at the FIRST
compile of the process, so configuring the cache after anything has compiled
(even a ``jax.random.PRNGKey``) silently disables it for the whole process.
Callers therefore invoke :func:`configure_compilation_cache` as the first
jax-touching act: ``MeshRLTrainer.__init__`` before it derives its RNG key,
``benchmark/run.py`` and ``chip_smoke.py`` before their first phase, and
``python -m trlx_tpu.analysis.ir`` before lowering.

Where the cache lives, in order:

1. ``$JAX_COMPILATION_CACHE_DIR`` — jax reads it itself. When it is set this
   module sets no directory at all: whoever runs the program placed the
   cache, and the path is part of the cache's key.
2. the explicit argument, ``train.compilation_cache_dir``, then
   ``mesh.compilation_cache_dir``.
3. on a TPU backend, ``<checkout>/.jax_cache`` (:data:`REPO_CACHE_DIR`): one
   fixed path, so two runs from the same checkout share their compiles.
4. otherwise the cache stays off (the CPU compiles of the tests are cheap and
   many; nobody asked to keep them).
"""

import os
from typing import Optional

from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)

JAX_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def resolve_cache_dir(config=None, cache_dir: Optional[str] = None) -> Optional[str]:
    """The directory this module would set for a TRLConfig, or None when it
    sets none: ``$JAX_COMPILATION_CACHE_DIR`` is in force, or nothing is
    configured off-TPU."""
    if os.environ.get(JAX_ENV_VAR):
        return None
    if cache_dir:
        return cache_dir
    for section in ("train", "mesh"):
        configured = getattr(getattr(config, section, None), "compilation_cache_dir", None)
        if configured:
            return configured

    import jax

    return REPO_CACHE_DIR if jax.default_backend() == "tpu" else None


def configure_compilation_cache(
    cache_dir: Optional[str] = None,
    config=None,
    min_compile_time_secs: float = 0.5,
) -> Optional[str]:
    """Turn the on-disk compile cache on where the module docstring says, and
    return the directory in force (None = cache off). ``min_compile_time_secs``
    trades cache-dir churn for coverage — 0.5s keeps real model steps while
    skipping the trivial host-side jits; tests pass 0.0 to cache everything."""
    import jax

    cache_dir = resolve_cache_dir(config, cache_dir)
    if cache_dir:
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    in_force = jax.config.jax_compilation_cache_dir
    if not in_force:
        return None
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", float(min_compile_time_secs)
    )
    # cache regardless of artifact size (the default skips small modules)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    placed_by = f" (${JAX_ENV_VAR})" if os.environ.get(JAX_ENV_VAR) else ""
    logger.info(f"persistent compilation cache at {in_force}{placed_by}")
    return in_force

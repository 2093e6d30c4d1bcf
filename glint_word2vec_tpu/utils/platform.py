"""Process-level JAX set-up shared by the CLI, bench.py, chip_smoke.py and
scripts/: which platform this process runs on, and where its compiled
programs are kept.

JAX reads ``JAX_PLATFORMS`` itself, so ``JAX_PLATFORMS=cpu python ...`` needs
no help. :func:`force_platform` is for the scripts that take their platform
from a variable of their own (``GLINT_SERVE_PLATFORM=cpu`` and friends) and
start children that must land on the same one.
"""

from __future__ import annotations

import os
from typing import Optional

import logging

logger = logging.getLogger(__name__)


def default_compile_cache_dir() -> Optional[str]:
    """``<checkout>/.jax_cache``: the persistent compile cache's place when
    ``JAX_COMPILATION_CACHE_DIR`` names none. Fixed, so the next run finds
    what this one compiled. ``None`` when the package does not run from a
    checkout it can write to (an installed copy sits in site-packages):
    then only the variable places a cache."""
    root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    in_checkout = os.path.isfile(os.path.join(root, "pyproject.toml"))
    if not in_checkout or not os.access(root, os.W_OK):
        return None
    return os.path.join(root, ".jax_cache")


def force_platform(platform: Optional[str]) -> None:
    """Pin this process, and the children it starts, to ``platform``.

    No-op for ``None``/empty. Must run before the first ``jax.devices()``
    or computation to take effect.
    """
    if not platform:
        return
    os.environ["JAX_PLATFORMS"] = platform
    import jax

    jax.config.update("jax_platforms", platform)


def enable_compile_cache(min_compile_secs: float = 1.0) -> Optional[str]:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory, or ``None`` where it stays off.

    * the backend JAX resolved is ``cpu`` (pinned or simply all there is):
      off. CPU cache hits broke bitwise checkpoint-resume parity in this
      repo's suite (tests/conftest.py).
    * ``JAX_COMPILATION_CACHE_DIR`` set: JAX honours it by itself; no
      directory is set in code, so that one and no other is used.
    * otherwise :func:`default_compile_cache_dir`; off, with a log line,
      where that has no writable place.

    ``min_compile_secs`` is the floor below which a program is not kept:
    JAX's own one second bounds the directory for training commands. The
    serving commands and ``chip_smoke.py`` pass 0, because a server start
    is ~50 programs of 0.1-3 s each, a closed family that is the same for
    every start of one table shape; nothing evicts, so a directory that
    has seen many shapes is cleared by hand (``rm -rf``).

    Op metadata is part of the cache key. JAX leaves it out by default, and
    a hit then hands back the executable of whoever compiled first, with
    THAT program's op names and source lines: a profile of this version
    would show the ``glint.*`` scopes (parallel/engine.py) of the version
    that filled the cache, or none. The price is a recompile when a line
    on a traced call's stack moves.

    Call from an entry point before the first compile, never at import;
    initialises the backend.
    """
    import jax

    def off() -> None:
        jax.config.update("jax_enable_compilation_cache", False)

    if jax.default_backend() == "cpu":
        return off()
    # Only ever lowered: an entry point that asked for 0 (chip_smoke.py)
    # keeps it through the cli commands it then runs in its process.
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        min(jax.config.jax_persistent_cache_min_compile_time_secs,
            min_compile_secs),
    )
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = default_compile_cache_dir()
    if path is None:
        logger.info(
            "persistent compile cache off: not running from a writable "
            "checkout and JAX_COMPILATION_CACHE_DIR is not set"
        )
        return off()
    jax.config.update("jax_compilation_cache_dir", path)
    return path

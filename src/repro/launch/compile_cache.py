"""JAX's persistent compilation cache, kept at one fixed path.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; where it is set, this
module sets nothing.  Otherwise the cache lives at ``<repo>/.jax_cache``
(listed in ``.gitignore``): a fixed path, never a temporary directory —
the path is part of what a later run looks up, so a second run of the
same program in the same checkout reads its executables back instead of
compiling them again.
"""

from __future__ import annotations

import os

import jax

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def enable() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

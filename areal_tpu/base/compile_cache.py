"""Where jax's persistent compilation cache lives.

Every device-owning entry point calls :func:`setup_compile_cache` before
its first use of jax.  The directory is part of nothing but itself: if
``JAX_COMPILATION_CACHE_DIR`` is set the choice is left entirely to jax
(nothing is set in code); otherwise the cache goes to ONE fixed path
inside the checkout, ``<repo>/.jax_cache`` — never a temp name, pid or
time, because a cache that moves between runs never hits.  The default is
exported through the environment so child processes share it.
"""

from __future__ import annotations

import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<repo>/.jax_cache`` (this file is ``<repo>/areal_tpu/base/``)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def setup_compile_cache() -> str:
    """Returns the cache directory in effect (see module docstring)."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    os.environ[ENV_VAR] = DEFAULT_CACHE_DIR
    if "jax" in sys.modules:
        # jax read the (then unset) env var at import; tell it directly
        import jax

        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def cache_entry_count(path: str) -> int:
    """Compiled programs in the cache directory (0 when it does not exist
    yet).  jax writes one ``*-cache`` file per program beside an
    ``*-atime`` bookkeeping file."""
    try:
        names = os.listdir(path)
    except FileNotFoundError:
        return 0
    return sum(1 for n in names if not n.endswith("-atime"))

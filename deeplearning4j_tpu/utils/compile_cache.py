"""Where JAX's persistent compilation cache lives.

Called by entry points only (`chip_smoke.py`, the benchmark's
processes, `serving/fleet/replica_main.py`, `tools/shardmap_smoke.py`),
before their first compile; never at package
import and never from `tests/conftest.py`. The directory is part of the
cache key, so it must not move between runs: no temporary name, process
id or time in it.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Return the cache directory in effect. Where
    `JAX_COMPILATION_CACHE_DIR` is set JAX reads it itself and no
    directory is set in code; otherwise the cache goes to
    `<checkout>/.jax_cache`.

    Debug locations are made part of the cache key. JAX 0.9.0 leaves them
    out, so a program whose ops carry layer names (`jax.named_scope` in
    the models' `_forward`) fetched the executable of the same program
    compiled without them, and a device trace then named no layer (chip
    run, PR 25). The price: an edit that moves lines in a traced file
    makes the next run compile again."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR

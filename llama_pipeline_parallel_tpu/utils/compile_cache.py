"""Where JAX's persistent compilation cache lives — the one rule.

Every entry point (`train.py`, `bench.py`, `chip_smoke.py`, `tools/serve.py`,
`tools/generate.py`, `tools/serve_traffic.py`) calls `setup()` before its
first compile:

- `JAX_COMPILATION_CACHE_DIR` set: JAX already honours it; the program sets
  nothing, so whoever owns the machine places the cache.
- unset: the cache goes to `<checkout>/.jax_cache` (git-ignored). A fixed
  path, never `/tmp`, a pid or a timestamp — the directory is part of the
  cache key, so one that moves never hits.

Either way the key includes the operations' metadata
(`jax_compilation_cache_include_metadata_in_key`; jax 0.9.0 leaves it out by
default). The scope names of utils/trace.py ARE metadata: with them left out
of the key, a cache warmed by a build whose scopes differ hands back an
executable that carries the old names, or none, and a trace of it reads as
unnamed (proved on CPU and on the v5e, PERF.md PR 24). The price is that an
edit which only moves source lines is a new key too.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def setup() -> str:
    """Resolve the cache directory (see module docstring) and return it."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def entry_count(path: str) -> int:
    """Number of compiled programs cached under `path` (0 if absent)."""
    try:
        return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
    except FileNotFoundError:
        return 0

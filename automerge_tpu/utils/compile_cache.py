"""JAX's persistent compilation cache, at one place for every entry point.

A cold TPU compile of this repo's kernels takes seconds each (the
megabucket ladder alone is a dozen shapes), and nothing survives a process
without a persistent cache. `configure()` is called by every entry point
(`chip_smoke.py`, the `bench.py` worker, `python -m automerge_tpu.perf`)
before its first compile:

- where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and this
  function sets nothing;
- otherwise it points JAX at `<checkout>/.jax_cache` — a fixed, git-ignored
  directory. The directory's path is part of what a hit depends on, so it is
  never a temporary name, a pid or a time.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_ROOT, ".jax_cache")


def configure() -> str:
    """Make sure a persistent compile cache is on; returns its directory."""
    env_dir = os.environ.get(_ENV)
    if env_dir:
        return env_dir
    import jax
    os.makedirs(DEFAULT_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # keep every executable, not only those that took a second to build:
    # the small bucket shapes add up on a cold start
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return DEFAULT_DIR


def entries(path: str) -> int:
    """Number of executables cached under `path` (0 for a missing
    directory); jax names them `<name>-<key>-cache`."""
    try:
        return sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    except OSError:
        return 0

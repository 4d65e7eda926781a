"""The jax surface this repo rides, for the ONE installation it supports
(jax 0.9.x: `jax.shard_map(check_vma=)`, `jax.enable_x64`,
`jax.errors.JaxRuntimeError`).

Two choke points live here:

* `shard_map` — the API the whole `parallel/` package is built on.
  This file is the ONLY place allowed to touch `jax.shard_map`
  directly: graftlint rule R7 (`shard-map-compat`, analysis/rules.py)
  makes a direct reference anywhere else a finding, so the next jax
  move is a one-file change.

* `enable_compile_cache` — the ONLY place that sets
  `jax_compilation_cache_dir`. Every process that compiles (the alpha
  server, chip_smoke.py's JAX children) calls it
  before first use, so a restarted server and a second benchmark run
  find what the first one compiled.
"""

from __future__ import annotations

import os

import jax

__all__ = ["shard_map", "enable_compile_cache"]

# <checkout>/.jax_cache (git-ignored). A fixed path on purpose: the
# directory is part of the cache key's lookup, so a temp name, pid or
# timestamp would never hit.
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and return its
    directory. With `JAX_COMPILATION_CACHE_DIR` in the environment
    nothing is set in code — jax reads the variable itself and the
    environment places the cache; otherwise it lives at
    `<checkout>/.jax_cache`."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    return _DEFAULT_CACHE_DIR

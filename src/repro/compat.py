"""The one seam between this repo and the installed jax (0.9.x).

Every jax API whose name or home moves between releases is reached through
this module, so a jax upgrade touches one file, and so is the one place the
persistent compilation cache is configured.  Mesh construction lives in
``repro.launch.mesh.make_auto_mesh``.
"""

from __future__ import annotations

import os
import pathlib

import jax

# fixed, inside the checkout: the cache directory is part of what makes a
# later process find an entry, so a path built per run would never hit
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check disabled."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def trace_state_clean() -> bool:
    """True when no jax transformation (jit, vmap, grad, ...) is tracing.

    Host-side state (caches, metrics, trace spans) is only touched at top
    level, so tracing a caller never leaks tracers into it.
    """
    return jax.core.trace_ctx.is_top_level()


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (jax reads it
    itself and nothing here overrides it); otherwise ``DEFAULT_CACHE_DIR``.
    Every executable is cached, however fast it compiled.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path

"""Toolchain setup shared by every entry point: sweep meshes and the
persistent compilation cache."""
from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = ["make_mesh", "use_compile_cache"]

# Fixed, inside the checkout: the cache directory is part of each entry's
# key, so a path that moved between runs (temp name, pid, time) never hits.
_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    ".jax_cache"))


def make_mesh(axis: str = "sweep", devices=None) -> Mesh:
    """A 1-D device mesh named ``axis`` (default: all local devices).

    ``jax.make_mesh`` builds Explicit-typed axes, under which the sweep
    runners' ``with_sharding_constraint``/``shard_map`` spellings change
    meaning; a plain ``Mesh`` over the device array keeps Auto axes.
    """
    devices = jax.devices() if devices is None else list(devices)
    return Mesh(np.asarray(devices), (axis,))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and nothing is changed.  Otherwise the cache goes to ``.jax_cache``
    at the root of the checkout.  Call before the first compilation.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    return _CACHE_DIR

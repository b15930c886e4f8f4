"""Device-mesh and compilation-cache helpers shared by the entry points.

* ``device_mesh`` — the one 1-D mesh constructor for data-parallel
  ``jax.shard_map`` callers (the sharded admission control plane, the
  multi-device smoke checks).
* ``enable_compile_cache`` — JAX's persistent compilation cache, turned on
  by the entry points (``chip_smoke.py``, ``benchmarks/run.py``), never on
  import.
"""

from __future__ import annotations

import os
import pathlib

import jax

# The in-checkout cache directory (git-ignored) used when the environment
# does not place the cache itself.  Fixed: the path is part of the cache
# key, so a directory that moved would never hit.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def device_mesh(n_dev: int, axis: str = "shards"):
    """A 1-D ``Mesh`` over the first ``n_dev`` visible devices.

    Kept here so CPU emulation via ``--xla_force_host_platform_device_count``
    and real multi-device runs build meshes identically.  Raises if fewer
    than ``n_dev`` devices are visible rather than silently wrapping."""
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < n_dev:
        raise ValueError(f"need {n_dev} devices for mesh axis {axis!r}, have {len(devs)}")
    return Mesh(np.asarray(devs[:n_dev]), (axis,))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no directory of its own; otherwise the cache goes to
    ``CHECKOUT_CACHE_DIR`` inside the checkout.  Thresholds drop to zero so
    even sub-second bucket programs are cached — the batched engines' cold
    start is many small compiles, not one big one."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path

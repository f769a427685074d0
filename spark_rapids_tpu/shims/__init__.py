"""Shim loader — the ShimLoader / SparkShimServiceProvider analog.

The reference supports 24 Spark versions by compiling per-version
"parallel worlds" source trees and mounting the right one at runtime
(sql-plugin-api/.../ShimLoader.scala:182, SparkShimServiceProvider SPI,
build/shimplify.py). The moving target here is JAX, whose public API
shifts across releases (shard_map moved from jax.experimental to the
jax namespace and renamed check_rep -> check_vma, among others). Each
shim module is a provider declaring which jax versions it serves; the
loader probes providers at first use and every caller goes through the
selected world. One installation is supported and pinned
(pyproject.toml), so there is one world; a version no provider serves
raises ShimError instead of running untested.

Adding support for a new jax release = adding one provider module, the
same mechanics as adding a spark3xx world in the reference.
"""

from __future__ import annotations

import threading
from typing import List, Optional

_PROVIDERS = (
    "spark_rapids_tpu.shims.jax_current",
)

# Every provider must export exactly this surface (api_validation
# checks it; see tools/api_validation.py and tests/test_shims.py)
SHIM_API = (
    "VERSIONS",
    "matches",
    "shard_map",
    "make_mesh",
    "description",
)

_lock = threading.Lock()
_selected = None


class ShimError(RuntimeError):
    pass


def _jax_version() -> str:
    import jax

    return jax.__version__


def detect_shim_provider(version: Optional[str] = None):
    """Probe providers in order; first match wins (ShimLoader.
    detectShimProvider analog)."""
    import importlib

    v = version or _jax_version()
    tried: List[str] = []
    for name in _PROVIDERS:
        mod = importlib.import_module(name)
        if mod.matches(v):
            return mod
        tried.append(f"{name} (serves {mod.VERSIONS})")
    raise ShimError(
        f"no shim provider serves jax {v}; probed: {tried}")


def get_shim():
    """The active shim world (cached after first detection)."""
    global _selected
    with _lock:
        if _selected is None:
            _selected = detect_shim_provider()
        return _selected

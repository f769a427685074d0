"""Shim world for the installed jax (0.9): `jax.shard_map` with
`check_vma`."""

from __future__ import annotations

VERSIONS = ("0.9.",)


def matches(version: str) -> bool:
    return version.startswith(VERSIONS)


def description() -> str:
    return "jax.shard_map world (jax 0.9)"


def shard_map(fn, mesh, in_specs, out_specs, check: bool = False):
    """Bind the SPMD program over the mesh (replication checking off by
    default: batch row counts legitimately differ per shard)."""
    import jax

    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def make_mesh(devices, axis_name: str):
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(devices), (axis_name,))

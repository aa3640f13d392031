"""The one ``shard_map`` spelling every manual-sharding module uses."""

from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with per-shard replication checking off — the
    schedules here build replication via explicit ``psum`` and assert it
    themselves (numerical pin tests), which the checker can't see."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

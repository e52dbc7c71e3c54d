"""`shard_map` as ring_attention / pipeline / moe call it: positional
mesh and specs, replication checking off (`check_vma=False`)."""
from __future__ import annotations

from jax import shard_map as _shard_map


def shard_map(f, mesh, in_specs, out_specs):
    return _shard_map(f, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)

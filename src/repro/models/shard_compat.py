"""``shard_map`` with the replication check off.

Every distributed body in this repo returns pmean'd/psum'd values the
checker cannot see through, so they all disable it.
"""

from __future__ import annotations

import jax


def shard_map_unchecked(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

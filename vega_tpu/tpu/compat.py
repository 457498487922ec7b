"""The dense tier's one shard_map spelling.

Every device program is `jax.jit(compat.shard_map(fn, ...))` with
`check_vma=False`: the exchange and sort programs call Pallas kernels whose
`out_shape` carries no varying-manual-axes annotation, and with the checker
on jax refuses them at trace time ("`vma` on `jax.ShapeDtypeStruct` must not
be `None`"). Pinning the flag here keeps a new program from being written
with the checker on; vegalint VG001 points every other `jax.shard_map`
spelling at this wrapper.
"""

from __future__ import annotations

import jax


def shard_map(f, mesh=None, in_specs=None, out_specs=None):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

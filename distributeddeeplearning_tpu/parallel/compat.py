"""The one ``shard_map`` spelling the ops layer uses."""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with ``check_vma=False`` by default: pallas calls
    and masked-psum patterns inside our kernels cannot annotate
    varying-mesh-axes metadata, and the ops' own tests pin correctness
    against unsharded references instead."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )

"""One ``shard_map`` surface for the repo.

Every in-repo caller goes through this wrapper so the replication check
defaults to off: the bodies here call Pallas kernels and host-routed
collectives whose outputs carry no varying-manual-axes annotation, which
``jax.shard_map``'s default ``check_vma=True`` would reject.
"""

from __future__ import annotations

import jax

__all__ = ["shard_map"]


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False, axis_names=None):
    """``jax.shard_map`` with ``check_vma=False`` by default.

    ``axis_names`` (when given) is the set of *manual* mesh axes.
    """
    kwargs = {} if axis_names is None else {"axis_names": axis_names}
    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=check_vma,
        **kwargs,
    )

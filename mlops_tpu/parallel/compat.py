"""The one seam every shard_map in the framework routes through."""

from __future__ import annotations

from typing import Callable

import jax


def shard_map(
    f: Callable, *, mesh, in_specs, out_specs, check_vma: bool = True
) -> Callable:
    """``jax.shard_map``; callers whose bodies the varying-manual-axes
    checker cannot type pass ``check_vma=False``."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def pcast_varying(x, axis_names: tuple[str, ...]):
    """Type ``x`` as varying over ``axis_names`` inside shard_map — the
    annotation scan carries need under the varying-manual-axes type
    system."""
    return jax.lax.pcast(x, axis_names, to="varying")

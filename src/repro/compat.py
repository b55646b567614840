"""The project's defaults for jax's mesh and shard_map entry points.

The repo is pinned to one jax release (see ``requirements.txt``).  These
two wrappers only fix the defaults every call site wants:

  * ``shard_map``  — ``jax.shard_map`` with replication checking
                     (``check_vma``) off unless asked for;
  * ``make_mesh``  — ``jax.make_mesh`` with every axis ``AxisType.Auto``,
                     so GSPMD propagates shardings the way the schedules
                     in ``kernels/systolic.py`` expect.

The Pallas-specific helpers (``compiler_params``, the interpret-mode
resolution) live with the kernels in ``repro.kernels.runtime``.
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map``; ``check`` is its ``check_vma`` replication check."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis of type ``Auto``."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices)

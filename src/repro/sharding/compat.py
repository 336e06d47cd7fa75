"""Thin wrappers over the installed JAX's sharding APIs.

One spelling per concept for the call sites: ``shard_map_norep`` (every
explicit-SPMD region here turns the replication checker off),
``make_mesh`` (Auto or Explicit axis types) and ``cost_analysis_dict``.
"""

from __future__ import annotations

import jax


def shard_map_norep(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off.  Every explicit-SPMD
    region in this repo (dp trainer, vocab-parallel CE, the mesh-aware
    compiled schedules) wants the check off — int8 collectives and Pallas
    bodies confuse the replication checker."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()``, or {} where the backend has none."""
    return compiled.cost_analysis() or {}


def make_mesh(axis_shapes, axis_names, *, explicit: bool = False):
    """``jax.make_mesh`` with every axis Auto (default) or Explicit."""
    at = (jax.sharding.AxisType.Explicit if explicit
          else jax.sharding.AxisType.Auto)
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(at,) * len(axis_names))

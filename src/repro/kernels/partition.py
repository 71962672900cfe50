"""Running a Pallas kernel under a mesh.

Mosaic kernels cannot be partitioned by GSPMD: a ``pallas_call`` must sit
in a ``shard_map`` manual over every mesh axis.  ``on_mesh`` wraps a
kernel's forward call in one, splitting batch over the data axes and heads
over ``model`` where they divide, and replicating the rest.

It names the axes that are manual already (the photonic step's rails)
too, because a nested ``shard_map`` lowers with only the axes it names
marked manual.  Naming them makes its transpose sum cotangents over those
axes, which would be wrong for per-shard inputs; so it wraps only the
forward ``pallas_call``, inside each kernel's ``custom_vjp``, and is
never differentiated.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType

from repro.parallel.sharding import MODEL_AXIS


def on_mesh(fn, batch: int, heads: int, spec_fn):
    """``fn`` made manual over the ambient mesh, or ``fn`` with no mesh.

    ``spec_fn(b, m)`` returns (in_specs, out_specs) given the batch axes
    ``b`` and the head axis ``m``, each None where it does not divide
    ``batch`` or ``heads``.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return fn
    auto = [n for n, t in zip(mesh.axis_names, mesh.axis_types)
            if t != AxisType.Manual]
    if not auto:
        return fn
    dp = tuple(a for a in auto if a != MODEL_AXIS)
    b = None
    if dp and batch % math.prod(mesh.shape[a] for a in dp) == 0:
        b = dp if len(dp) > 1 else dp[0]
    m = None
    if MODEL_AXIS in auto and heads % mesh.shape[MODEL_AXIS] == 0:
        m = MODEL_AXIS
    in_specs, out_specs = spec_fn(b, m)
    return jax.shard_map(fn, in_specs=in_specs, out_specs=out_specs,
                         axis_names=set(mesh.axis_names), check_vma=False)

"""The version-sensitive JAX surface, in one file (jax 0.9.0).

The repo is written against one installed JAX release (README "Supported
JAX versions"). The APIs that have moved between releases are reached only
through this module, so a future upgrade touches one file; the
``version-api`` lint rule (``repro.analysis.lint``) keeps other modules
from importing them directly.

  make_mesh(shape, axes)   a Mesh with Auto axis types, over the default
                           devices or over ``devices`` in the given order.
  shard_map(...)           ``jax.shard_map``.
  pvary(x, axes)           mark x device-varying over ``axes``.
  new_primitive(name)      a ``jax.extend.core.Primitive``.
"""
from __future__ import annotations

import os

import jax
import numpy as np
from jax.extend.core import Primitive
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes, devices=None):
    """Mesh over ``devices`` (kept in the order given) or, when None, over
    the default devices in ``jax.make_mesh``'s order."""
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=types)
    return Mesh(np.asarray(devices).reshape(shape), axes, axis_types=types)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_vma)


def pvary(x, axes):
    """Mark x device-varying over `axes`."""
    if not axes:
        return x
    return jax.lax.pcast(x, tuple(axes), to="varying")


def new_primitive(name: str):
    return Primitive(name)


def enable_cpu_collectives(impl: str = "gloo") -> None:
    """Select the cross-process collectives backend for the CPU client.

    Must run before the first jax device access AND before
    ``jax.distributed.initialize`` — without it, a multi-process CPU cluster
    forms but every cross-host collective deadlocks.
    """
    os.environ.setdefault("JAX_CPU_COLLECTIVES_IMPLEMENTATION", impl)
    jax.config.update("jax_cpu_collectives_implementation", impl)

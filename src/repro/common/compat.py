"""Small helpers over the JAX sharding API (JAX 0.9).

Every ``shard_map`` and varying-cast call site goes through here, so the
one place that knows the API's defaults and sharp edges is this module.
"""
from __future__ import annotations

import jax
from jax import lax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None, **kwargs):
    """``jax.shard_map``; ``check_vma=None`` keeps the library default.

    Bodies that call Pallas kernels pass ``check_vma=False``: under the
    check, ``pallas_call`` wants every output shape annotated with how it
    varies over the mesh, which the kernels (written per device) do not
    carry."""
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def pcast_varying(x, axes):
    """Mark every leaf of ``x`` device-varying over ``axes``: the scan
    carries and literals that shard_map's vma typing needs to see as
    varying. Leaves already varying over an axis are left alone on that
    axis (``lax.pcast`` rejects a varying -> varying cast)."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)

    def cast(leaf):
        todo = tuple(a for a in axes if a not in jax.typeof(leaf).vma)
        return lax.pcast(leaf, todo, to="varying") if todo else leaf

    return jax.tree.map(cast, x)


def default_interpret() -> bool:
    """Pallas kernels only compile for TPU; interpret everywhere else."""
    return jax.default_backend() != "tpu"

"""Production mesh definitions (functions only — importing this module never
touches jax device state)."""
from __future__ import annotations

import jax


def make_mesh(shape, names):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``: keeps the mesh
    out of explicit-sharding mode, so GSPMD propagates the shardings the
    ``sharding/specs.py`` rules annotate.  Every mesh in this repo (and in
    the test subprocesses) goes through here."""
    return jax.make_mesh(shape, names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """Whatever this host has (tests / CPU examples)."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))

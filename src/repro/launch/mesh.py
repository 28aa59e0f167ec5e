"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import; smoke
tests see the real single CPU device).

Mesh construction goes through ``repro.compat``, the one module that
names the mesh API.
"""
from __future__ import annotations

from ..compat import make_mesh as _make_mesh
from ..compat import use_mesh  # noqa: F401  (re-export: the mesh entry point)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Explicit mesh for elastic re-carves and tests."""
    return _make_mesh(shape, axes)


def single_device_mesh():
    """1×1 mesh over the local device — lets the same pjit code paths run in
    CPU tests."""
    return _make_mesh((1, 1), ("data", "model"))

"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
initialization; smoke tests and benches see the 1 real CPU device.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """The assigned 256-chip pod mesh — ('data', 'model') 16x16, or
    ('pod', 'data', 'model') 2x16x16 with ``multi_pod``."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many devices exist (tests)."""
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: GSPMD propagates shardings
    from the inputs and the models' ``constrain`` hints."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def dp_axes(mesh) -> tuple:
    """The data-parallel mesh axes (includes 'pod' when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def axis_size(mesh, name) -> int:
    """Total extent of ``name`` — an axis name, or a tuple/list of
    names (product of extents); absent axes count as 1."""
    if isinstance(name, (tuple, list)):
        out = 1
        for n in name:
            out *= axis_size(mesh, n)
        return out
    return mesh.shape[name] if name in mesh.axis_names else 1

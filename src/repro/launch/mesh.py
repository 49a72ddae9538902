"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — only ``dryrun.py`` forces the 512-device host
platform, and only in its own process.

Mesh axes are ``Auto``: the model's sharding rules are GSPMD annotations
(``repro.parallel.sharding``), and ``jax.make_mesh`` defaults to
``Explicit`` axes, under which every ambiguous contraction must name its
output sharding.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (elastic re-mesh path, tests)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))

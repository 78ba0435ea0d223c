"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — device count is
locked at first jax init, and only ``dryrun_aqp.py`` forces the
512-device host platform.

Topology: v5e pods of 256 chips arranged (data=16, model=16); the
multi-pod mesh prepends a ``pod`` axis (2 × 256 = 512 chips). ``model``
is the innermost axis → maps onto the torus' fastest contiguous links.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host has (CPU smoke tests: 1 device)."""
    n = len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"))

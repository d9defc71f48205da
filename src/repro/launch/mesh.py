"""Mesh factories.

``make_production_mesh`` builds the target deployment mesh:
  single-pod : (16, 16)    axes (data, model)   = 256 chips (one v5e pod)
  multi-pod  : (2, 16, 16) axes (pod, data, model) = 512 chips

FLAD mapping: ``pod`` = cloud regions, ``data`` = vehicles/edge clients,
``model`` = intra-cluster pipeline/tensor ranks.

Functions (never module-level constants) so importing this module does not
touch jax device state — the dry-run must set XLA_FLAGS before first init.
Prefer the declarative :class:`repro.api.MeshSpec` front end, which also
handles host-device forcing.
"""
from __future__ import annotations

import jax


def _mk(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


#: public alias used by repro.api.MeshSpec
make_mesh = _mk


#: deployment shapes, keyed by multi_pod (shared with repro.api.MeshSpec)
PRODUCTION_SHAPES = {False: (16, 16), True: (2, 16, 16)}


def make_production_mesh(*, multi_pod: bool = False):
    shape = PRODUCTION_SHAPES[multi_pod]
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh over forced host devices for CPU tests."""
    if pod:
        return _mk((pod, data, model), ("pod", "data", "model"))
    return _mk((data, model), ("data", "model"))

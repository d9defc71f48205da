"""Declarative mesh construction for :mod:`repro.api`.

``MeshSpec`` replaces the per-launcher ``--mesh 2,4`` string parsing and
manual ``XLA_FLAGS`` device forcing. A spec is plain data: it can be built
before jax touches any device, so the host-device forcing (needed for CPU
testing of multi-client meshes) happens at exactly the right moment —
before the first backend init — no matter which entrypoint runs first.
Forcing applies only when the CPU is the pinned platform
(``JAX_PLATFORMS=cpu``); on an accelerator the mesh is built from the
chips present and ``XLA_FLAGS`` is left alone.

FLAD axis mapping (see :mod:`repro.launch.mesh`): ``pod`` = cloud regions,
``data`` = vehicles / edge FL clients, ``model`` = intra-cluster
pipeline/tensor ranks.
"""
from __future__ import annotations

import dataclasses
import math
import os
import re
from typing import Optional, Sequence, Tuple, Union

AXES = ("pod", "data", "model")

_FORCE_RE = re.compile(r"--xla_force_host_platform_device_count=(\d+)")

# set once the first mesh is built (jax locks the device count at first
# backend init; after that forcing is verification-only)
_devices_locked = False


def _cpu_pinned() -> bool:
    """True iff ``jax_platforms`` (``JAX_PLATFORMS``) makes the CPU the
    platform jax will use — read without initializing a backend."""
    import jax
    return (jax.config.jax_platforms or "").split(",")[0] == "cpu"


def ensure_host_devices(n: int) -> None:
    """Require ``n`` devices; on the pinned CPU platform, first force at
    least ``n`` host devices (before the first backend init).

    Safe to call repeatedly: once jax has initialized, or on an
    accelerator, this is only a check that enough devices exist, and the
    error names the platform and the count found.
    """
    global _devices_locked
    if n <= 0:
        return
    if not _devices_locked and _cpu_pinned():
        flags = os.environ.get("XLA_FLAGS", "")
        m = _FORCE_RE.search(flags)
        current = int(m.group(1)) if m else 0
        if current < n:
            flags = _FORCE_RE.sub("", flags).strip()
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n}"
            ).strip()
    import jax

    devices = jax.devices()
    _devices_locked = True
    if len(devices) < n:
        platform = devices[0].platform
        hint = ("jax locks the host device count at first backend use: "
                "set JAX_PLATFORMS=cpu and build the Session/MeshSpec (or "
                "call ensure_host_devices) before any other jax device "
                "access" if platform == "cpu"
                else "use a mesh no larger than the chips present")
        raise RuntimeError(
            f"need {n} devices, have {len(devices)} {platform} "
            f"device(s); {hint}")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh: dims + axis names + (optional) device forcing.

    ``dims``     trailing-aligned against ``(pod, data, model)`` unless
                 ``axes`` is given: ``(2, 4)`` -> data=2, model=4.
    ``devices``  None (default) requires ``prod(dims)`` devices, forcing
                 that many host devices on the pinned CPU platform;
                 0 disables forcing (use whatever jax already has);
                 N requires (on the CPU: forces) at least N.
    ``production``/``multi_pod`` select the deployment meshes from
                 :func:`repro.launch.mesh.make_production_mesh`.
    """

    dims: Tuple[int, ...] = (2, 4)
    axes: Optional[Tuple[str, ...]] = None
    devices: Optional[int] = None
    production: bool = False
    multi_pod: bool = False

    @classmethod
    def parse(cls, spec: Union["MeshSpec", str, Sequence[int], None], *,
              devices: Optional[int] = None) -> "MeshSpec":
        """Coerce ``--mesh``-style input ('2,4', (2, 4), MeshSpec, None)."""
        if spec is None:
            return cls(devices=devices)
        if isinstance(spec, MeshSpec):
            return spec if devices is None else \
                dataclasses.replace(spec, devices=devices)
        try:
            if isinstance(spec, str):
                dims = tuple(int(x) for x in spec.split(","))
            else:
                dims = tuple(int(x) for x in spec)
        except (TypeError, ValueError):
            raise ValueError(
                f"mesh spec {spec!r}: expected comma-separated ints like "
                f"'2,4' (data,model) or '2,4,4' (pod,data,model)") from None
        if not 1 <= len(dims) <= len(AXES):
            raise ValueError(f"mesh dims {dims}: want 1..{len(AXES)} axes")
        return cls(dims=dims, devices=devices)

    @property
    def size(self) -> int:
        if self.production:
            from repro.launch.mesh import PRODUCTION_SHAPES
            return math.prod(PRODUCTION_SHAPES[self.multi_pod])
        return math.prod(self.dims)

    @property
    def axis_names(self) -> Tuple[str, ...]:
        if self.production:
            return AXES if self.multi_pod else AXES[1:]
        return self.axes or AXES[-len(self.dims):]

    def build(self):
        """Materialize the jax Mesh (forcing host devices if requested)."""
        from repro.launch.mesh import make_mesh, make_production_mesh

        force = self.size if self.devices is None else self.devices
        ensure_host_devices(force)
        if self.production:
            return make_production_mesh(multi_pod=self.multi_pod)
        return make_mesh(tuple(self.dims), self.axis_names)

"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and everything else must see the real (single) device.

Mesh shapes:
  single-pod  (16, 16)      axes ("data", "model")   = 256 chips/pod
  multi-pod   (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

``pod`` is the hierarchical data-parallel axis: batch shards over
(pod, data); gradient reduction is reduce-scatter within the pod before
anything crosses the inter-pod links (sharding/collectives.py).
"""

from __future__ import annotations

from jax.sharding import Mesh

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(data: int = 1, model: int = 1) -> Mesh:
    """A mesh over however many (CPU) devices the test process has."""
    return make_mesh((data, model), ("data", "model"))


def mesh_name(mesh: Mesh) -> str:
    return "x".join(str(mesh.shape[a]) for a in mesh.axis_names)


def n_devices(mesh: Mesh) -> int:
    n = 1
    for a in mesh.axis_names:
        n *= mesh.shape[a]
    return n

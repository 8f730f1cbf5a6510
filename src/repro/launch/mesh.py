"""Production mesh construction.

The production target is a TPU v5e pod of 16×16 = 256 chips; multi-pod
runs stack a leading ``pod`` axis (2 pods = 512 chips for the dry-run,
but the same code scales the pod axis to any fleet size — the pod axis
only ever carries data parallelism + ZeRO state sharding, so its
collectives are DCN-friendly ring all-reduces).

``make_production_mesh`` is a *function* (never a module-level constant)
so importing this module touches no jax device state — required for the
dry-run's forced host-device count to work.
"""

from __future__ import annotations

import jax

# Canonical axis names used by every PartitionSpec in the framework.
POD_AXIS = "pod"
DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod mesh, or 2×16×16 multi-pod mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = (POD_AXIS, DATA_AXIS, MODEL_AXIS) if multi_pod else (DATA_AXIS, MODEL_AXIS)
    return make_mesh(shape, axes)


def make_mesh(shape, axes, devices=None):
    """Arbitrary mesh (tests, elastic resizes, selection meshes).

    ``devices`` optionally restricts the mesh to a subset of the host's
    devices (parity tests build a (data, model) submesh next to the full
    (pod, data, model) lattice mesh this way).  Every axis is Auto: the
    runners place their operands with explicit ``shard_map`` specs.
    """
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(
        shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_lattice_mesh(pod: int, axes=(POD_AXIS, DATA_AXIS, MODEL_AXIS)):
    """(pod, data, model) mesh for the OPT-guess lattice runtime.

    The leading ``pod`` axis carries independent (OPT, α) guesses
    (``core.distributed.dash_auto_distributed``); the remaining host
    devices are factorized data-major over the trailing two axes — e.g.
    8 devices with ``pod=2`` gives the (2, 2, 2) pod-in-miniature mesh
    the CI distributed job exercises.
    """
    n = len(jax.devices())
    assert n % pod == 0, f"{n} devices not divisible by pod={pod}"
    rest = n // pod
    d = 1
    for cand in range(int(rest ** 0.5), 0, -1):
        if rest % cand == 0:
            d = cand
            break
    return make_mesh((pod, rest // d, d), axes)


def make_host_mesh(max_devices: int | None = None, axes=("data", "model")):
    """Best-effort mesh from whatever devices exist on this host (tests)."""
    n = len(jax.devices())
    if max_devices:
        n = min(n, max_devices)
    # Greedy 2-way factorization, data-major.
    d = 1
    for cand in range(int(n ** 0.5), 0, -1):
        if n % cand == 0:
            d = cand
            break
    if len(axes) == 2:
        return make_mesh((n // d, d), axes)
    return make_mesh((n,), axes[:1])


def mesh_num_devices(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n

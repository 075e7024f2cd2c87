"""Production meshes. A FUNCTION (not a module constant) so importing this
module never touches jax device state — the dry-run forces 512 host
devices before first jax init; tests see the single real CPU device.

Besides the model-stack meshes this module owns the cache daemon's
placement mesh: :func:`make_lane_mesh` is a 1-D ``"lane"`` mesh over
which ``core/shards.py`` places one execution lane (= shard state
pytree) per device via ``shard_map``."""
from __future__ import annotations

import functools

import jax

LANE_AXIS = "lane"


@functools.lru_cache(maxsize=None)
def make_lane_mesh(n_devices: int):
    """1-D ``("lane",)`` mesh over the first ``n_devices`` local devices.

    Cached so every table/executor sharing a device count sees the *same*
    Mesh object (jit cache keys and NamedSharding comparisons stay cheap
    and stable). The axis is ``Auto``: the lane executors are written
    for GSPMD propagation around their ``shard_map`` body, and jax's
    ``Explicit`` default would demand an ``out_sharding`` on every
    scatter that touches the assembled lane stack."""
    return jax.make_mesh((n_devices,), (LANE_AXIS,),
                         axis_types=(jax.sharding.AxisType.Auto,))


def lane_mesh_for(n_shards: int, n_devices: int | None = None):
    """The daemon's placement mesh for an ``n_shards``-way table, or
    ``None`` when placement is pointless (one device would hold all
    lanes).

    Policy: use ``d`` devices where ``d`` is the largest divisor of
    ``n_shards`` with ``d <= min(n_shards, local device count)`` — each
    device then owns a contiguous block of ``n_shards // d`` lanes, so
    assembled state splits evenly along the leading lane axis."""
    if n_devices is None:
        n_devices = jax.local_device_count()
    lim = min(int(n_shards), int(n_devices))
    d = max((k for k in range(1, lim + 1) if n_shards % k == 0), default=1)
    return make_lane_mesh(d) if d > 1 else None


def refuse_fleet_on_accelerator(what: str) -> None:
    """Stop ``what`` (a launcher of several daemon processes on this
    host) when JAX's backend here is an accelerator: every daemon would
    claim the chip, and a chip serves one process at a time. Only the
    CPU backend (e.g. ``JAX_PLATFORMS=cpu``) runs a local fleet."""
    backend = jax.default_backend()
    if backend != "cpu":
        raise SystemExit(
            f"{what}: this host's JAX backend is {backend!r}; several "
            "daemon processes cannot share its chip. Run one daemon per "
            "host, or set JAX_PLATFORMS=cpu for a local CPU fleet.")


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes: 'pod' = outer data-parallel axis (gradient reduction crosses the
    inter-pod links), 'data' = in-pod batch/FSDP axis, 'model' = tensor/
    expert axis (innermost => fastest ICI ring).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *, pods: int = 0):
    """Small host-device mesh for lowering tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count >= product)."""
    if pods:
        return jax.make_mesh((pods, n_data, n_model),
                             ("pod", "data", "model"))
    return jax.make_mesh((n_data, n_model), ("data", "model"))

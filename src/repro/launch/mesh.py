"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; tests see 1 CPU).
"""

from __future__ import annotations

import os
from typing import Callable, Dict, Tuple

import jax
from jax._src import xla_bridge

# Published per-chip peaks, keyed by ``jax.Device.device_kind`` (Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s;
# ICI taken as ~50 GB/s per link).  A kind not listed is an error, never a
# default: a share of the wrong chip's peak is not a measurement.
CHIP_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s": 50e9},
}
# the chip the production meshes (and the dry-run roofline) are built of
PRODUCTION_CHIP = "TPU v5 lite"


def chip_peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind``."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}"
                       ) from None


SINGLE_POD_SHAPE = (16, 16)
SINGLE_POD_AXES = ("data", "model")
MULTI_POD_SHAPE = (2, 16, 16)
MULTI_POD_AXES = ("pod", "data", "model")

# the emulated-cluster mesh for the SPMD replay (DESIGN.md §13): S parameter-
# server shards × L learner-group devices on XLA host devices
SIM_AXES = ("ps", "learner")

_HOST_COUNT_FLAG = "--xla_force_host_platform_device_count"


def _jax_initialized() -> bool:
    """Whether a jax backend has already been created (after which the
    host-platform device count is locked in)."""
    return xla_bridge.backends_are_initialized()


def ensure_host_devices(n: int) -> int:
    """Ensure ≥ n (emulated) host devices, returning the live device count.

    The ``xla_force_host_platform_device_count`` XLA flag (SNIPPETS §3, the
    dry-run trick) only takes effect BEFORE the first jax backend is
    created.  Called early, this sets/extends ``XLA_FLAGS`` (keeping an
    existing larger request) and initializes jax; called after jax is
    already live with fewer than n devices it raises a RuntimeError that
    says exactly how to fix the launch — instead of the opaque
    "mesh shape is larger than the number of devices" failure
    ``make_debug_mesh`` used to die with."""
    if n < 1:
        raise ValueError(f"need at least 1 device, got n={n}")
    if not _jax_initialized():
        flags = os.environ.get("XLA_FLAGS", "").split()
        kept, have = [], 0
        for f in flags:
            if f.startswith(_HOST_COUNT_FLAG):
                try:
                    have = int(f.split("=", 1)[1])
                except (IndexError, ValueError):
                    have = 0
            else:
                kept.append(f)
        want = max(n, have)
        os.environ["XLA_FLAGS"] = " ".join(
            kept + [f"{_HOST_COUNT_FLAG}={want}"]).strip()
    count = jax.device_count()
    if count < n:
        raise RuntimeError(
            f"need {n} devices but jax initialized with {count}: the host "
            f"device count locks at first backend use, so set "
            f"XLA_FLAGS={_HOST_COUNT_FLAG}={n} in the environment (or call "
            f"launch.mesh.ensure_host_devices({n}) before any jax "
            f"computation / device query)")
    return count


def _require_devices(n: int, what: str) -> None:
    if jax.device_count() < n:
        raise RuntimeError(
            f"{what} needs {n} devices but only {jax.device_count()} are "
            f"visible; run under XLA_FLAGS={_HOST_COUNT_FLAG}={n} or call "
            f"launch.mesh.ensure_host_devices({n}) before jax initializes")


def _make_mesh(shape, axes) -> jax.sharding.Mesh:
    # Auto axes: the model code places arrays with sharding constraints and
    # leaves the rest to the partitioner's propagation.  jax.make_mesh's
    # default (Explicit) puts shardings into types, and the model's
    # reshapes and gathers do not state them.
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = MULTI_POD_SHAPE if multi_pod else SINGLE_POD_SHAPE
    axes = MULTI_POD_AXES if multi_pod else SINGLE_POD_AXES
    return _make_mesh(shape, axes)


def data_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """The learner (batch) axes: ('pod', 'data') on multi-pod meshes."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def n_chips(mesh: jax.sharding.Mesh) -> int:
    return mesh.devices.size


def n_learners(mesh: jax.sharding.Mesh) -> int:
    """λ for the distributed runtime = product of the learner axes."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    lam = 1
    for a in data_axes(mesh):
        lam *= sizes[a]
    return lam


def make_debug_mesh(data: int = 2, model: int = 2) -> jax.sharding.Mesh:
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count
    to have been set before jax init — see :func:`ensure_host_devices`)."""
    _require_devices(data * model, f"debug mesh ({data}×{model})")
    return _make_mesh((data, model), ("data", "model"))


def make_sim_mesh(ps: int, learners: int) -> jax.sharding.Mesh:
    """The SPMD-replay cluster: ``ps × learner`` emulated host devices.

    Axis "ps" holds the S parameter-server shards (one (K, Dp) ring slice
    per device); axis "learner" splits the c gradient slots of an update
    across learner-group devices (DESIGN.md §13)."""
    _require_devices(ps * learners, f"sim mesh ({ps}×{learners})")
    return _make_mesh((ps, learners), SIM_AXES)


def shard_map(f: Callable, mesh: jax.sharding.Mesh, *, in_specs,
              out_specs) -> Callable:
    """``jax.shard_map`` with replication checking off: the replay
    out-specs replicate the ring over the learner axis, which the checker
    cannot prove through a psum-inside-scan body."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

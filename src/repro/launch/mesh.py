"""Production meshes.

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is the Mix2FLD *device* axis: cross-pod DCN is the scarce
uplink, intra-pod ICI the fat downlink (DESIGN.md §3).

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12   # per chip
HBM_BW = 819e9             # bytes/s per chip
ICI_BW = 50e9              # bytes/s per link


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the compiler propagates
    shardings, as every path here assumes (``jax.make_mesh`` defaults to
    ``Explicit`` axes, under which plain indexing of a sharded array
    must name its output sharding)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke tests (same axis names)."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_device_mesh(num_devices: int, shards: int | None = None):
    """1-D ("data",) mesh for the federated *device* axis of the round loop
    (core.protocols, ``FederatedConfig.shard_devices``).

    shard_map blocks must be equal-sized, so the shard count defaults to
    the largest divisor of the device population that fits the local chip
    count — a 1-chip host gets a 1-shard mesh (the sharded path then
    reduces to the vmapped path exactly, which the protocol-regression
    equivalence test locks down).
    """
    avail = len(jax.devices())
    if shards is None:
        shards = max(n for n in range(1, min(num_devices, avail) + 1)
                     if num_devices % n == 0)
    if num_devices % shards:
        raise ValueError(f"device population {num_devices} not divisible "
                         f"by {shards} mesh shards")
    return _auto_mesh((shards,), ("data",))


def _largest_divisor(n: int, limit: int) -> int:
    """Largest divisor of ``n`` that is <= ``limit`` (>= 1)."""
    return max(d for d in range(1, max(1, min(n, limit)) + 1)
               if n % d == 0)


def grid_mesh_shape(grid_size: int, num_devices: int,
                    shape: tuple | None = None,
                    avail: int | None = None) -> tuple[int, int]:
    """Resolve the ``(grid_shards, device_shards)`` shape of a 2-D pod
    mesh without building it (the sweep engine re-resolves per program
    group — each group's grid slice has its own G).

    Auto-shaping greedily spends chips on the *grid* axis first: grid
    points are embarrassingly parallel (no cross-point collectives at
    all), whereas device-axis shards pay a psum per aggregation — the
    roofline model (``roofline.analysis.recommend_execution``) reaches
    the same ordering from the bytes-per-FLOP side.  Both entries must
    divide their axis (shard_map blocks are equal-sized); an explicit
    ``shape`` that doesn't is an error, the auto path picks the largest
    divisors that fit ``avail`` chips.
    """
    avail = len(jax.devices()) if avail is None else avail
    if shape is not None:
        gs, ds = int(shape[0]), int(shape[1])
        if gs < 1 or ds < 1:
            raise ValueError(f"mesh shape entries must be >= 1, "
                             f"got {shape}")
        if grid_size % gs:
            raise ValueError(f"grid size {grid_size} not divisible by "
                             f"{gs} grid shards")
        if num_devices % ds:
            raise ValueError(f"device population {num_devices} not "
                             f"divisible by {ds} device shards")
        if gs * ds > avail:
            raise ValueError(f"mesh shape {gs}x{ds} needs {gs * ds} "
                             f"chips but only {avail} are available")
        return gs, ds
    gs = _largest_divisor(grid_size, avail)
    ds = _largest_divisor(num_devices, avail // gs)
    return gs, ds


def make_grid_mesh(grid_size: int, num_devices: int,
                   shape: tuple | None = None):
    """2-D ("grid", "data") mesh for pod-scale sweeps: hyperparameter
    grid points shard along "grid", each point's federated device axis
    along "data" (``launch.sharding.federated_grid_pspecs``).  On a
    1-chip host this degenerates to a (1, 1) mesh and the shard_mapped
    program reduces to the vmapped one exactly — the same fallback
    contract as :func:`make_device_mesh`.
    """
    gs, ds = grid_mesh_shape(grid_size, num_devices, shape)
    return _auto_mesh((gs, ds), ("grid", "data"))


def data_axes(mesh) -> tuple:
    """Axes that shard the batch: ("pod","data") when pods exist."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))

"""Production meshes.

Target hardware: TPU v5e pods — 256 chips/pod (16x16), 197 bf16 TFLOP/s,
16 GiB HBM @ 819 GB/s, ~50 GB/s/link ICI per chip.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): (16, 16) "data" x "model" single-pod, or
(2, 16, 16) "pod" x "data" x "model" for the 2-pod = 512-chip fleet.
FedGAN maps agents onto ("pod", "data") — see repro.core.fedgan.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.dist.sharding import mesh_dims  # noqa: F401  (canonical copy)

# v5e roofline constants (per chip)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link (~ per-chip usable, 1 link)
DCI_BW = 25e9                     # bytes/s cross-pod (data-center links, est.)
HBM_BYTES = 16 * 1024 ** 3


def _make_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_serving_mesh(*, model_parallel: int = 1, devices=None):
    """Serving mesh shaped from the devices actually present: ("data",
    "model") with ``model_parallel`` chips of tensor parallelism per replica
    and the rest as batch parallelism.  The 1-device CPU case degenerates to
    a (1, 1) mesh on which every constraint is a no-op, so the
    ``repro.serve`` engine runs the identical code path from laptop to pod.
    """
    devs = list(devices) if devices is not None else jax.devices()
    n = len(devs)
    if model_parallel < 1 or n % model_parallel:
        raise ValueError(f"model_parallel {model_parallel} must divide the "
                         f"{n} available devices")
    return _make_mesh((n // model_parallel, model_parallel), ("data", "model"),
                      devs)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU multi-device tests (requires the XLA host-device
    flag to have been set before jax initialised)."""
    return _make_mesh(shape, axes)

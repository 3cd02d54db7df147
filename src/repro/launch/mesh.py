"""Production meshes. TPU v5e target: 16×16 = 256 chips/pod, 2 pods = 512.

A FUNCTION (not module-level constant) so importing never touches jax device
state — required because the dry-run overrides the host device count and the
smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax
import numpy as np

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
# interconnect — 50 GB/s on each of a chip's four ICI links.
CHIP_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def chip_peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``. A device with no published
    entry is an error: a roofline against another chip's peaks is wrong."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(CHIP_PEAKS)})") from None


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_test_mesh(model: int = None, *, data: int = 1):
    """Small mesh over the LOCAL devices for multi-device tests and the
    vocab-sharded heads: the first ``data * model`` devices reshaped to
    ("data", "model"). ``model=None`` uses every device not claimed by
    ``data``. Pairs with the 8-simulated-host-device test harness
    (tests/conftest.py sets --xla_force_host_platform_device_count=8)."""
    devs = jax.devices()
    if model is None:
        model = max(len(devs) // data, 1)
    need = data * model
    if len(devs) < need:
        raise ValueError(f"make_test_mesh needs {need} devices, have "
                         f"{len(devs)} (force host devices via XLA_FLAGS="
                         f"--xla_force_host_platform_device_count={need})")
    arr = np.asarray(devs[:need]).reshape(data, model)
    return jax.sharding.Mesh(arr, ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def data_axes(mesh) -> tuple:
    """Axes used for batch/data parallelism (pod folds into data)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)

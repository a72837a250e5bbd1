"""Chip peaks used by the roofline and the anomaly monitor.

One table, keyed by the ``device_kind`` JAX reports for a device.  A kind
that is not in the table is an error, never a default.  The search runs on
host CPU devices (virtual meshes) and on the TPU compiler's described
chips; the counters it derives there model the chip named by
``MODELLED_KIND``.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_flops_bf16: float  # FLOP/s per chip
    hbm_bw: float           # bytes/s per chip
    ici_bw: float           # bytes/s per link (charged per chip, conservative)
    hbm_bytes: float        # HBM capacity per chip
    vmem_bytes: float


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s ICI per chip (charged here as ~50 GB/s per link).
CHIPS = {
    "TPU v5 lite": ChipSpec(name="tpu-v5e", peak_flops_bf16=197e12,
                            hbm_bw=819e9, ici_bw=50e9,
                            hbm_bytes=16 * 1024**3, vmem_bytes=128 * 1024**2),
}

# the chip whose peaks the search's counters model when they are not
# computed on a TPU device (CPU meshes, described topologies)
MODELLED_KIND = "TPU v5 lite"


def chip_spec(device_kind: str) -> ChipSpec:
    """Peaks of the chip JAX calls ``device_kind``; unknown kinds raise."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks recorded for device kind {device_kind!r}; "
            f"known kinds: {sorted(CHIPS)}") from None


V5E = chip_spec(MODELLED_KIND)


def chip_of_meshes(meshes: dict) -> ChipSpec:
    """The chip counters are modelled on: the TPU the meshes are made of,
    or, for host CPU meshes and stand-ins, ``MODELLED_KIND``."""
    from jax.sharding import Mesh
    for mesh in meshes.values():
        if isinstance(mesh, Mesh):
            dev = mesh.devices.flat[0]
            if dev.platform == "tpu":
                return chip_spec(dev.device_kind)
    return V5E


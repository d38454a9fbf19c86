"""One hardware model shared by the roofline and the trace-contract analyzer.

Peak numbers live in one table keyed by JAX's ``device_kind``, each row with
its source.  Consumers take a :class:`HardwareModel` explicitly, or ask
:func:`get_default_hardware` for the chip the process runs on — a device
whose kind is not in the table is an error, never a silent default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class HardwareModel:
    """Per-chip peak numbers used for roofline terms and trace budgets."""

    name: str
    peak_flops: float               # bf16 FLOP/s per chip
    hbm_bw: float                   # B/s per chip
    ici_bw: float                   # B/s per link
    hbm_bytes: float                # HBM capacity per chip
    vmem_bytes: float               # on-chip vector memory


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 16 GB HBM, 1,600 Gbit/s ICI per chip (four links of 50 GB/s).
TPU_V5E = HardwareModel(name="tpu-v5e", peak_flops=197e12, hbm_bw=819e9,
                        ici_bw=50e9, hbm_bytes=16e9, vmem_bytes=128e6)

# keyed by ``jax.devices()[0].device_kind``
PEAKS: dict[str, HardwareModel] = {
    "TPU v5 lite": TPU_V5E,
}


def hardware_for(device_kind: str) -> HardwareModel:
    """The peak table's row for ``device_kind``; an unknown kind raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak numbers for device kind {device_kind!r}; add a sourced "
            f"row to repro.analysis.hardware.PEAKS (known: {sorted(PEAKS)})"
        ) from None


def get_default_hardware() -> HardwareModel:
    """The peak numbers of the chip this process runs on."""
    import jax

    return hardware_for(jax.devices()[0].device_kind)

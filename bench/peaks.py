"""The yardstick's constants: published chip peaks and uplink wire widths.

Copied here so that a change to the program cannot move what the
benchmark measures against.

CHIP_PEAKS: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s
of inter-chip interconnect over four links.  Keyed by jax's
``device_kind``; a device that is not in the table is an error.

UPLINK_WIRE_BYTES: bytes per gradient element each uplink dtype puts on
the wire (the program's ``kernels/ops.py`` table at the time the
benchmark was defined: f32 4, bf16 2, int8 1).
"""
from __future__ import annotations

CHIP_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9,
                    "ici_link_bw": 50e9},
}

UPLINK_WIRE_BYTES = {"f32": 4, "bf16": 2, "int8": 1}


def chip_peaks(device_kind: str) -> dict:
    """The peak row of ``device_kind``; raises for a device with no
    published peaks here (a CPU among them)."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak table entry for device {device_kind!r} "
                         f"(known: {sorted(CHIP_PEAKS)})") from None


def round_step_bytes(num_devices: int, param_dim: int, uplink: str) -> int:
    """HBM bytes one cell-round of the fused OTA round step needs: the
    [N, D] uplink at its wire width, the [D] f32 noise draw, and the [D]
    f32 params read and written (unpadded D)."""
    return (num_devices * param_dim * UPLINK_WIRE_BYTES[uplink]
            + 3 * param_dim * 4)

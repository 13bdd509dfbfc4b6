"""The chip the run is on, and its peaks.

A run names its device as JAX reports it and refuses anything but a TPU
whose ``device_kind`` the peak table knows: there is no CPU fallback.
"""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "peaks.json")


class DeviceError(RuntimeError):
    """The run is not on a chip it can measure."""


def peaks() -> dict:
    with open(PEAKS) as f:
        return json.load(f)["devices"]


def check(devices, chips: int) -> dict:
    """Platform, kind and count of ``devices`` (``jax.devices()``), and the
    kind's peaks; raises :class:`DeviceError` on a non-TPU platform, a kind
    missing from the table, or fewer chips than the cell asks for."""
    if not devices:
        raise DeviceError("JAX reports no device")
    dev = devices[0]
    if dev.platform != "tpu":
        raise DeviceError(f"device platform is {dev.platform!r}, not a TPU")
    table = peaks()
    if dev.device_kind not in table:
        raise DeviceError(f"no peaks for device kind {dev.device_kind!r}")
    if len(devices) < chips:
        raise DeviceError(f"{len(devices)} chips, the cell asks for {chips}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "peaks": table[dev.device_kind]}

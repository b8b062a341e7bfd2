"""What the run sees of the machine: platform, chips, memory, peaks."""

from __future__ import annotations

import json
import os


class WrongDevice(Exception):
    """The machine is not the one the cell asks for."""


def require(chips: int, rehearsal: bool):
    """The devices of this run, or WrongDevice. A chip run takes a TPU and
    exactly the cell's chips; a rehearsal takes the CPU."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        raise WrongDevice(f"jax found no backend: {exc}") from exc
    platform = devices[0].platform
    want = "cpu" if rehearsal else "tpu"
    if platform != want:
        raise WrongDevice(
            f"platform is {platform!r}, not {want!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}); --rehearsal is the "
            "CPU mode")
    if len(devices) != chips:
        raise WrongDevice(f"the cell asks for {chips} device(s), jax "
                          f"reports {len(devices)}")
    return devices


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, not a default."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}"
                       f" (known: {sorted(table)})")
    return table[device_kind]


def _largest(devices, stat: str) -> int:
    return max(int((d.memory_stats() or {}).get(stat, 0)) for d in devices)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device (0 where the backend does
    not say, as on the CPU)."""
    return _largest(devices, "peak_bytes_in_use")


def memory_limit_bytes(devices) -> int:
    """What the backend lets a program use of one device's memory."""
    return _largest(devices, "bytes_limit")


def describe(devices, peak_bytes: int) -> dict:
    """The result line's ``device``, with the peak as read after the window
    (the correctness check that follows may use more)."""
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak_bytes}

"""The device a run is on: what it must be, what it reports, its peaks."""

from __future__ import annotations

from .spec import bench_path, load_json


class NoChip(SystemExit):
    """The run has no right to a result line: wrong platform or count."""


def require_devices(chips: int, rehearse_cpu: bool):
    """``jax.devices()`` if they are what the cell asks for.  A TPU run
    needs platform ``tpu`` and exactly ``chips`` devices; a CPU rehearsal
    needs the explicit flag and platform ``cpu``."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearse_cpu:
        if platform != "cpu":
            raise NoChip(f"--rehearse-cpu wants JAX_PLATFORMS=cpu, found "
                         f"{platform!r}")
        return devices
    if platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0] is {platform!r} "
                     f"({devices[0].device_kind}); a CPU rehearsal needs "
                     "--rehearse-cpu and prints no device metric")
    if len(devices) != chips:
        raise NoChip(f"the cell asks for {chips} chip(s), JAX sees "
                     f"{len(devices)}")
    return devices


def peak_bytes(devices):
    """Per-device peak of device memory; None where the backend reports no
    memory statistics (XLA:CPU).  ``peak_bytes_in_use`` is the high-water
    mark of live buffers; the TPU runtime books what loaded programs need
    while they run (their temporaries) apart, under ``peak_bytes_reserved``,
    and the chip needs both.  The sum is an upper bound: the two marks need
    not fall at the same instant."""
    stats = [d.memory_stats() for d in devices]
    if any(not s or "peak_bytes_in_use" not in s for s in stats):
        return None
    return [int(s["peak_bytes_in_use"]) + int(s.get("peak_bytes_reserved", 0))
            for s in stats]


def peaks_for(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = load_json(bench_path("peaks.json"))["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"benchmark/peaks.json has no entry for device_kind="
                       f"{device_kind!r}; add one with its source")
    return table[device_kind]

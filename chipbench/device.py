"""The gate: a benchmark run needs the chips its cell asks for, of a
kind the table of peaks holds. It never falls back to the CPU; the
tests rehearse on the CPU through an explicit flag, and such a run
says `cpu` in its result."""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class NoChip(Exception):
    pass


def load_peaks(path=None):
    with open(path or os.path.join(HERE, "peaks.json")) as f:
        return json.load(f)


def peaks_for(device_kind, peaks=None):
    peaks = load_peaks() if peaks is None else peaks
    if device_kind not in peaks:
        raise NoChip("no peaks for device_kind %r (known: %s)"
                     % (device_kind, sorted(peaks)))
    return peaks[device_kind]


def claim(chips, rehearsal):
    """The devices this run uses and their peaks. Raises NoChip when
    JAX finds no TPU, fewer chips than `chips`, or an unknown kind.
    `rehearsal` admits CPU devices (peaks None)."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not rehearsal:
        raise NoChip("JAX found platform %r, not a TPU" % platform)
    if len(devices) < chips:
        raise NoChip("the cell needs %d chips, JAX found %d"
                     % (chips, len(devices)))
    devices = devices[:chips]
    peaks = None if platform != "tpu" else peaks_for(devices[0].device_kind)
    return devices, peaks


def describe(devices):
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}

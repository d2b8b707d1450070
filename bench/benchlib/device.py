"""The chip: its published peaks, and the look for it.

Peaks are keyed by ``device_kind`` as JAX reports it.  A device that is
not in the table is an error, never a default.
"""

from __future__ import annotations

import jax

PEAKS = {
    # JAX's device_kind for a TPU v5e chip
    "TPU v5 lite": {
        "bf16_flops": 197e12,         # FLOP/s, bfloat16 on the MXUs
        "hbm_bytes_per_s": 819e9,     # HBM bandwidth
        "hbm_bytes": 16e9,            # HBM capacity
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


class NoChip(RuntimeError):
    """The process sees no accelerator, or fewer chips than the cell asks
    for: nothing is measured."""


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[kind]


def find_chips(chips: int) -> list:
    """The TPU devices a cell runs on; raises :class:`NoChip` otherwise."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs[:chips]


def describe(devs, trace: dict | None = None) -> dict:
    """The result's ``device`` entry: as JAX reports it, with the peak
    bytes in use on the fullest chip."""
    peaks_used = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devs]
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": max(peaks_used)}
    if trace is not None:
        out["busy_s"] = trace["busy_s"]
        out["window_s"] = trace["window_s"]
    return out

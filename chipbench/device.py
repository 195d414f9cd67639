"""The chips a run holds: the check that they are there, what JAX says
of them, their memory peak, and their published peaks."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def require_chips(count: int):
    """The first ``count`` TPU devices, or :class:`NoChip`.  Never falls
    back to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU found: the default JAX device is "
                     f"{devs[0].platform!r} ({devs[0].device_kind}); the "
                     f"benchmark runs only on TPU chips")
    if len(devs) < count:
        raise NoChip(f"the cell needs {count} TPU chips, JAX finds "
                     f"{len(devs)}")
    return devs[:count]


def describe(devs) -> dict:
    import jax

    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest of ``devs`` (0 where the backend
    keeps no statistics)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def peaks(kind: str) -> dict:
    """Published peaks of one chip of ``kind`` (``device_kind``).  A kind
    not in the table is an error, never a default."""
    with open(PEAKS_FILE) as fh:
        table = json.load(fh)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"{PEAKS_FILE.name}; known: "
                       f"{sorted(k for k in table if not k.startswith('_'))}")
    return table[kind]

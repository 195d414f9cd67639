"""Roofline device table: the per-chip ceilings and dispatch-cost
constants that :mod:`repro.analysis.opcost` and ``backend='auto'``
dispatch read, keyed by device name (``tpu_v5e``, ``tpu_v4`` and the
``interpret`` CPU pseudo-device).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class Device:
    """One roofline device entry (per chip).

    The physical ceilings are ``peak_flops``/``hbm_bw``/``ici_bw``; the
    remaining fields parameterize the per-op dispatch cost model in
    :mod:`repro.analysis.opcost`:

    vmem_bytes        : working-set budget for row-tiled Pallas
                        accumulators (the ``GJ_VMEM_BYTES`` knob, now a
                        device property); ``None`` = uncapped (interpret
                        mode has no VMEM — it pays per-step interpreter
                        overhead instead).
    jnp_bw/pallas_bw  : effective streamed bandwidth each backend
                        sustains on this device (<= hbm_bw; on the
                        ``interpret`` pseudo-device these are host-RAM
                        figures calibrated against ``--tune`` data).
    jnp_launch        : per-dispatch overhead on the jnp side [s] —
                        one fused XLA kernel launch on compiled
                        devices; on the ``interpret`` pseudo-device it
                        is the eager per-primitive dispatch cost the
                        oracle pays ``jnp_kernels`` times (opcost
                        counts the oracle's primitive dispatches).
    pallas_call       : fixed pallas_call entry overhead [s].
    pallas_step       : per-grid-step cost [s] — compiled program
                        prologue, or the interpreter's per-step Python
                        loop on the pseudo-device.
    interp_op         : interpret mode only: per kernel-body primitive
                        per grid step [s] (numpy dispatch overhead);
                        0.0 on compiled devices.
    interpret         : True for the CPU-emulation pseudo-device.
    """

    name: str
    peak_flops: float
    hbm_bw: float
    ici_bw: float
    vmem_bytes: Optional[int] = 2 * 1024 * 1024
    jnp_bw: float = 0.0          # 0 -> defaults to hbm_bw
    pallas_bw: float = 0.0       # 0 -> defaults to hbm_bw
    jnp_launch: float = 2e-6
    pallas_call: float = 2e-6
    pallas_step: float = 1e-7
    interp_op: float = 0.0
    interpret: bool = False

    def bw(self, backend: str) -> float:
        eff = self.jnp_bw if backend == "jnp" else self.pallas_bw
        return eff or self.hbm_bw


DEVICES: Dict[str, Device] = {
    # TPU v5e per chip (bf16 peak) — the paper-model target.
    "tpu_v5e": Device(name="tpu_v5e", peak_flops=197e12, hbm_bw=819e9,
                      ici_bw=50e9),
    # TPU v4 per chip: larger part, same model structure.
    "tpu_v4": Device(name="tpu_v4", peak_flops=275e12, hbm_bw=1228e9,
                     ici_bw=100e9),
    # The interpret/CPU pseudo-device: Pallas kernels run under the
    # interpreter (numpy per grid step), jnp runs through XLA:CPU.  The
    # effective-rate and overhead constants are calibrated against the
    # committed .autotune/interpret.json measurements on this host
    # class (weighted relative-error fit over the 62-entry grid); they
    # exist to rank backends, not to predict wall time.  jnp_launch is
    # the eager per-primitive dispatch cost — the oracle's fixed
    # overhead scales with opcost's jnp_kernels dispatch counts.
    "interpret": Device(name="interpret", peak_flops=5e9, hbm_bw=10e9,
                        ici_bw=10e9, vmem_bytes=None,
                        jnp_bw=7e9, pallas_bw=9e9,
                        jnp_launch=70e-6, pallas_call=20e-6,
                        pallas_step=10e-6, interp_op=2e-6,
                        interpret=True),
}


#: ``jax.Device.device_kind`` -> roofline device name.  A compiled
#: policy on a chip missing here fails instead of borrowing another
#: chip's row.
DEVICE_KINDS: Dict[str, str] = {
    "TPU v5 lite": "tpu_v5e",
    "TPU v4": "tpu_v4",
}


def device_for_kind(kind: str) -> str:
    """The roofline device name of a ``jax.Device.device_kind``."""
    try:
        return DEVICE_KINDS[kind]
    except KeyError:
        raise ValueError(f"no roofline device for device_kind {kind!r}; "
                         f"known kinds: {sorted(DEVICE_KINDS)}") from None


def get_device(name: str) -> Device:
    try:
        return DEVICES[name]
    except KeyError:
        raise ValueError(f"unknown roofline device {name!r}; "
                         f"known: {sorted(DEVICES)}") from None


# Module constants (TPU v5e per chip); they alias the device-table entry.
PEAK_FLOPS = DEVICES["tpu_v5e"].peak_flops    # bf16
HBM_BW = DEVICES["tpu_v5e"].hbm_bw            # bytes/s
ICI_BW = DEVICES["tpu_v5e"].ici_bw            # bytes/s per link

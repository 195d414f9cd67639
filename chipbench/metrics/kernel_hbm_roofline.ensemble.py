"""Share of the HBM-bandwidth roofline over every device op of the
traced window (kernels layer: the Pallas kernels and XLA fusions).

The bytes each op declares in HBM (``chipbench.devtrace.op_bytes``)
over the chip's peak HBM bandwidth (``peaks.json``, by
``device_kind``) is the least time the ops could take; divided by
their summed device time.  Bandwidth is the bound: these kernels do a
few operations per byte."""


def read(rec):
    if rec.trace is None:
        return None
    roof = rec.trace.hbm_roofline(rec.peaks["hbm_bytes_per_s"])
    return None if roof is None else roof[0]

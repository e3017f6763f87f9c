"""Share of the device's busy time spent in compiled Pallas (Mosaic)
kernels; the rest is XLA glue: gathers, pads, concatenations, slices."""


def read(run):
    if run.trace is None or run.trace.mosaic_s <= 0:
        return None
    return 100.0 * run.trace.mosaic_s / run.trace.busy_s

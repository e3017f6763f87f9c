"""The whole step's share of the cell's chips' peak: useful FLOPs of the
requests completed in the profiled half of the window, over its length
times one chip's peak FLOP/s (``peaks.json``) times the cell's chips.
The work is taken as split evenly over the chips."""


def read(run):
    if run.peak is None or run.served == 0 or run.window_s <= 0:
        return None
    flops = run.served * run.work.flops
    return 100.0 * flops / (run.window_s * run.peak["flops_per_s"]
                            * run.chips)

"""The least time the cell's chips could take for the layers' work of the
requests completed in the profiled window (``work.py``: FLOPs over peak
or bytes over HBM bandwidth, whichever is larger, with one chip's peaks
times the cell's chips), over the busy time of one device.  The work is
taken as split evenly over the chips, and the trace's busy time is the
mean over devices."""
import work


def read(run):
    if run.peak is None or run.trace is None or run.served == 0:
        return None
    peak = {"flops_per_s": run.peak["flops_per_s"] * run.chips,
            "hbm_bytes_per_s": run.peak["hbm_bytes_per_s"] * run.chips}
    least = run.work.least_seconds(
        run.served, work.batches_for(run.served, run.max_batch), peak)
    return 100.0 * least / run.trace.busy_s

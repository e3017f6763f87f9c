"""The least time the chip could take for the layers' work of the requests
completed in the profiled window (``work.py``: FLOPs over peak or bytes
over HBM bandwidth, whichever is larger), over the device's busy time."""
import work


def read(run):
    if run.peak is None or run.trace is None or run.served == 0:
        return None
    least = run.work.least_seconds(
        run.served, work.batches_for(run.served, run.max_batch), run.peak)
    return 100.0 * least / run.trace.busy_s

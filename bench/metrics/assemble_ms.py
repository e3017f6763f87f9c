"""Mean host time to assemble one served batch: the executor's
``exec.assemble`` span (each sample to a device array, the stack, the zero
pad to ``max_batch``)."""


def read(run):
    durs = [e["dur"] for e in run.spans if e["name"] == "exec.assemble"]
    return sum(durs) / len(durs) / 1e3 if durs else None

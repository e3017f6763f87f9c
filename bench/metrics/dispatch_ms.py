"""Mean host time to dispatch every layer of one served batch: the
executor's ``exec.network`` span, which waits for no device work."""


def read(run):
    durs = [e["dur"] for e in run.spans if e["name"] == "exec.network"]
    return sum(durs) / len(durs) / 1e3 if durs else None

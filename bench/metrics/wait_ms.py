"""Mean time the host waits on the device per served batch, once every
layer is dispatched: the engine's ``serve.wait`` span."""


def read(run):
    durs = [e["dur"] for e in run.spans if e["name"] == "serve.wait"]
    return sum(durs) / len(durs) / 1e3 if durs else None

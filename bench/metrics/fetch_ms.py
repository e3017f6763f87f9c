"""Mean time to copy one served batch's request outputs to the host:
the engine's ``serve.fetch`` span."""


def read(run):
    durs = [e["dur"] for e in run.spans if e["name"] == "serve.fetch"]
    return sum(durs) / len(durs) / 1e3 if durs else None

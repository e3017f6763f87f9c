"""Mean duration of one served batch: the engine's ``serve.batch`` span,
which ends when the batch's outputs are on the host."""


def read(run):
    durs = [e["dur"] for e in run.spans if e["name"] == "serve.batch"]
    return sum(durs) / len(durs) / 1e3 if durs else None

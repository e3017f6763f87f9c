"""Mean host time to slice one served batch's output into its
requests' outputs: the executor's ``exec.split`` span."""


def read(run):
    durs = [e["dur"] for e in run.spans if e["name"] == "exec.split"]
    return sum(durs) / len(durs) / 1e3 if durs else None

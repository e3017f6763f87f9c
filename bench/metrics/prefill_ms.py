"""Mean time of one LM batch's prefill: the engine's ``lm.prefill`` span,
which ends when the prompt's logits are on the device."""


def read(run):
    durs = [e["dur"] for e in run.spans if e["name"] == "lm.prefill"]
    return sum(durs) / len(durs) / 1e3 if durs else None

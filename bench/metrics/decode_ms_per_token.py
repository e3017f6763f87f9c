"""Mean time of one decode step of an LM batch: each ``lm.decode`` span
(the decode steps through the wait on the last token) over its ``steps``."""


def read(run):
    per = [e["dur"] / e["attrs"]["steps"] for e in run.spans
           if e["name"] == "lm.decode" and e["attrs"].get("steps")]
    return sum(per) / len(per) / 1e3 if per else None

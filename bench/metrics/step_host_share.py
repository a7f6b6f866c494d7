"""Replica loop: the time inside the window's ``serve.step`` spans that lies
outside their ``serve.step.sync`` child (the host work the device waits
for each step) over the time inside those spans, in %.  The traced
stretch is left out."""
from bench import program_spans as PS


def read(run):
    a = PS.aligned(run)
    if a is None:
        return None
    steps = PS.window_steps(run, a["steps"])
    ids = {s[0] for s in steps}
    total = sum(s[4] - s[3] for s in steps)
    if total <= 0:
        return None
    sync = sum(s[4] - s[3] for s in a["spans"] if s[2] == "serve.step.sync" and s[1] in ids)
    return 100.0 * (total - sync) / total

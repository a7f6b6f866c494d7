"""Host runtime: the time inside the program's ``gc`` spans (collections of
generation 1 and 2) over the window's length, in %, the traced stretch
left out of both."""
from bench import program_spans as PS


def read(run):
    a = PS.aligned(run)
    if a is None:
        return None
    cut = PS.traced(run) or (0.0, 0.0)
    length = run.seconds - PS.overlap(*cut, 0.0, run.seconds)
    if length <= 0:
        return None
    paused = 0.0
    for s in a["spans"]:
        if s[2] == "gc":
            lo, hi = max(s[3], 0.0), min(s[4], run.seconds)
            if hi > lo:
                paused += hi - lo - PS.overlap(lo, hi, *cut)
    return 100.0 * paused / length

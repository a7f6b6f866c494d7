"""Decode launch: model operations of the active slots' tokens over the time
of the ``step`` calls in the window at the chip's bf16 peak, in %.  Parked
slots and padded expert capacity do not count."""
from bench.kernels import model


def read(run):
    steps = [s for s in run.rec.steps if s[0] >= 0.0 and s[1] <= run.seconds]
    t = sum(b - a for a, b, _ in steps)
    if not steps or t <= 0 or not run.peaks:
        return None
    flops = sum(model.decode(run.dims, ctx) for _, _, ctx in steps)
    return 100.0 * flops / (t * run.peaks["bf16_flops_per_s"])

"""Kernels: flash_decode's device time in the traced stretch against the
least time its useful work needs (``bench/kernels/flash_decode.py``), in %."""
from bench import trace
from bench.kernels import flash_decode as K
from bench.metrics_common import roofline, traced_steps


def read(run):
    steps = traced_steps(run)
    if run.trace is None or not run.peaks or not steps:
        return None
    secs = trace.kernel_seconds(run.trace, K.NAMES)
    if secs <= 0:
        return None
    dm = run.dims
    flops = bytes_ = 0.0
    for _, _, ctx in steps:
        f, b = K.work(ctx, dm["nq"], dm["nkv"], dm["hd"])
        flops += f * dm["layers"]
        bytes_ += b * dm["layers"]
    return roofline(flops, bytes_, secs, run.peaks)

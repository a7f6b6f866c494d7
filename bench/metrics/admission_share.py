"""Replica loop: share of the window spent inside ``admit`` calls, in %."""
from bench import stats


def read(run):
    return 100.0 * stats.span_seconds(run.rec.spans, "admit", run.seconds) / run.seconds

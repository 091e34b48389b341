"""Median host time of one ``Engine.step``: the decode program and the
host's sync on the sampled tokens."""
import statistics


def read(run, cell, peaks):
    d = [b - a for _, a, b, _ in run.spans.named("decode")]
    return 1e3 * statistics.median(d) if d else None

"""Median host time of one admission (``Engine.add_request`` with a
free slot, or ``finish`` that drains the queue): every chunked prefill
call of the prompt, through the first token on the host."""
import statistics


def read(run, cell, peaks):
    d = [b - a for _, a, b, _ in run.spans.named("admit")]
    return 1e3 * statistics.median(d) if d else None

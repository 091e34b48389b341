"""Per ``run_pipeline`` call, the host's self time in the program's
``hdarray.dispatch`` and ``hdarray.capture`` spans: the executor's
launch of each step program outside the scan, and the scan's lookup
(or build) and launch.  Read from the traced window's spans; nothing
to read where the program has none."""
from chipbench import program_spans


def read(run, cell, peaks):
    return program_spans.per_call_ms(run.trace,
                                     ("hdarray.dispatch", "hdarray.capture"))

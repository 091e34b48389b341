"""The host's time inside ``run_pipeline`` calls (the program's
``hdarray.run_pipeline`` spans) over the window's device busy time
(mean over the chips), in percent.  Calls are sent ahead, so under 100
the chip sets the pace of ``step_ms``; at 100 the host does.  Nothing
to read where the program has no such span or no device op ran."""
from chipbench import program_spans


def read(run, cell, peaks):
    calls = program_spans.call_seconds(run.trace)
    if not calls or not run.trace.busy_s:
        return None
    return 100.0 * calls / run.trace.busy_s

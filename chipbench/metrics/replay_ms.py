"""Per ``run_pipeline`` call, the host's time in the program's
``hdarray.replay`` span: the planner's metadata advance (plan cache
hit, commit replay, log) for every step the captured scan ran.  Read
from the traced window's spans; nothing to read where the program has
none."""
from chipbench import program_spans


def read(run, cell, peaks):
    # no program span nests in the replay: its self time is its time
    return program_spans.per_call_ms(run.trace, ("hdarray.replay",))

"""Per ``run_pipeline`` call, the host's self time in the program's
``hdarray.plan`` and ``hdarray.commit`` spans: Eqns (1)-(4), or the
§4.2 cache hit and fingerprint replay, of the steps run outside the
captured scan.  Read from the traced window's spans; nothing to read
where the program has none."""
from chipbench import program_spans


def read(run, cell, peaks):
    return program_spans.per_call_ms(run.trace,
                                     ("hdarray.plan", "hdarray.commit"))

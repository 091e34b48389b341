"""The decode program's share of its roofline: the least time the chip
could take for the traced decode steps (larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s, from ``cost.decode_step`` at each
step's live positions) over the device time of the decode programs
in the trace."""
from chipbench import cost

PROGRAM = r"decode_step"


def read(run, cell, peaks):
    runs = run.trace.module_runs(PROGRAM)
    steps = run.spans.named("decode")
    if not runs or len(runs) != len(steps):
        return None
    pk = cost.peak(peaks, run.devices)
    least = sum(cost.least_seconds(*cost.decode_step(cell.config, a["pos"]),
                                   pk) for _, _, _, a in steps)
    return 100.0 * least / sum(b - a for a, b in runs)

"""Model FLOPs of the traced decode steps (``cost.decode_step``, live
slots only) over the decode programs' device time, as a share of the
chip's peak bf16 FLOP/s."""
from chipbench import cost

PROGRAM = r"decode_step"


def read(run, cell, peaks):
    runs = run.trace.module_runs(PROGRAM)
    steps = run.spans.named("decode")
    if not runs or len(runs) != len(steps):
        return None
    flops = sum(cost.decode_step(cell.config, a["pos"])[0]
                for _, _, _, a in steps)
    pk = cost.peak(peaks, run.devices)
    return 100.0 * flops / sum(b - a for a, b in runs) / pk[
        "bf16_flops_per_s"]

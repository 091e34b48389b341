"""The whole sweep's share of the chip's peak: the least time a sweep
needs on a chip (larger of FLOPs over peak FLOP/s and bytes over peak
bytes/s, ``cost.jacobi_sweep``) over the traced window's time per
sweep."""
from chipbench import cost


def read(run, cell, peaks):
    sweeps = run.facts["calls"] * run.facts["sweeps_per_call"]
    pk = cost.peak(peaks, run.devices)
    least = cost.least_seconds(*cost.jacobi_sweep(cell.config,
                                                  run.facts["chips"]), pk)
    return 100.0 * least * sweeps / run.facts["window_s"]

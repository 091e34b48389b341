"""The stencil kernel's share of its roofline: the bytes one sweep needs
on a chip (``cost.jacobi_sweep``) over peak bytes/s, against the device
time per sweep of the operations named after the stencil
(``%jacobi_step.N``, the Pallas kernel), averaged over the chips.  The
executor's copies around the kernel are left out: ``mfu.jacobi`` reads
the whole sweep.  Nothing to read where no such operation ran."""
import re

from chipbench import cost, trace

KERNEL = re.compile(r"^%jacobi_step\b")


def read(run, cell, peaks):
    tr = run.trace
    if not tr.ops:
        return None
    busy = sum(trace.total(tr.busy(d, KERNEL.search))
               for d in tr.devices) / len(tr.devices)
    if busy == 0:
        return None
    sweeps = run.facts["calls"] * run.facts["sweeps_per_call"]
    pk = cost.peak(peaks, run.devices)
    least = cost.least_seconds(*cost.jacobi_sweep(cell.config,
                                                  run.facts["chips"]), pk)
    return 100.0 * least * sweeps / busy

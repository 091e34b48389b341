"""Per sweep, the device time of collective operations (the halo
exchange) during which no other operation runs on that chip, averaged
over the chips.  Nothing to read where no collective ran."""


def read(run, cell, peaks):
    if not run.trace.has_collectives():
        return None
    sweeps = run.facts["calls"] * run.facts["sweeps_per_call"]
    return 1e3 * run.trace.exposed_collective_s() / sweeps

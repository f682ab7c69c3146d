"""Mean host time per batch to repair the rows the device sampler could
not serve: stale-parent rows replayed from the cache's host mirror and
uncached rows sampled from the host CSR (the ``sample_repair`` span),
over the builds that ended inside the window."""
from benchlib.stages import mean_ms


def read(run):
    return mean_ms(run, "sample_repair")

"""Mean host time per batch to draw every hop's randomness, enqueue the
device sampler chain (with the H2D copy of the draws) and fetch the seeds'
labels while it runs (the ``sample_dispatch`` span), over the builds that
ended inside the window."""
from benchlib.stages import mean_ms


def read(run):
    return mean_ms(run, "sample_dispatch")

"""Mean host time per batch spent reading the device sampler chain back:
the wait on the device plus the copy to the host (the ``sample_sync``
span), over the builds that ended inside the window."""
from benchlib.stages import mean_ms


def read(run):
    return mean_ms(run, "sample_sync")

"""Mean host time per batch of the sampling accounting: the observer tap
and the topology traffic counts (the ``sample_account`` span), over the
builds that ended inside the window."""
from benchlib.stages import mean_ms


def read(run):
    return mean_ms(run, "sample_account")

"""Mean host time per batch to copy the miss rows out of the host feature
table into the staging buffer and zero its tail (the ``fill_miss`` span),
over the builds that ended inside the window."""
from benchlib.stages import mean_ms


def read(run):
    return mean_ms(run, "fill_miss")

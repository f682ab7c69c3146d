"""Mean host time per batch to split the unique ids into cache hits and
misses, count the feature traffic and lay out the padded maps (the
``fill_split`` span), over the builds that ended inside the window."""
from benchlib.stages import mean_ms


def read(run):
    return mean_ms(run, "fill_split")

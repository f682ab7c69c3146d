"""Mean host time per batch to find the sampled subgraph's unique vertex
ids and each level's positions into them (the ``spec_dedup`` span), over
the builds that ended inside the window."""
from benchlib.stages import mean_ms


def read(run):
    return mean_ms(run, "spec_dedup")

"""Mean host time to build one batch spec: seed draw, sampling, hit/miss
split and miss fill (the Prefetcher's ``prefetch_build`` span), over the
builds that ended inside the window."""
from benchlib.spans import in_window


def read(run):
    got = in_window(run, "prefetch_build")
    return sum(s.dur_ns for s in got) / len(got) / 1e6 if got else None

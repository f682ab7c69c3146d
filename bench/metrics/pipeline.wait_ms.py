"""Mean time the train loop waited on the prefetch queue for its next
batch (the Prefetcher's ``prefetch_get`` span), over the gets that ended
inside the window."""
from benchlib.spans import in_window


def read(run):
    got = in_window(run, "prefetch_get")
    return sum(s.dur_ns for s in got) / len(got) / 1e6 if got else None

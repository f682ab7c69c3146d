"""Mean time per batch of the synchronous host-to-device copy of the staged
miss rows (the ``h2d_staging`` span), over the copies that ended inside the
window."""
from benchlib.spans import in_window


def read(run):
    got = in_window(run, "h2d_staging")
    return sum(s.dur_ns for s in got) / len(got) / 1e6 if got else None

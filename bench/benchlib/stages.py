"""Means per batch over the program's stage spans that ended inside the
window: the leaves of the batch build and the consumer's finalize."""
from benchlib.spans import in_window


def mean_ms(run, name: str):
    """Mean duration of the ``name`` spans that ended in the window."""
    got = in_window(run, name)
    return sum(s.dur_ns for s in got) / len(got) / 1e6 if got else None


def self_ms(run, name: str, child: str):
    """Mean self time of the ``name`` spans that ended in the window: each
    one's duration less the ``child`` spans it contains on its thread."""
    got = in_window(run, name)
    if not got:
        return None
    kids = [s for s in run.spans if s.name == child]
    total = 0
    for s in got:
        total += s.dur_ns - sum(
            k.dur_ns for k in kids if k.thread == s.thread
            and s.t0_ns <= k.t0_ns and k.t1_ns <= s.t1_ns)
    return total / len(got) / 1e6

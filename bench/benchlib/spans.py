"""Selecting the program's spans by the measured window."""


def in_window(run, name: str):
    """Spans called ``name`` that ended inside the run's window."""
    t0, t1 = run.window_ns
    return [s for s in run.spans if s.name == name and t0 < s.t1_ns <= t1]

"""Share of the window call's unique feature requests served by the
unified cache (``TrafficCounter`` feature hits over requests)."""


def read(run):
    c = run.counter
    return 100.0 * c.feature_hits / c.feature_requests if c.feature_requests else None

"""Share of the window call's adjacency reads served by the cached
topology (``TrafficCounter`` topology hits over requests)."""


def read(run):
    c = run.counter
    return 100.0 * c.topo_hits / c.topo_requests if c.topo_requests else None

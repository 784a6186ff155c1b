"""Reader `latency_p95`: the 95th percentile (nearest rank), over every
operation of one `role` in the window, of the time its client call took
on the harness's clock, in ms. Nothing to read in a cell whose mix has
no operation of that role."""


def begin(spec):
    return None


def read(spec, before, run):
    return run["latency_p95_ms"].get(spec["role"])

"""Reader `trace_field`: one number of trace_reduce.reduce_trace's
result; nothing to read in a run that was not traced."""


def begin(spec):
    return None


def read(spec, before, run):
    if run["trace"] is None:
        return None
    return run["trace"][spec["field"]]

"""Reader `memory_peak`: peak bytes in use on the fullest chip after
the window (device.memory_stats()), in units of `bytes_per_unit`."""


def begin(spec):
    return None


def read(spec, before, run):
    if not run["memory_peak_bytes"]:
        return None
    return run["memory_peak_bytes"] / spec["bytes_per_unit"]

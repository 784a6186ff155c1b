"""Reader `counter_ratio`: deltas of METRICS counters over the window,
summed over every entity of the named type, divided by other counters'
deltas or by operations completed, times `scale`.

  "numerator":   [[entity_type, counter], ...]
  "denominator": {"counters": [[entity_type, counter], ...]}
               | {"ops": [kind, ...]}      operations completed of these kinds
               | {"constant": 1}
A denominator of 0 gives nothing to read.
"""

from pegasus_tpu.utils.metrics import METRICS


def _sums(spec):
    """(numerator, denominator counters) summed from one snapshot."""
    num, den = spec["numerator"], spec["denominator"].get("counters", [])
    totals = [0, 0]
    for ent in METRICS.snapshot():
        for i, pairs in enumerate((num, den)):
            for etype, name in pairs:
                if ent["type"] == etype and name in ent["metrics"]:
                    totals[i] += ent["metrics"][name]["value"]
    return totals


def begin(spec):
    return _sums(spec)


def read(spec, before, run):
    top, bottom = (after - b for after, b in zip(_sums(spec), before))
    d = spec["denominator"]
    if "ops" in d:
        bottom = sum(run["by_kind"].get(k, 0) for k in d["ops"])
    elif "constant" in d:
        bottom = d["constant"]
    if bottom == 0:
        return None
    return spec.get("scale", 1) * top / bottom

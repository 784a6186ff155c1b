"""Reader `counter_window`: METRICS counters summed over every entity
of the named type, as a count, a rate or a share.

  "numerator": [[entity_type, counter], ...]
  "per":       "window_s"                  a rate over the window's seconds
             | {"constant": n}
             | {"counters": [[entity_type, counter], ...]}
  "since":     "window" (the delta over the window; the default)
             | "start"  (the counters as they stand: since the process
                         started, so what the warm-up did counts too)
  "scale":     a factor (default 1)

A program that has not every counter of the numerator (the parent of
the PR that brought it) gives nothing to read; so does a denominator
of 0.
"""

from pegasus_tpu.utils.metrics import METRICS


def _sums(spec):
    """(numerator, denominator) sums from one snapshot, and the
    numerator's counters that no entity has."""
    num = [tuple(p) for p in spec["numerator"]]
    per = spec["per"]
    den = ([tuple(p) for p in per.get("counters", [])]
           if isinstance(per, dict) else [])
    totals, missing = [0, 0], set(num)
    for ent in METRICS.snapshot():
        for i, pairs in enumerate((num, den)):
            for etype, name in pairs:
                if ent["type"] == etype and name in ent["metrics"]:
                    totals[i] += ent["metrics"][name]["value"]
                    missing.discard((etype, name))
    return totals, missing


def begin(spec):
    return _sums(spec)[0]


def read(spec, before, run):
    after, missing = _sums(spec)
    if missing:
        return None
    if spec.get("since", "window") == "window":
        after = [a - b for a, b in zip(after, before)]
    top, bottom = after
    per = spec["per"]
    if per == "window_s":
        bottom = run["window_s"]
    elif "constant" in per:
        bottom = per["constant"]
    if not bottom:
        return None
    return spec.get("scale", 1) * top / bottom

"""Loader `ttl`: a retention table a week past its last compaction.

YCSB core records as reference.make_records makes them (its values for
the seed; no row of its own expired). Each record's rows were written
at one time, drawn for each record uniform over the `write_span_s`
seconds before `now`, and carry expire_ts = write time + the table's
`default_ttl` (`table_envs`): the rows written more than default_ttl
ago have expired at `now`, about half of them where the span is twice
the TTL, scattered over every block because the draw is per record.

No live row expires within `expiry_margin_s` of `now` (a run checks
every reply at one clock): the write times skip the slice
(now - default_ttl, now - default_ttl + expiry_margin_s], and the
span is drawn `expiry_margin_s` short so the rest stay uniform.
"""

import numpy as np

from benchmarks.reference import make_records


def write_ages(seed, config):
    """Seconds before `now` each record was written: whole seconds in
    [0, write_span_s), none in [ttl - margin, ttl)."""
    ttl = int(config["table_envs"]["default_ttl"])
    margin = int(config["expiry_margin_s"])
    span = int(config["write_span_s"])
    rng = np.random.default_rng([seed, 0x771])
    ages = rng.integers(0, span - margin, size=config["records"])
    return np.where(ages >= ttl - margin, ages + margin, ages)


def rows(seed, config, now):
    ttl = int(config["table_envs"]["default_ttl"])
    expire = (now + ttl - write_ages(seed, config)).tolist()
    fields = config["fields"]
    for n, (hk, sk, value, _ets) in enumerate(make_records(
            seed, config["records"], fields, config["field_length"], 0.0,
            now)):
        yield hk, sk, value, expire[n // fields]

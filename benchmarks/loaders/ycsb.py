"""Loader `ycsb`, the default where a config names no `loader`: YCSB
core records as reference.make_records makes them, `expired_share` of
the rows 100 s past their expiry.

A loader is benchmarks/loaders/<name>.py, named by a config's `loader`
and found by name. It has one function:

    rows(seed, config, now) -> iterable of (hashkey, sortkey, value, expire_ts)

the loaded table as of `now` (Pegasus epoch seconds; expire_ts 0 never
expires), the same rows for the same seed and `now`. A hashkey's rows
come together, and hashkeys, all of one length, come in key order:
Cluster.load takes each partition's first and last hashkey from the
stream. The harness calls it twice with one `now`: to load the store
and, after the window, to build the reference it checks the answers
against.
"""

from benchmarks.reference import make_records


def rows(seed, config, now):
    return make_records(seed, config["records"], config["fields"],
                        config["field_length"], config["expired_share"], now)

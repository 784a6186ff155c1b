"""Op kind `scan_record`: the rows of one YCSB record through the
paging scanner (ClusterClient.get_scanner on its hashkey), one scanner
an operation, under the sortkey prefix filter the spec gives (`field`
keeps every row of a record). The scanner's pages over a block the
partition has not yet evaluated under that filter go to the device
predicate whatever the block codec. No mix holds it: it is the
`trace_probe` of both, the operations a traced run sends to show the
device path at work.

args = (hk, options); reply = [(hashkey, sortkey, value)].
"""

import time

from pegasus_tpu.client.client import ScanOptions
from pegasus_tpu.ops.predicates import FT_MATCH_PREFIX

from benchmarks.generator import key_drawer
from benchmarks.reference import hashkey_of


def _options(spec):
    return ScanOptions(
        sort_key_filter_type=FT_MATCH_PREFIX,
        sort_key_filter_pattern=spec["sortkey_prefix"].encode())


def draw(rng, shape_rng, n, spec, ctx):
    records = key_drawer(spec["key"], ctx["n_records"])(rng, n)
    options = _options(spec)
    return [(hashkey_of(r), options) for r in records.tolist()]


def warm(hashkeys, spec, ctx):
    options = _options(spec)
    return [(hk, options) for hk in hashkeys]


def send(client, batch, ctx):
    out = []
    for hk, options in batch:
        t0 = time.perf_counter()
        rows = list(client.get_scanner(hk, options=options))
        out.append((rows, time.perf_counter() - t0))
    return out


def check(model, args, reply, now):
    hk, options = args
    want = [(hk, sk, value) for sk, value in model.record_rows(
        hk, now, options.sort_key_filter_pattern)]
    if [tuple(r[:3]) for r in reply] != want:
        return (f"scanner over record {hk!r} returned {len(reply)} rows, "
                f"the reference {len(want)}")
    return None


def apply(model, args):
    pass


def readback(args):
    return []

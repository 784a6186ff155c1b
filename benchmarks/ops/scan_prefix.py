"""Op kind `scan_prefix`: one pass over the table under a hashkey-prefix
filter, as the shell's count_data / copy_data send it:
ClusterClient.get_unordered_scanners(max_split_count, ScanOptions(
hash_key_filter_type=FT_MATCH_PREFIX, hash_key_filter_pattern=<tenant>,
batch_size=...)), every scanner drained in turn through the paging
path (get_scanner, then scan pages on the server-held context). The
tenant is a hashkey prefix of `prefix_bytes` bytes drawn by `key` over
those the loaded records have.

args = (pattern, options, max_split_count);
reply = [[(hashkey, sortkey, value)] a scanner], as returned.
"""

import time

from pegasus_tpu.client.client import ScanOptions
from pegasus_tpu.ops.predicates import FT_MATCH_PREFIX

from benchmarks.generator import key_drawer
from benchmarks.reference_prefix import n_tenants, prefix_of, prefix_rows


def draw(rng, shape_rng, n, spec, ctx):
    width = spec["prefix_bytes"]
    tenants = key_drawer(spec["key"], n_tenants(ctx["n_records"], width))(
        rng, n)
    out = []
    for t in tenants.tolist():
        pattern = prefix_of(t, width)
        out.append((pattern, ScanOptions(
            batch_size=spec["batch_size"],
            hash_key_filter_type=FT_MATCH_PREFIX,
            hash_key_filter_pattern=pattern), spec["max_split_count"]))
    return out


def send(client, batch, ctx):
    out = []
    for _pattern, options, split in batch:
        t0 = time.perf_counter()
        rows = [list(scanner) for scanner in
                client.get_unordered_scanners(split, options)]
        out.append((rows, time.perf_counter() - t0))
    return out


def check(model, args, reply, now):
    """S1 and S2: scanner i of n holds partitions i, i + n, ...; their
    rows, each partition's in key order, are the reference's, no row
    more and none twice."""
    pattern, _options, split = args
    want = prefix_rows(model, pattern, now)
    n = min(split, model.n_partitions)
    if len(reply) != n:
        return (f"scan under {pattern!r}: {len(reply)} scanners, "
                f"{n} partitions' worth expected")
    for i, rows in enumerate(reply):
        mine = [row for p in range(i, model.n_partitions, n)
                for row in want.get(p, ())]
        if rows != mine:
            return (f"scan under {pattern!r}: scanner {i} returned "
                    f"{len(rows)} rows, the reference {len(mine)}, or "
                    f"they differ in order or value")
    return None


def apply(model, args):
    pass


def readback(args):
    return []

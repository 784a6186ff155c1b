"""Op kind `scan`: the next n rows of a hashkey's partition from the
hashkey's first row, through ClusterClient.scan_multi (one_page, hash
validated). A reply that stops short of n (the server bounds the rows
one ranged read examines) is continued from its last key, as a client
would, in a further scan_multi call of the same window.

args = (pidx, start_key, n, first request); reply = [ScanResponse].
"""

import time

from pegasus_tpu.base.key_schema import generate_key, key_hash_parts
from pegasus_tpu.server.types import GetScannerRequest

from benchmarks.generator import key_drawer
from benchmarks.reference import hashkey_of


def _request(start_key, inclusive, n):
    return GetScannerRequest(start_key=start_key, start_inclusive=inclusive,
                             batch_size=n, validate_partition_hash=True,
                             one_page=True)


def draw(rng, shape_rng, n, spec, ctx):
    records = key_drawer(spec["key"], ctx["n_records"])(rng, n)
    if spec["length"]["dist"] != "uniform":
        raise ValueError(f"unknown length distribution {spec['length']!r}")
    lens = shape_rng.integers(spec["length"]["min"],
                              spec["length"]["max"] + 1, size=n)
    out = []
    for r, ln in zip(records.tolist(), lens.tolist()):
        hk = hashkey_of(r)
        start = generate_key(hk, b"")
        out.append((key_hash_parts(hk) % ctx["n_partitions"], start, ln,
                    _request(start, True, ln)))
    return out


def send(client, batch, ctx):
    """[(reply or None, seconds from the first call's start to the
    return of the call that completed the op)]."""
    pages = [[] for _ in batch]
    done = [0.0] * len(batch)
    failed = set()
    pending = [(i, a[3], a[2]) for i, a in enumerate(batch)]
    t0 = time.perf_counter()
    while pending:
        groups = {}
        for i, req, _remaining in pending:
            groups.setdefault(batch[i][0], []).append(req)
        replies = client.scan_multi(groups)
        t = time.perf_counter() - t0
        cursor = dict.fromkeys(groups, 0)
        nxt = []
        for i, _req, remaining in pending:
            p = batch[i][0]
            resp = replies[p][cursor[p]]
            cursor[p] += 1
            done[i] = t
            if resp.error != 0:
                failed.add(i)
                continue
            pages[i].append(resp)
            got = len(resp.kvs)
            if 0 < got < remaining:
                nxt.append((i, _request(resp.kvs[-1].key, False,
                                        remaining - got), remaining - got))
        pending = nxt
    return [(None if i in failed else pages[i], done[i])
            for i in range(len(batch))]


def check(model, args, reply, now):
    pidx, start, n, _req = args
    got = [(kv.key, kv.value) for resp in reply for kv in resp.kvs]
    want = model.scan(pidx, start, n, now)
    if got != want:
        return (f"scan of partition {pidx} from {start!r} for {n} returned "
                f"{len(got)} rows, the reference {len(want)}")
    return None


def apply(model, args):
    pass


def readback(args):
    return []

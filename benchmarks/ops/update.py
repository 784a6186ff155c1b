"""Op kind `update`: one field of one loaded YCSB record rewritten
(YCSB's writeallfields=false), as OP_PUT with no TTL through
ClusterClient.write_multi. The keys are drawn like `get`'s, over the
loaded records, so an update overwrites a row and hot rows are
overwritten many times in a run.

args = (hk, sk, value, (OP_PUT, (key, value, 0), partition hash), pidx,
        last); reply = status.

`last` is the run's table of read-back rows, (hk, sk) -> [hk, sk,
value]: made in `draw` on the run's `ctx` and carried in every args, so
two runs in one process (rehearse.py, the tests) share nothing.
"""

import time

from pegasus_tpu.base.key_schema import generate_key, key_hash_parts
from pegasus_tpu.rpc.codec import OP_PUT

from benchmarks.generator import key_drawer
from benchmarks.reference import hashkey_of, make_values, sortkey_of


def draw(rng, shape_rng, n, spec, ctx):
    last = ctx.setdefault("update_readback_rows", {})
    records = key_drawer(spec["key"], ctx["n_records"])(rng, n)
    fields = key_drawer(spec["field"], ctx["fields"])(rng, n)
    values = make_values(rng, n, ctx["field_length"])
    out = []
    for r, f, value in zip(records.tolist(), fields.tolist(), values):
        hk, sk = hashkey_of(r), sortkey_of(f)
        ph = key_hash_parts(hk, sk)
        out.append((hk, sk, value,
                    (OP_PUT, (generate_key(hk, sk), value, 0), ph),
                    ph % ctx["n_partitions"], last))
    return out


def send(client, batch, ctx):
    groups = {}
    for a in batch:
        groups.setdefault(a[4], []).append(a[3])
    t0 = time.perf_counter()
    replies = client.write_multi(groups)
    t = time.perf_counter() - t0
    cursor = dict.fromkeys(groups, 0)
    out = []
    for a in batch:
        status = replies[a[4]][cursor[a[4]]]
        cursor[a[4]] += 1
        out.append((status if status == 0 else None, t))
    return out


def check(model, args, reply, now):
    return (None if reply == 0
            else f"update of {args[0]!r}/{args[1]!r}: status {reply}")


def apply(model, args):
    model.put(args[0], args[1], args[2], 0)


def readback(args):
    """One row a key, kept at the key's last acknowledged value.

    The harness reads every returned row back after the window and
    expects the value it carries. An update overwrites: of a key
    updated five times only the last acknowledged value is still
    there, and the four before it are not lost writes. So the first
    acknowledged update of a key returns its row, as a list the harness
    keeps, and a later one (the harness calls this in replay order, for
    acknowledged operations only, and reads back after the last)
    rewrites that row's value and returns nothing more.
    """
    hk, sk, value, last = args[0], args[1], args[2], args[5]
    row = last.get((hk, sk))
    if row is None:
        row = last[(hk, sk)] = [hk, sk, value]
        return [row]
    row[2] = value
    return []

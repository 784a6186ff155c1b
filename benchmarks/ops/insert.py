"""Op kind `insert`: one new YCSB record, its rows as one OP_MULTI_PUT
(atomic on its hashkey), through ClusterClient.write_multi. Records are
numbered on from the loaded range, so no insert overwrites a row.

args = (hk, [(sk, value)], partition hash, pidx, request); reply = status.
"""

import time

from pegasus_tpu.base.key_schema import key_hash_parts
from pegasus_tpu.rpc.codec import OP_MULTI_PUT
from pegasus_tpu.server.types import KeyValue, MultiPutRequest

from benchmarks.reference import hashkey_of, make_values, sortkey_of


def draw(rng, shape_rng, n, spec, ctx):
    fields, length = ctx["fields"], ctx["field_length"]
    values = make_values(rng, n * fields, length)
    out = []
    for i in range(n):
        hk = hashkey_of(ctx["next_record"])
        ctx["next_record"] += 1
        rows = [(sortkey_of(j), values[i * fields + j])
                for j in range(fields)]
        ph = key_hash_parts(hk)
        out.append((hk, rows, ph, ph % ctx["n_partitions"], MultiPutRequest(
            hk, [KeyValue(sk, v) for sk, v in rows], 0)))
    return out


def send(client, batch, ctx):
    groups = {}
    for _hk, _rows, ph, pidx, req in batch:
        groups.setdefault(pidx, []).append((OP_MULTI_PUT, req, ph))
    t0 = time.perf_counter()
    replies = client.write_multi(groups)
    t = time.perf_counter() - t0
    cursor = dict.fromkeys(groups, 0)
    out = []
    for a in batch:
        status = replies[a[3]][cursor[a[3]]]
        cursor[a[3]] += 1
        out.append((status if status == 0 else None, t))
    return out


def check(model, args, reply, now):
    return None if reply == 0 else f"insert of {args[0]!r}: status {reply}"


def apply(model, args):
    for sk, value in args[1]:
        model.put(args[0], sk, value, 0)


def readback(args):
    return [(args[0], sk, value) for sk, value in args[1]]

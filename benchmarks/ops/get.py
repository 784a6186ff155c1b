"""Op kind `get`: one (hashkey, sortkey) through
ClusterClient.point_read_multi.

args = (hk, sk, encoded key, partition hash, pidx); reply = (err, value).
"""

import time

from pegasus_tpu.base.key_schema import generate_key, key_hash_parts

from benchmarks.generator import key_drawer
from benchmarks.reference import hashkey_of, sortkey_of

NOT_FOUND = 1   # StorageStatus.NOT_FOUND, the rrdb status of a miss


def draw(rng, shape_rng, n, spec, ctx):
    records = key_drawer(spec["key"], ctx["n_records"])(rng, n)
    fields = key_drawer(spec["field"], ctx["fields"])(rng, n)
    out = []
    for r, f in zip(records.tolist(), fields.tolist()):
        hk, sk = hashkey_of(r), sortkey_of(f)
        ph = key_hash_parts(hk, sk)
        out.append((hk, sk, generate_key(hk, sk), ph,
                    ph % ctx["n_partitions"]))
    return out


def send(client, batch, ctx):
    groups = {}
    for _hk, _sk, key, ph, pidx in batch:
        groups.setdefault(pidx, []).append(("get", key, ph))
    t0 = time.perf_counter()
    replies = client.point_read_multi(groups)
    t = time.perf_counter() - t0
    cursor = dict.fromkeys(groups, 0)
    out = []
    for a in batch:
        out.append((replies[a[4]][cursor[a[4]]], t))
        cursor[a[4]] += 1
    return out


def check(model, args, reply, now):
    hk, sk = args[0], args[1]
    err, value = reply
    want = model.get(hk, sk, now)
    if want is None:
        if err != NOT_FOUND:
            return (f"get {hk!r}/{sk!r}: err {err} where the reference has "
                    f"no row")
    elif err != 0 or value != want:
        return (f"get {hk!r}/{sk!r}: err {err}, value differs from the "
                f"reference")
    return None


def apply(model, args):
    pass


def readback(args):
    return []

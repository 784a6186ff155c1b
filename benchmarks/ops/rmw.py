"""Op kind `rmw`: YCSB's read-modify-write of one field of one loaded
record, written as a Pegasus user writes an update that must depend on
what was read: the row through ClusterClient.point_read_multi, then
check_and_set through ClusterClient.write_multi on the bytes read
(`CT_VALUE_BYTES_EQUAL`, or `CT_VALUE_NOT_EXIST` where the read found
no row), setting a fresh value (YCSB's buildSingleValue: independent
of the old one) with no TTL and `return_check_value`. A failed check
returns the row's value, and the RMW goes again on that value in the
call's next write_multi round, up to reference_rmw.ROUNDS rounds.
The keys are drawn like `get`'s, over the loaded records.

One call sends a window's RMWs: one point_read_multi of their rows,
then the rounds. An RMW's time is the sum of the calls it was in.

args = (hk, sk, new value, encoded key, partition hash, pidx, last);
reply = ((read err, read value), rounds, chain, index): `rounds` the
RMW's answers, (status, check value or None for no row) a round, or
(status,) where the reply was a bare status; `chain` the new values
of the call's RMWs of this row in submission order, and `index` this
one's place in it. The harness checks a whole call before it applies
any of it, so the chain is what lets `check` see the RMWs of the same
row sent before this one. `last` is the run's table of read-back rows,
as in ops/update.py.
"""

import time

from pegasus_tpu.base.key_schema import generate_key, key_hash_parts
from pegasus_tpu.rpc.codec import OP_CAS
from pegasus_tpu.server.types import CasCheckType, CheckAndSetRequest

from benchmarks.generator import key_drawer
from benchmarks.reference import hashkey_of, make_values, sortkey_of
from benchmarks.reference_rmw import OK, ROUNDS, TRY_AGAIN, rmw_rounds

NOT_FOUND = 1   # StorageStatus.NOT_FOUND, the rrdb status of a miss


def draw(rng, shape_rng, n, spec, ctx):
    last = ctx.setdefault("rmw_readback_rows", {})
    records = key_drawer(spec["key"], ctx["n_records"])(rng, n)
    fields = key_drawer(spec["field"], ctx["fields"])(rng, n)
    values = make_values(rng, n, ctx["field_length"])
    out = []
    for r, f, value in zip(records.tolist(), fields.tolist(), values):
        hk, sk = hashkey_of(r), sortkey_of(f)
        ph = key_hash_parts(hk, sk)
        out.append((hk, sk, value, generate_key(hk, sk), ph,
                    ph % ctx["n_partitions"], last))
    return out


def _cas(a, operand):
    if operand is None:
        check_type, operand = CasCheckType.CT_VALUE_NOT_EXIST, b""
    else:
        check_type = CasCheckType.CT_VALUE_BYTES_EQUAL
    return (OP_CAS, CheckAndSetRequest(
        a[0], a[1], int(check_type), operand, set_sort_key=a[1],
        set_value=a[2], return_check_value=True), a[4])


def _by_partition(batch, idxs, make):
    """{pidx: [make(i)]} in submission order, and where each i went."""
    groups, where = {}, {}
    for i in idxs:
        lst = groups.setdefault(batch[i][5], [])
        where[i] = len(lst)
        lst.append(make(i))
    return groups, where


def send(client, batch, ctx):
    n = len(batch)
    groups, where = _by_partition(
        batch, range(n), lambda i: ("get", batch[i][3], batch[i][4]))
    t0 = time.perf_counter()
    got = client.point_read_multi(groups)
    took = [time.perf_counter() - t0] * n
    reads = [got[a[5]][where[i]] for i, a in enumerate(batch)]
    operand = [value if err == 0 else None for err, value in reads]
    rounds = [[] for _ in batch]
    done = [False] * n
    pending = list(range(n))
    for _ in range(ROUNDS):
        if not pending:
            break
        groups, where = _by_partition(
            batch, pending, lambda i: _cas(batch[i], operand[i]))
        t0 = time.perf_counter()
        got = client.write_multi(groups)
        t = time.perf_counter() - t0
        left = []
        for i in pending:
            took[i] += t
            resp = got[batch[i][5]][where[i]]
            if isinstance(resp, int):   # a bare status: no check value
                rounds[i].append((resp,))
                done[i] = resp == OK
                continue
            seen = resp.check_value if resp.check_value_exist else None
            rounds[i].append((resp.error, seen))
            if resp.error == TRY_AGAIN:
                operand[i] = seen
                left.append(i)
            else:
                done[i] = resp.error == OK
        pending = left
    chains = {}
    for i, a in enumerate(batch):
        chains.setdefault((a[0], a[1]), []).append(i)
    out = []
    for i, a in enumerate(batch):
        chain = chains[(a[0], a[1])]
        reply = (reads[i], rounds[i], tuple(batch[j][2] for j in chain),
                 chain.index(i))
        out.append((reply if done[i] else None, took[i]))
    return out


def check(model, args, reply, now):
    hk, sk = args[0], args[1]
    (err, value), rounds, chain, index = reply
    before = model.get(hk, sk, now)
    if before is None:
        if err != NOT_FOUND:
            return (f"rmw {hk!r}/{sk!r}: read err {err} where the reference "
                    f"has no row")
    elif err != 0 or value != before:
        return (f"rmw {hk!r}/{sk!r}: read err {err}, value differs from the "
                f"reference")
    want = rmw_rounds(before, list(chain))[0][index]
    if len(rounds) != len(want) or any(
            got[0] != w[0] or (len(got) > 1 and got[1] != w[1])
            for got, w in zip(rounds, want)):
        return (f"rmw {hk!r}/{sk!r} ({index + 1} of {len(chain)} of its row "
                f"in the call): statuses {[r[0] for r in rounds]}, the "
                f"reference {[w[0] for w in want]}, or a check value differs")
    return None


def apply(model, args):
    model.put(args[0], args[1], args[2], 0)


def readback(args):
    """One row a key, kept at the key's last acknowledged value, as
    ops/update.py keeps it: an RMW overwrites its row."""
    hk, sk, value, last = args[0], args[1], args[2], args[6]
    row = last.get((hk, sk))
    if row is None:
        row = last[(hk, sk)] = [hk, sk, value]
        return [row]
    row[2] = value
    return []

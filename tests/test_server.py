"""PartitionServer tests: the full rrdb handler surface.

Modeled on the reference's server-layer unit tests
(src/server/test/pegasus_server_impl_test.cpp) — a real PartitionServer
against a scratch storage dir.
"""

import pytest

from pegasus_tpu.base.key_schema import generate_key, restore_key
from pegasus_tpu.base.value_schema import epoch_now
from pegasus_tpu.ops.predicates import FT_MATCH_PREFIX
from pegasus_tpu.server import (
    BatchGetRequest,
    CasCheckType,
    CheckAndMutateRequest,
    CheckAndSetRequest,
    FullKey,
    GetScannerRequest,
    IncrRequest,
    KeyValue,
    MultiGetRequest,
    MultiPutRequest,
    MultiRemoveRequest,
    Mutate,
    MutateOperation,
    PartitionServer,
    SCAN_CONTEXT_ID_COMPLETED,
    SCAN_CONTEXT_ID_NOT_EXIST,
)
from pegasus_tpu.utils.errors import StorageStatus

OK = int(StorageStatus.OK)
NOT_FOUND = int(StorageStatus.NOT_FOUND)
INCOMPLETE = int(StorageStatus.INCOMPLETE)
INVALID = int(StorageStatus.INVALID_ARGUMENT)
TRY_AGAIN = int(StorageStatus.TRY_AGAIN)


@pytest.fixture
def server(tmp_path):
    s = PartitionServer(str(tmp_path / "p0"))
    yield s
    s.close()


def put(s, hk, sk, v, ttl=0):
    return s.on_put(generate_key(hk, sk), v, ttl)


def test_put_get_remove(server):
    key = generate_key(b"u", b"s")
    assert server.on_put(key, b"hello") == OK
    assert server.on_get(key) == (OK, b"hello")
    assert server.on_remove(key) == OK
    assert server.on_get(key) == (NOT_FOUND, b"")


def test_ttl_visibility(server):
    key = generate_key(b"u", b"s")
    server.on_put(key, b"v", ttl_seconds=10_000)
    err, ttl = server.on_ttl(key)
    assert err == OK and 9_000 < ttl <= 10_000
    # eternal record: ttl == -1
    key2 = generate_key(b"u", b"s2")
    server.on_put(key2, b"v")
    assert server.on_ttl(key2) == (OK, -1)
    # expired record invisible to get
    key3 = generate_key(b"u", b"s3")
    server.write_service.put(key3, b"v", epoch_now() - 5,
                             server._next_decree())
    assert server.on_get(key3) == (NOT_FOUND, b"")
    assert server.metrics.counter("abnormal_read_count").value() >= 1


def test_multi_put_multi_get_point(server):
    req = MultiPutRequest(b"hk", [KeyValue(b"s%d" % i, b"v%d" % i)
                                  for i in range(5)])
    assert server.on_multi_put(req) == OK
    resp = server.on_multi_get(MultiGetRequest(
        b"hk", sort_keys=[b"s1", b"s3", b"nope"]))
    assert resp.error == OK
    assert [(kv.key, kv.value) for kv in resp.kvs] == [
        (b"s1", b"v1"), (b"s3", b"v3")]


def test_multi_get_range_and_filters(server):
    for i in range(20):
        put(server, b"hk", b"a%02d" % i, b"v%d" % i)
    for i in range(5):
        put(server, b"hk", b"b%02d" % i, b"w%d" % i)
    # range [a05, a10)
    resp = server.on_multi_get(MultiGetRequest(
        b"hk", start_sortkey=b"a05", stop_sortkey=b"a10"))
    assert resp.error == OK
    assert [kv.key for kv in resp.kvs] == [b"a%02d" % i for i in range(5, 10)]
    # inclusive stop
    resp = server.on_multi_get(MultiGetRequest(
        b"hk", start_sortkey=b"a05", stop_sortkey=b"a10",
        stop_inclusive=True))
    assert resp.kvs[-1].key == b"a10"
    # exclusive start
    resp = server.on_multi_get(MultiGetRequest(
        b"hk", start_sortkey=b"a05", stop_sortkey=b"a10",
        start_inclusive=False))
    assert resp.kvs[0].key == b"a06"
    # prefix filter on sortkey
    resp = server.on_multi_get(MultiGetRequest(
        b"hk", sort_key_filter_type=FT_MATCH_PREFIX,
        sort_key_filter_pattern=b"b"))
    assert [kv.key for kv in resp.kvs] == [b"b%02d" % i for i in range(5)]
    # reverse returns ascending order of the LAST n
    resp = server.on_multi_get(MultiGetRequest(b"hk", max_kv_count=3,
                                               reverse=True))
    assert [kv.key for kv in resp.kvs] == [b"b02", b"b03", b"b04"]


def test_multi_get_incomplete_on_count_limit(server):
    for i in range(10):
        put(server, b"hk", b"s%02d" % i, b"v")
    resp = server.on_multi_get(MultiGetRequest(b"hk", max_kv_count=4))
    assert resp.error == INCOMPLETE
    assert len(resp.kvs) == 4


def test_multi_get_no_value(server):
    put(server, b"hk", b"s", b"payload")
    resp = server.on_multi_get(MultiGetRequest(b"hk", no_value=True))
    assert resp.kvs[0].value == b""


def test_multi_remove(server):
    for i in range(4):
        put(server, b"hk", b"s%d" % i, b"v")
    err, count = server.on_multi_remove(
        MultiRemoveRequest(b"hk", [b"s0", b"s2"]))
    assert err == OK and count == 2
    assert server.on_multi_remove(MultiRemoveRequest(b"hk", []))[0] == INVALID
    err, n = server.on_sortkey_count(b"hk")
    assert (err, n) == (OK, 2)


def test_batch_get(server):
    put(server, b"h1", b"s1", b"v1")
    put(server, b"h2", b"s2", b"v2")
    resp = server.on_batch_get(BatchGetRequest(
        [FullKey(b"h1", b"s1"), FullKey(b"h2", b"s2"),
         FullKey(b"h3", b"nope")]))
    assert resp.error == OK
    assert [(d.hash_key, d.value) for d in resp.data] == [
        (b"h1", b"v1"), (b"h2", b"v2")]


def test_incr(server):
    key = generate_key(b"h", b"cnt")
    resp = server.on_incr(IncrRequest(key, 5))
    assert (resp.error, resp.new_value) == (OK, 5)
    resp = server.on_incr(IncrRequest(key, -2))
    assert resp.new_value == 3
    assert server.on_get(key) == (OK, b"3")
    # non-numeric value -> invalid
    key2 = generate_key(b"h", b"str")
    server.on_put(key2, b"abc")
    assert server.on_incr(IncrRequest(key2, 1)).error == INVALID
    # overflow -> invalid, value unchanged
    resp = server.on_incr(IncrRequest(key, (1 << 62)))
    assert resp.error == OK
    resp = server.on_incr(IncrRequest(key, (1 << 62)))
    assert resp.error == INVALID
    # ttl: reset then clear
    resp = server.on_incr(IncrRequest(key, 0, expire_ts_seconds=500))
    assert server.on_ttl(key)[1] > 0
    server.on_incr(IncrRequest(key, 0, expire_ts_seconds=-1))
    assert server.on_ttl(key)[1] == -1


def test_check_and_set(server):
    req = CheckAndSetRequest(
        b"h", b"k1", CasCheckType.CT_VALUE_NOT_EXIST, b"",
        set_value=b"first")
    assert server.on_check_and_set(req).error == OK
    assert server.on_get(generate_key(b"h", b"k1")) == (OK, b"first")
    # second attempt: NOT_EXIST now fails with TryAgain
    resp = server.on_check_and_set(req)
    assert resp.error == TRY_AGAIN
    # int compare + diff sort key + return check value
    server.on_put(generate_key(b"h", b"num"), b"42")
    req2 = CheckAndSetRequest(
        b"h", b"num", CasCheckType.CT_VALUE_INT_GREATER_OR_EQUAL, b"40",
        set_diff_sort_key=True, set_sort_key=b"winner", set_value=b"yes",
        return_check_value=True)
    resp = server.on_check_and_set(req2)
    assert resp.error == OK and resp.check_value == b"42"
    assert server.on_get(generate_key(b"h", b"winner")) == (OK, b"yes")
    # malformed int operand -> invalid
    req3 = CheckAndSetRequest(
        b"h", b"num", CasCheckType.CT_VALUE_INT_LESS, b"xx",
        set_value=b"no")
    assert server.on_check_and_set(req3).error == INVALID


def test_check_and_mutate(server):
    server.on_put(generate_key(b"h", b"guard"), b"ready")
    req = CheckAndMutateRequest(
        b"h", b"guard", CasCheckType.CT_VALUE_BYTES_EQUAL, b"ready",
        mutate_list=[
            Mutate(MutateOperation.MO_PUT, b"a", b"va"),
            Mutate(MutateOperation.MO_PUT, b"b", b"vb"),
            Mutate(MutateOperation.MO_DELETE, b"guard"),
        ])
    assert server.on_check_and_mutate(req).error == OK
    assert server.on_get(generate_key(b"h", b"a")) == (OK, b"va")
    assert server.on_get(generate_key(b"h", b"guard")) == (NOT_FOUND, b"")
    # failed check mutates nothing
    req2 = CheckAndMutateRequest(
        b"h", b"a", CasCheckType.CT_VALUE_BYTES_EQUAL, b"wrong",
        mutate_list=[Mutate(MutateOperation.MO_DELETE, b"a")])
    assert server.on_check_and_mutate(req2).error == TRY_AGAIN
    assert server.on_get(generate_key(b"h", b"a")) == (OK, b"va")
    # empty mutate list -> invalid
    req3 = CheckAndMutateRequest(
        b"h", b"a", CasCheckType.CT_NO_CHECK, b"", mutate_list=[])
    assert server.on_check_and_mutate(req3).error == INVALID


def test_scanner_paging(server):
    for i in range(25):
        put(server, b"hk%02d" % (i % 5), b"s%02d" % i, b"v%d" % i)
    seen = []
    resp = server.on_get_scanner(GetScannerRequest(batch_size=10))
    while True:
        seen.extend(kv.key for kv in resp.kvs)
        if resp.context_id == SCAN_CONTEXT_ID_COMPLETED:
            break
        resp = server.on_scan(resp.context_id)
        assert resp.error == OK
    assert len(seen) == 25
    assert seen == sorted(seen)  # total order over encoded keys
    # expired/unknown context
    resp = server.on_scan(99999)
    assert resp.context_id == SCAN_CONTEXT_ID_NOT_EXIST


def test_scanner_filters_and_count(server):
    for i in range(10):
        put(server, b"alpha", b"s%d" % i, b"v")
        put(server, b"beta", b"s%d" % i, b"v")
    resp = server.on_get_scanner(GetScannerRequest(
        hash_key_filter_type=FT_MATCH_PREFIX, hash_key_filter_pattern=b"al",
        batch_size=100))
    assert len(resp.kvs) == 10
    assert all(restore_key(kv.key)[0] == b"alpha" for kv in resp.kvs)
    # count-only scan
    resp = server.on_get_scanner(GetScannerRequest(only_return_count=True))
    assert resp.kv_count == 20 and resp.kvs == []


def test_scanner_range_bounds(server):
    for i in range(10):
        put(server, b"hk", b"s%02d" % i, b"v")
    start = generate_key(b"hk", b"s03")
    stop = generate_key(b"hk", b"s07")
    resp = server.on_get_scanner(GetScannerRequest(
        start_key=start, stop_key=stop, start_inclusive=False,
        stop_inclusive=True, batch_size=100))
    got = [restore_key(kv.key)[1] for kv in resp.kvs]
    assert got == [b"s04", b"s05", b"s06", b"s07"]


def test_scanner_return_expire_ts(server):
    put(server, b"hk", b"s", b"v", ttl=5000)
    resp = server.on_get_scanner(GetScannerRequest(return_expire_ts=True,
                                                   batch_size=10))
    assert resp.kvs[0].expire_ts_seconds > 0


def test_scan_validates_partition_hash(tmp_path):
    # two partitions of an 8-partition table; each scan only returns
    # records its partition owns
    from pegasus_tpu.base.key_schema import partition_index
    pc = 8
    servers = {i: PartitionServer(str(tmp_path / f"p{i}"), pidx=i,
                                  partition_count=pc) for i in range(2)}
    try:
        written = {0: 0, 1: 0}
        for i in range(60):
            hk = b"user_%d" % i
            pidx = partition_index(hk, pc)
            if pidx in servers:
                servers[pidx].on_put(generate_key(hk, b"s"), b"v")
                written[pidx] += 1
        from pegasus_tpu.storage.engine import WriteBatchItem
        for pidx, s in servers.items():
            # pretend some stale post-split data: write a foreign key
            s.engine.write_batch(
                [WriteBatchItem(0, generate_key(b"foreign_%d" % pidx, b"s"),
                                b"\x00\x00\x00\x00stale", 0)],
                s.engine.last_committed_decree + 1)
            resp = s.on_get_scanner(GetScannerRequest(
                batch_size=1000, validate_partition_hash=True))
            assert resp.error == OK
            keys = [restore_key(kv.key)[0] for kv in resp.kvs]
            from pegasus_tpu.base.key_schema import partition_index as pi
            assert all(pi(hk, pc) == pidx for hk in keys)
    finally:
        for s in servers.values():
            s.close()


def test_scan_after_flush_and_compact(server):
    for i in range(30):
        put(server, b"hk", b"s%02d" % i, b"v%d" % i)
    server.flush()
    for i in range(30, 40):
        put(server, b"hk", b"s%02d" % i, b"v%d" % i)
    server.manual_compact()
    err, n = server.on_sortkey_count(b"hk")
    assert (err, n) == (OK, 40)
    resp = server.on_multi_get(MultiGetRequest(b"hk"))
    assert len(resp.kvs) == 40


def test_capacity_units_accumulate(server):
    put(server, b"hk", b"s", b"v" * 5000)  # 2 write CUs
    assert server.cu.write_cu >= 2
    server.on_get(generate_key(b"hk", b"s"))
    assert server.cu.read_cu >= 2


def test_batched_multi_scan_matches_individual(tmp_path):
    """on_get_scanner_batch: shared-block dedup must return exactly what
    per-request serving returns (pagination included)."""
    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.base.value_schema import epoch_now, generate_value
    from pegasus_tpu.server.partition_server import PartitionServer
    from pegasus_tpu.server.types import (
        GetScannerRequest,
        SCAN_CONTEXT_ID_COMPLETED,
    )
    from pegasus_tpu.storage.engine import WriteBatchItem
    from pegasus_tpu.storage.wal import OP_PUT

    srv = PartitionServer(str(tmp_path / "p"), partition_count=1)
    now = epoch_now()
    items = []
    for i in range(900):
        ets = 0 if i % 7 else now - 50  # some expired records
        items.append(WriteBatchItem(
            OP_PUT, generate_key(b"h%03d" % (i % 30), b"s%04d" % i),
            generate_value(1, b"v%d" % i, ets), ets))
    srv.engine.write_batch(items, 1)
    srv.manual_compact()  # the columnar fast path qualifies

    reqs = [
        GetScannerRequest(start_key=generate_key(b"h00%d" % d, b""),
                          batch_size=25)
        for d in range(5)
    ] + [GetScannerRequest(start_key=b"", batch_size=40)] * 3
    batch = srv.on_get_scanner_batch(list(reqs))
    for req, got in zip(reqs, batch):
        solo = srv.on_get_scanner(req)
        assert got.error == solo.error
        assert [(kv.key, kv.value) for kv in got.kvs] == \
            [(kv.key, kv.value) for kv in solo.kvs], req
        assert (got.context_id == SCAN_CONTEXT_ID_COMPLETED) == \
            (solo.context_id == SCAN_CONTEXT_ID_COMPLETED)
        # paging continues correctly from the batch-created context
        if got.context_id >= 0:
            page2 = srv.on_scan(got.context_id)
            solo2 = srv.on_scan(solo.context_id)
            assert [(kv.key, kv.value) for kv in page2.kvs] == \
                [(kv.key, kv.value) for kv in solo2.kvs]
    srv.close()


def test_batched_scan_falls_back_off_fast_path(tmp_path):
    """An overlay (memtable) or filtered request serves per-request."""
    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.ops.predicates import FT_MATCH_PREFIX
    from pegasus_tpu.server.partition_server import PartitionServer
    from pegasus_tpu.server.types import GetScannerRequest

    srv = PartitionServer(str(tmp_path / "p"), partition_count=1)
    for i in range(50):
        srv.on_put(generate_key(b"hk", b"s%02d" % i), b"v%d" % i)
    # memtable overlay -> fallback path must still answer correctly
    reqs = [GetScannerRequest(start_key=generate_key(b"hk", b""),
                              batch_size=100),
            GetScannerRequest(start_key=b"",
                              sort_key_filter_type=FT_MATCH_PREFIX,
                              sort_key_filter_pattern=b"s0",
                              batch_size=100)]
    out = srv.on_get_scanner_batch(reqs)
    assert len(out[0].kvs) == 50
    assert len(out[1].kvs) == 10
    srv.close()


def test_batched_scan_overlay_merge_matches_individual(tmp_path):
    """A small write overlay merges host-side onto the device-filtered
    base: batched results must equal per-request serving, including
    shadowing (updates + tombstones) and pagination."""
    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.base.value_schema import generate_value
    from pegasus_tpu.server.partition_server import PartitionServer
    from pegasus_tpu.server.types import GetScannerRequest
    from pegasus_tpu.storage.engine import WriteBatchItem
    from pegasus_tpu.storage.wal import OP_PUT

    srv = PartitionServer(str(tmp_path / "p"), partition_count=1)
    items = [WriteBatchItem(
        OP_PUT, generate_key(b"h%02d" % (i % 10), b"s%04d" % i),
        generate_value(1, b"base%d" % i, 0), 0) for i in range(400)]
    srv.engine.write_batch(items, 1)
    srv.manual_compact()
    # overlay: updates shadowing base rows, fresh inserts, tombstones
    srv.on_put(generate_key(b"h00", b"s0000"), b"UPDATED")
    srv.on_put(generate_key(b"h00", b"s0000x"), b"INSERTED")
    srv.on_remove(generate_key(b"h01", b"s0011"))
    srv.engine.flush()  # some overlay in L0...
    srv.on_put(generate_key(b"h02", b"s0002"), b"NEWEST")  # ...some in mem

    reqs = [GetScannerRequest(start_key=generate_key(b"h0%d" % d, b""),
                              batch_size=17) for d in range(4)] \
        + [GetScannerRequest(start_key=b"", batch_size=33)]
    batch = srv.on_get_scanner_batch(list(reqs))
    for req, got in zip(reqs, batch):
        solo = srv.on_get_scanner(req)
        assert [(kv.key, kv.value) for kv in got.kvs] == \
            [(kv.key, kv.value) for kv in solo.kvs], req
        # paging equivalence
        g, s_ = got, solo
        while g.context_id >= 0 and s_.context_id >= 0:
            g = srv.on_scan(g.context_id)
            s_ = srv.on_scan(s_.context_id)
            assert [(kv.key, kv.value) for kv in g.kvs] == \
                [(kv.key, kv.value) for kv in s_.kvs]
        assert (g.context_id >= 0) == (s_.context_id >= 0)
    # the shadowed values surfaced
    all_rows = dict((kv.key, kv.value)
                    for kv in srv.on_get_scanner(
                        GetScannerRequest(start_key=b"",
                                          batch_size=1000)).kvs)
    assert all_rows[generate_key(b"h00", b"s0000")] == b"UPDATED"
    assert all_rows[generate_key(b"h02", b"s0002")] == b"NEWEST"
    assert generate_key(b"h01", b"s0011") not in all_rows
    srv.close()


# ---- the scan overlay by request range: the batched path, the per-
# request path and a plain sorted-dict model agree, case by case ---------


class _OverlayStore:
    """One partition with `n_base` rows compacted to L1 (blocks of 1,024
    rows) and whatever a case lays over them, beside a plain dict of
    what every write meant: key -> (user bytes | None, expire_ts)."""

    PARTS = 8  # so that a stale-split row has somewhere else to belong

    def __init__(self, path, n_base=400):
        from pegasus_tpu.base.key_schema import partition_index

        self.now = epoch_now()
        self.rows = {}
        self.hks = [b"h%03d" % i for i in range(400)
                    if partition_index(b"h%03d" % i, self.PARTS) == 0][:10]
        self.foreign = next(b"f%03d" % i for i in range(400)
                            if partition_index(b"f%03d" % i, self.PARTS))
        self.srv = PartitionServer(str(path), pidx=0,
                                   partition_count=self.PARTS)
        self.write([(self.hks[i % 10], b"s%04d" % i, b"base%d" % i, 0)
                    for i in range(n_base)])
        self.srv.manual_compact()

    def key(self, h, sk):
        return generate_key(self.hks[h] if isinstance(h, int) else h, sk)

    def write(self, rows):
        """[(hashkey | index into hks, sortkey, user bytes, expire_ts)]
        as one committed batch."""
        from pegasus_tpu.base.value_schema import generate_value
        from pegasus_tpu.storage.engine import WriteBatchItem
        from pegasus_tpu.storage.wal import OP_PUT

        items = []
        for h, sk, user, ets in rows:
            items.append(WriteBatchItem(
                OP_PUT, self.key(h, sk), generate_value(1, user, ets), ets))
            self.rows[self.key(h, sk)] = (user, ets)
        self.srv.engine.write_batch(
            items, self.srv.engine.last_committed_decree + 1)

    def remove(self, h, sk):
        assert self.srv.on_remove(self.key(h, sk)) == OK
        self.rows[self.key(h, sk)] = (None, 0)

    def expected(self, req):
        from pegasus_tpu.base.key_schema import partition_index

        vf = req.pushdown.value_filter_pattern if req.pushdown else b""
        out = []
        for key in sorted(self.rows):
            user, ets = self.rows[key]
            hk, sk = restore_key(key)
            if key < req.start_key:
                continue
            if req.stop_key and (key > req.stop_key or (
                    key == req.stop_key and not req.stop_inclusive)):
                continue
            if user is None or 0 < ets <= self.now:
                continue
            if (req.validate_partition_hash
                    and partition_index(hk, self.PARTS) != 0):
                continue
            if not sk.startswith(req.sort_key_filter_pattern):
                continue
            if vf not in user:
                continue
            out.append((key, user))
        return out

    def drained(self, resp):
        """Every row of a scan, its first page and the pages after."""
        rows = []
        while True:
            assert resp.error == OK
            rows += [(kv.key, kv.value) for kv in resp.kvs]
            if resp.context_id < 0:
                return rows
            resp = self.srv.on_scan(resp.context_id)


def _req(store, h=0, sk=b"", **kw):
    kw.setdefault("batch_size", 17)
    return GetScannerRequest(start_key=store.key(h, sk), **kw)


def _case_memtable_only(s):
    s.write([(0, b"s0000", b"UPDATED", 0), (0, b"s0000x", b"NEW", 0),
             (3, b"s9999", b"LAST", 0)])
    return [_req(s, 0), _req(s, 3), _req(s, 5)]


def _case_l0_under_a_newer_memtable_copy(s):
    s.write([(0, b"s0000", b"L0-OLD", 0), (0, b"s0005", b"L0-ONLY", 0),
             (1, b"s0001", b"L0-GONE", 0)])
    s.srv.engine.flush()
    s.remove(2, b"s0002")
    s.srv.engine.flush()                  # two L0 tables, newest first
    s.write([(0, b"s0000", b"MEM-NEW", 0), (2, b"s0002", b"BACK", 0)])
    s.remove(1, b"s0001")
    assert len(s.srv.engine.lsm.l0) == 2 and len(s.srv.engine.lsm.memtable)
    return [_req(s, 0), _req(s, 1), _req(s, 2)]


def _case_tombstone_over_a_base_row(s):
    s.remove(0, b"s0000")
    s.remove(0, b"s0010")
    return [_req(s, 0)]


def _case_expired_overlay_row_over_a_live_base_row(s):
    s.write([(0, b"s0000", b"EXPIRED", s.now - 50),
             (0, b"s0010", b"LIVES", s.now + 3600)])
    return [_req(s, 0, return_expire_ts=True)]


def _case_stale_split_row_under_validate(s):
    s.write([(s.foreign, b"s", b"STALE", 0), (0, b"s0000", b"OWNED", 0)])
    return [GetScannerRequest(start_key=b"", batch_size=1000,
                              validate_partition_hash=True),
            _req(s, 0, validate_partition_hash=True)]


def _case_sortkey_prefix_filter(s):
    s.write([(0, b"s0000", b"IN", 0), (0, b"t0000", b"OUT", 0),
             (0, b"s001", b"IN-TOO", 0)])
    s.remove(0, b"s0010")
    return [_req(s, 0, sort_key_filter_type=FT_MATCH_PREFIX,
                 sort_key_filter_pattern=b"s00")]


def _case_value_filter_leaves_a_hidden_shadow(s):
    from pegasus_tpu.ops.predicates import FT_MATCH_ANYWHERE
    from pegasus_tpu.ops.pushdown import PushdownSpec

    # base0 passes the filter and its newer copy does not: the old value
    # must not come back from under it
    s.write([(0, b"s0000", b"other", 0), (0, b"s0005", b"base-new", 0)])
    return [_req(s, 0, pushdown=PushdownSpec(
        value_filter_type=FT_MATCH_ANYWHERE, value_filter_pattern=b"base"))]


def _case_explicit_stop_key(s):
    s.write([(0, b"s0005", b"BEFORE", 0), (0, b"s0200", b"AT-STOP", 0),
             (0, b"s0300", b"AFTER", 0)])
    return [_req(s, 0, stop_key=s.key(0, b"s0200")),
            _req(s, 0, stop_key=s.key(0, b"s0200"), stop_inclusive=True)]


def _case_capped_plan_with_overlay_on_both_sides_of_the_frontier(s):
    # 3,000 base rows are three blocks, the first of which ends at
    # (hks[3], s1233); under the prefix filter s123 it answers 4 rows of
    # the 17 asked, so the page ends at the plan's frontier, with overlay
    # rows on either side of it
    run, = s.srv.engine.lsm.l1_runs
    assert len(run.blocks) == 3
    assert run.blocks[0].last_key == s.key(3, b"s1233")
    s.write([(0, b"s1230k", b"LOW", 0), (3, b"s1233k", b"JUST-PAST", 0),
             (9, b"s1239k", b"HIGH", 0)])
    s.remove(1, b"s1231")
    req = GetScannerRequest(start_key=b"", batch_size=17,
                            sort_key_filter_type=FT_MATCH_PREFIX,
                            sort_key_filter_pattern=b"s123")
    state = s.srv.plan_scan_batch([req])
    frontier = state["req_plans"][0][7]
    (window,), _entries = state["overlay"]
    assert window == [s.key(0, b"s1230k"), s.key(1, b"s1231")]
    assert window[-1] < frontier < s.key(3, b"s1233k")
    page, = s.srv.finish_scan_batch(state, s.srv.eval_planned_masks(state))
    assert [kv.value for kv in page.kvs] == [b"base1230", b"LOW",
                                             b"base1232", b"base1233"]
    assert page.context_id >= 0       # and goes on from the frontier
    return [req]


def _case_two_overlapping_requests_in_one_batch(s):
    s.write([(0, b"s0000", b"A", 0), (0, b"s0100", b"B", 0),
             (0, b"s0200", b"C", 0)])
    s.remove(0, b"s0110")
    return [_req(s, 0), _req(s, 0, b"s0050"), _req(s, 0),
            _req(s, 0, b"s0050", stop_key=s.key(0, b"s0150"))]


_OVERLAY_CASES = [
    _case_memtable_only,
    _case_l0_under_a_newer_memtable_copy,
    _case_tombstone_over_a_base_row,
    _case_expired_overlay_row_over_a_live_base_row,
    _case_stale_split_row_under_validate,
    _case_sortkey_prefix_filter,
    _case_value_filter_leaves_a_hidden_shadow,
    _case_explicit_stop_key,
    _case_capped_plan_with_overlay_on_both_sides_of_the_frontier,
    _case_two_overlapping_requests_in_one_batch,
]


@pytest.mark.parametrize(
    "case", _OVERLAY_CASES, ids=[c.__name__[6:] for c in _OVERLAY_CASES])
def test_scan_overlay_by_range_matches_solo_and_model(tmp_path, case):
    store = _OverlayStore(tmp_path / "p",
                          n_base=3000 if "capped" in case.__name__ else 400)
    try:
        reqs = case(store)
        srv = store.srv
        assert srv.plan_scan_batch(list(reqs)) is not None  # the path
        batch = srv.on_get_scanner_batch(list(reqs))
        for req, got in zip(reqs, batch):
            want = store.expected(req)
            assert want, req                     # no case compares nothing
            assert len(got.kvs) <= req.batch_size
            assert store.drained(got) == want, req
            assert store.drained(srv.on_get_scanner(req)) == want, req
            if req.return_expire_ts:
                ets = {kv.key: kv.expire_ts_seconds for kv in got.kvs}
                assert all(ets[k] == store.rows[k][1] for k in ets)
    finally:
        store.srv.close()


def test_scan_overlay_work_is_bound_by_the_request_range(tmp_path):
    """500 rows in the memtable, a capped scan whose range holds k of
    them: k rows are evaluated, once a batch however often the range is
    asked for, and only a request with overlay rows in its range takes
    the merge path."""
    from pegasus_tpu.utils.metrics import METRICS

    def counts():
        node = METRICS.entity("storage", "node")
        return (node.counter("overlay_rows_walked").value(),
                node.counter("scan_merge_path_requests").value())

    def moved(reqs):
        before = counts()
        out = store.srv.on_get_scanner_batch(list(reqs))
        assert all(r.error == OK and len(r.kvs) == 10 for r in out)
        return tuple(a - b for a, b in zip(counts(), before))

    store = _OverlayStore(tmp_path / "p", n_base=3000)
    try:
        base = sorted(store.rows)
        # one new row after every 6th base row: 500 of them
        store.write([(hk, sk + b"k", b"new", 0)
                     for hk, sk in map(restore_key, base[::6])][:500])
        assert len(store.srv.engine.lsm.memtable) == 500
        run, = store.srv.engine.lsm.l1_runs
        # a scan of 10 rows from the 100th base row plans the first block
        # alone (924 rows >= 2 * 16 + 64) and ends at its last key
        req = GetScannerRequest(start_key=base[100], batch_size=10)
        frontier = run.blocks[0].last_key + b"\x00"
        k = sum(1 for key in store.rows
                if key.endswith(b"k") and base[100] <= key < frontier)
        assert k == len(range(102, 1024, 6)) == 154
        assert moved([req]) == (k, 1)
        assert moved([req, req]) == (k, 2)
        # a stop_key before the first new row of the range: beside the
        # overlay, nothing evaluated, nothing merged
        beside = GetScannerRequest(start_key=base[103], stop_key=base[108],
                                   batch_size=10)
        assert [len(r.kvs) for r in
                store.srv.on_get_scanner_batch([beside])] == [5]
        before = counts()
        store.srv.on_get_scanner_batch([beside, beside])
        assert counts() == before
        # both in one batch: the counts of the one with a window
        assert moved([req, GetScannerRequest(
            start_key=base[2000], batch_size=10)])[1] == 2
    finally:
        store.srv.close()


def test_env_triggered_manual_compact(server):
    """Remote manual compaction rides the `manual_compact.once.
    trigger_time` app env (parity: pegasus_manual_compact_service.cpp
    MANUAL_COMPACT_ONCE_TRIGGER_TIME_KEY, written by the shell and
    delivered to replicas via config-sync): a fresh trigger compacts
    once (asynchronously), re-deliveries are idempotent, and a stale
    trigger older than the store's recorded finish time never
    re-compacts."""
    import time

    for i in range(50):
        put(server, b"mc%02d" % i, b"s", b"v%d" % i)
    lsm = server.engine.lsm
    assert len(lsm.memtable) == 50 and not lsm.l1_runs

    # unix-seconds trigger (the reference's `date +%s` convention)
    server.update_app_envs(
        {"manual_compact.once.trigger_time": str(int(time.time()))})
    deadline = time.monotonic() + 30
    while server._mc_running and time.monotonic() < deadline:
        time.sleep(0.02)
    assert not server._mc_running
    assert lsm.l1_runs and not len(lsm.memtable)
    gen = lsm.generation
    # the data survived, TTL semantics intact
    assert server.on_get(generate_key(b"mc07", b"s")) == (OK, b"v7")

    # config-sync re-delivery of the SAME env value: no second run
    server.update_app_envs(
        {"manual_compact.once.trigger_time":
         str(server._mc_trigger_seen)})
    time.sleep(0.1)
    assert lsm.generation == gen

    # restart-shaped staleness: a brand-new server over the same store
    # re-syncing the old trigger must see it already satisfied (the
    # finish time persists in the manifest, independent of the run set)
    assert lsm.compact_finish_time > 0
    server._mc_trigger_seen = 0
    server.update_app_envs(
        {"manual_compact.once.trigger_time":
         str(lsm.compact_finish_time)})
    time.sleep(0.1)
    assert lsm.generation == gen


def test_scans_stay_consistent_during_env_compaction(server):
    """The env-triggered compaction runs on its own thread while the
    node keeps serving: every concurrent scan must return the complete,
    correct row set before AND after the atomic generation publish —
    no torn reads, no errors from swapped-out runs."""
    import threading
    import time

    for i in range(3000):
        put(server, b"cc%04d" % (i % 300), b"s%02d" % (i // 300),
            b"val-%d" % (i % 300))
    server.engine.flush()
    for i in range(40):  # an overlay too
        put(server, b"ov%02d" % i, b"s", b"o")

    errors = []
    gens_seen = set()
    stop = threading.Event()
    lsm = server.engine.lsm
    gen_before = lsm.generation

    def scan_loop():
        try:
            while not stop.is_set():
                g = lsm.generation
                total = 0
                resp = server.on_get_scanner(
                    GetScannerRequest(start_key=b"", batch_size=5000))
                while True:
                    assert resp.error == OK, resp.error
                    total += len(resp.kvs)
                    if resp.context_id < 0:
                        break
                    resp = server.on_scan(resp.context_id)
                assert total == 3040, total
                gens_seen.add(g)
        except Exception as exc:  # noqa: BLE001 - surfaced below
            errors.append(repr(exc))

    t = threading.Thread(target=scan_loop)
    t.start()
    # warm one scan round before triggering so the overlap window isn't
    # eaten by first-touch compiles
    deadline = time.monotonic() + 60
    while not gens_seen and time.monotonic() < deadline:
        time.sleep(0.01)
    server.update_app_envs(
        {"manual_compact.once.trigger_time": str(int(time.time()))})
    while server._mc_running and time.monotonic() < deadline:
        time.sleep(0.01)
    # keep scanning until a post-publish round completes
    while lsm.generation not in gens_seen and \
            time.monotonic() < deadline and not errors:
        time.sleep(0.01)
    stop.set()
    t.join(timeout=10)
    assert not errors, errors
    assert not server._mc_running
    # rounds completed at BOTH the pre- and post-publish generation
    assert gen_before in gens_seen, (gen_before, gens_seen)
    assert lsm.generation > gen_before
    assert lsm.generation in gens_seen, (lsm.generation, gens_seen)
    assert lsm.l1_runs

"""The stack fill of the solo paging path (PartitionServer.
_static_keep_window): a look-ahead window with a mask miss, of a read
that may page on, tops its misses up to a whole stack of STACK_CHUNK
blocks with the range's next blocks whose mask is not cached, so a
partition of b blocks takes ceil(b / STACK_CHUNK) mask programs a fresh
pattern where it took one a page. Against the plain reference
(benchmarks/reference_prefix.py), on one partition of 32-row blocks
whose pages end after one block under a 32-row iteration budget. And
where a paging read's window and page end: at the block whose rows use
up the budget, each block entered, counted and looked up once.
"""

import math

import numpy as np
import pytest

from benchmarks.reference import Model, epoch_now, hashkey_of, sortkey_of
from benchmarks.reference_prefix import prefix_rows
from pegasus_tpu.base.key_schema import generate_key, restore_key
from pegasus_tpu.ops.predicates import FT_MATCH_PREFIX
from pegasus_tpu.ops.pushdown import PushdownSpec
from pegasus_tpu.server.partition_server import LOOKAHEAD, PartitionServer
from pegasus_tpu.server.scan_coordinator import STACK_CHUNK, stacked_block_eval
from pegasus_tpu.server.types import (
    SCAN_CONTEXT_ID_COMPLETED,
    GetScannerRequest,
)
from pegasus_tpu.utils.errors import StorageStatus
from pegasus_tpu.utils.flags import FLAGS
from pegasus_tpu.utils.metrics import METRICS

OK = int(StorageStatus.OK)
ROWS = 32           # a block
FIELDS = 10         # rows a record


def _counter(etype: str, name: str) -> int:
    return sum(ent["metrics"][name]["value"]
               for ent in METRICS.snapshot(entity_type=etype)
               if name in ent["metrics"])


class Moved:
    """What a read moved: mask programs, look-ups, filled blocks."""

    NAMES = (("engine", "mask_programs"), ("storage", "mask_cache_miss"),
             ("storage", "mask_cache_hit"), ("storage", "mask_fill_blocks"),
             ("storage", "scan_rows_evaluated"))

    def __enter__(self):
        self._before = [_counter(*n) for n in self.NAMES]
        return self

    def __exit__(self, *exc):
        (self.programs, self.miss, self.hit, self.fill, self.rows) = (
            _counter(*n) - b for n, b in zip(self.NAMES, self._before))


@pytest.fixture(scope="module", autouse=True)
def page_is_one_block():
    """A page ends after the first block that spends the budget."""
    old = FLAGS.get("pegasus.server", "rocksdb_max_iteration_count")
    FLAGS.set("pegasus.server", "rocksdb_max_iteration_count", ROWS)
    yield
    FLAGS.set("pegasus.server", "rocksdb_max_iteration_count", old)


def _build(path, n_blocks):
    """One partition of `n_blocks` L1 blocks of ROWS rows: records
    user%08d with FIELDS rows each, every seventh with a TTL to come."""
    srv = PartitionServer(str(path))
    srv.engine.lsm._block_capacity = ROWS
    model = Model(1)
    rng = np.random.default_rng(n_blocks)
    now = epoch_now()
    for i in range(n_blocks * ROWS):
        hk, sk = hashkey_of(i // FIELDS), sortkey_of(i % FIELDS)
        value = rng.integers(32, 127, 20, dtype=np.uint8).tobytes()
        ttl = 100_000 if i % 7 == 0 else 0
        assert srv.on_put(generate_key(hk, sk), value, ttl) == OK
        model.put(hk, sk, value, now + ttl if ttl else 0)
    srv.flush()
    srv.manual_compact()
    assert srv.engine.lsm.sorted_runs() is not None
    assert _blocks(srv) and len(_blocks(srv)) == n_blocks
    return srv, model


@pytest.fixture(scope="module")
def p40(tmp_path_factory):
    srv, model = _build(tmp_path_factory.mktemp("fill40"), 40)
    yield srv, model
    srv.close()


@pytest.fixture(scope="module")
def p21(tmp_path_factory):
    srv, model = _build(tmp_path_factory.mktemp("fill21"), 21)
    yield srv, model
    srv.close()


def _blocks(srv):
    """[(ckey, run, idx, BlockMeta)] of the L1 blocks in key order."""
    return [((run.path, bm.offset), run, i, bm)
            for run in srv.engine.lsm.l1_runs
            for i, bm in enumerate(run.blocks)]


def _req(pattern, **kw):
    return GetScannerRequest(hash_key_filter_type=FT_MATCH_PREFIX,
                             hash_key_filter_pattern=pattern, **kw)


def _drain(srv, req, between_pages=None):
    """Every page of a scanner: ([(hashkey, sortkey, value)], pages)."""
    rows, pages = [], 0
    resp = srv.on_get_scanner(req)
    while True:
        assert resp.error == OK
        pages += 1
        rows.extend(restore_key(kv.key) + (kv.value,) for kv in resp.kvs)
        if resp.context_id == SCAN_CONTEXT_ID_COMPLETED:
            return rows, pages
        if between_pages is not None:
            between_pages(pages)
        resp = srv.on_scan(resp.context_id)


def _want(model, pattern, below=None):
    rows = prefix_rows(model, pattern, epoch_now()).get(0, [])
    return [r for r in rows if below is None or r[:2] < below]


def _filter_key(pattern):
    return (FT_MATCH_PREFIX, pattern, 0, b"")


def _mask_of(srv, ckey, pattern):
    """The cached mask of a block for the scans' flavor (no hash
    validation on a partition count of 1), or None."""
    return srv._mask_cache.get(
        (ckey, srv.partition_version, False, _filter_key(pattern)))


def test_filled_masks_are_page_by_page_masks_and_rows_are_exact(p40):
    srv, model = p40
    pattern = b"user0000000"                # records 0..9: block 0..3
    with Moved() as m:
        rows, pages = _drain(srv, _req(pattern))
    assert rows == _want(model, pattern)
    assert pages >= len(_blocks(srv)) - 8 and m.fill > 0
    filled = {ckey: _mask_of(srv, ckey, pattern)
              for ckey, *_ in _blocks(srv)}
    assert all(mask is not None for mask in filled.values())
    # each block's mask as one program of its own computes it
    srv._mask_cache.clear()
    for ckey, run, i, _bm in _blocks(srv):
        dev = srv._device_cached_block(ckey, run.read_block(i))
        ((_tag, alone),) = stacked_block_eval(
            [(ckey, dev, srv.pidx)], False, srv.partition_version,
            filter_key=_filter_key(pattern))
        assert np.array_equal(np.asarray(alone), filled[ckey]), ckey


@pytest.mark.parametrize("pattern, stop_record", [
    (b"user000000", 37),                    # mid-block
    (b"user00000", 40)])                    # on a block's first key
def test_a_fill_never_crosses_the_stop_key(p40, pattern, stop_record):
    srv, model = p40
    stop = (hashkey_of(stop_record), b"")
    with Moved() as m:
        rows, _pages = _drain(srv, _req(pattern, stop_key=generate_key(
            *stop)))
    assert rows == _want(model, pattern, below=stop)
    assert m.fill > 0
    stop_key = generate_key(*stop)
    for ckey, _run, _i, bm in _blocks(srv):
        cached = _mask_of(srv, ckey, pattern) is not None
        assert cached == (bm.first_key < stop_key), (bm.first_key, stop)


@pytest.mark.parametrize("case", ["one_page", "no_miss"])
def test_no_fill_for_one_page_or_a_window_without_a_miss(p40, case):
    srv, model = p40
    pattern = {"one_page": b"user0000001", "no_miss": b"user0000002"}[case]
    if case == "no_miss":
        _drain(srv, _req(pattern))          # every mask now cached
    with Moved() as m:
        rows, pages = _drain(srv, _req(pattern, one_page=case == "one_page"))
    assert m.fill == 0
    if case == "one_page":
        # the guard that a one-page read keeps its window of LOOKAHEAD
        # blocks: with no fill it is the read's only device look-ahead
        assert pages == 1 and m.programs == 1 and m.miss == LOOKAHEAD
        assert rows == _want(model, pattern)[:len(rows)]
    else:
        assert (m.programs, m.miss) == (0, 0) and m.hit > 0
        assert rows == _want(model, pattern)


@pytest.mark.parametrize("server", ["p40", "p21"])
def test_a_fresh_pattern_takes_one_program_a_stack_of_blocks(request,
                                                             server):
    """Not one a page after the first stack, and at a block count that
    is no multiple of STACK_CHUNK the last stack is partly padding."""
    srv, model = request.getfixturevalue(server)
    b = len(_blocks(srv))
    assert b % STACK_CHUNK
    pattern = b"user0000003"
    with Moved() as m:
        rows, pages = _drain(srv, _req(pattern))
    assert rows == _want(model, pattern)
    assert pages >= b - 8
    assert m.programs == math.ceil(b / STACK_CHUNK)
    assert m.miss + m.fill == b
    # a paging window is the page's one block: it misses once a stack
    assert m.miss == m.programs
    with Moved() as again:
        assert _drain(srv, _req(pattern))[0] == rows
    assert (again.programs, again.miss, again.fill) == (0, 0, 0)


def test_a_publish_between_pages_leaves_no_old_filled_mask_in_use(
        tmp_path, monkeypatch):
    srv, model = _build(tmp_path / "p", 40)
    try:
        pattern = b"user000000"
        read = []
        keep_window = srv._static_keep_window

        def recorded(window, *a, **kw):
            read.append([ckey for ckey, *_ in window])
            return keep_window(window, *a, **kw)

        monkeypatch.setattr(srv, "_static_keep_window", recorded)
        old = {ckey for ckey, *_ in _blocks(srv)}
        published = []

        def compact_after_page_three(pages):
            if pages == 3:
                # a row of another tenant: a new generation of files
                assert srv.on_put(generate_key(b"zzz", b"s"), b"v") == OK
                srv.flush()
                srv.manual_compact()
                published.append(len(read))

        with Moved() as m:
            rows, _pages = _drain(srv, _req(pattern),
                                  between_pages=compact_after_page_three)
        assert rows == _want(model, pattern)
        assert published and not old & {ckey for ckey, *_ in _blocks(srv)}
        # every window after the publish reads the new generation's
        # blocks, whose masks it evaluates (and fills) afresh
        after = [ck for window in read[published[0]:] for ck in window]
        assert after and not old & set(after)
        assert m.fill > 16
    finally:
        srv.close()


def test_count_pushdown_counts_the_same_with_and_without_a_fill(p21,
                                                                monkeypatch):
    srv, model = p21
    pattern = b"user0000004"

    def count():
        req = _req(pattern, pushdown=PushdownSpec(aggregate="count"))
        resp = srv.on_get_scanner(req)
        while resp.context_id != SCAN_CONTEXT_ID_COMPLETED:
            assert resp.error == OK
            resp = srv.on_scan(resp.context_id)
        assert resp.error == OK and resp.pushdown_applied
        return resp.agg["count"]

    want = len(_want(model, pattern))
    assert want > 0
    with Moved() as filled:
        assert count() == want
    assert filled.fill > 0
    # the same pages again, each window's misses evaluated alone
    srv._mask_cache.clear()
    monkeypatch.setattr(srv, "_fill_blocks", lambda *a, **kw: [])
    with Moved() as unfilled:
        assert count() == want
    assert unfilled.fill == 0 and unfilled.programs > filled.programs


# ---- where a paging read's window and page end --------------------------


@pytest.fixture
def budget():
    """Sets the iteration budget for one test: a page of a read that
    pages on ends after the block whose rows use it up."""
    def set_to(rows):
        FLAGS.set("pegasus.server", "rocksdb_max_iteration_count", rows)
    yield set_to
    set_to(ROWS)


def _pages(srv, req):
    """Every page of a scanner: [(rows, resume key)], the resume key
    the one the server keeps for the next page, None on the last."""
    pages = []
    resp = srv.on_get_scanner(req)
    while True:
        assert resp.error == OK
        rows = [restore_key(kv.key) + (kv.value,) for kv in resp.kvs]
        if resp.context_id == SCAN_CONTEXT_ID_COMPLETED:
            pages.append((rows, None))
            return pages
        ctx = srv._scan_cache._contexts[resp.context_id]
        pages.append((rows, ctx.resume_key))
        resp = srv.on_scan(resp.context_id)


def _want_pages(model, pattern, budget_rows):
    """The reference's pages of a whole-partition read over blocks of
    ROWS rows each under a budget of `budget_rows`: a page is the
    blocks whose rows use the budget up and resumes right after the
    last of them; the page that reaches the range's end with budget
    left is the last, else an empty page ends the read."""
    keys = sorted(model.rows[0])        # the store's keys: all on disk
    step = -(-budget_rows // ROWS) * ROWS
    want = _want(model, pattern)
    pages = []
    for at in range(0, len(keys), step):
        first, last = keys[at], keys[min(at + step, len(keys)) - 1]
        rows = [r for r in want if first <= generate_key(*r[:2]) <= last]
        used_up = at + step <= len(keys)
        pages.append((rows, last + b"\x00" if used_up else None))
    if pages[-1][1] is not None:
        pages.append(([], None))
    return pages


PAGE_BUDGETS = [
    (ROWS, 1),                          # blocks of the budget's size
    (ROWS - 7, 1),                      # ... or larger: a block a page
    (2 * ROWS + 16, 3),                 # the block that uses it up
    (LOOKAHEAD * ROWS + 1, LOOKAHEAD + 1)]    # past one window


@pytest.mark.parametrize("budget_rows, blocks_a_page", PAGE_BUDGETS)
def test_a_paging_read_ends_its_window_and_page_where_the_budget_ends(
        p40, budget, budget_rows, blocks_a_page):
    srv, model = p40
    b = len(_blocks(srv))
    budget(budget_rows)
    pattern = b"user000000"                 # records 0..99: 1,000 rows
    with Moved() as m:
        pages = _pages(srv, _req(pattern))
    assert pages == _want_pages(model, pattern, budget_rows)
    assert len(pages) == -(-b // blocks_a_page) + (b % blocks_a_page == 0)
    # each block entered, counted and looked up once
    assert m.rows == b * ROWS
    assert m.hit + m.miss == b


@pytest.mark.parametrize("budget_rows", [rows for rows, _n in PAGE_BUDGETS])
def test_a_paging_count_reads_each_block_once(p21, budget, budget_rows):
    srv, model = p21
    budget(budget_rows)
    pattern = b"user0000001"
    req = _req(pattern, pushdown=PushdownSpec(aggregate="count"))
    with Moved() as m:
        resp = srv.on_get_scanner(req)
        while resp.context_id != SCAN_CONTEXT_ID_COMPLETED:
            assert resp.error == OK
            resp = srv.on_scan(resp.context_id)
    assert resp.error == OK and resp.pushdown_applied
    assert resp.agg["count"] == len(_want(model, pattern))
    assert m.rows == len(_blocks(srv)) * ROWS
    assert m.hit + m.miss == len(_blocks(srv))

"""Bulk block-level compaction (the GB/s path, storage/lsm.py).

Parity intent: manual CompactRange over a settled store
(pegasus_manual_compact_service.h:48) — here a pure-L1 store takes a
columnar rewrite with vectorized survivor gathers instead of the
per-record merge. These tests pin the path-specific behaviors: verbatim
re-serialization of untouched blocks, run-capacity rolling, TTL header
patching (and its absence at the raw-engine layer), and equivalence
with the merge path.
"""

import os

import numpy as np
import pytest

from pegasus_tpu.base.key_schema import generate_key
from pegasus_tpu.base.value_schema import (
    epoch_now,
    extract_expire_ts,
    generate_value,
)
from pegasus_tpu.storage.engine import StorageEngine, WriteBatchItem
from pegasus_tpu.storage.wal import OP_PUT


def _fill(eng, n, ets_of=lambda i: 0, prefix=b"hk", start_decree=1):
    items = [WriteBatchItem(OP_PUT, generate_key(b"%s%06d" % (prefix, i),
                                                 b"s"),
                            generate_value(1, b"v%d" % i, ets_of(i)),
                            ets_of(i))
             for i in range(n)]
    d = start_decree
    for off in range(0, n, 1000):
        eng.write_batch(items[off:off + 1000], decree=d)
        d += 1
    eng.flush()
    return d


def _merge_compact(eng, **kwargs):
    """manual_compact held to the per-record merge path (which a store
    the block path can read no longer takes by itself)."""
    eng.lsm.bulk_compact_snapshot = lambda frozen=False: None
    try:
        eng.manual_compact(**kwargs)
    finally:
        del eng.lsm.bulk_compact_snapshot


def test_bulk_path_engages_and_matches_merge(tmp_path):
    """Second compact (pure L1) must produce the same visible records
    the merge compact produced."""
    eng = StorageEngine(str(tmp_path / "e"))
    now = epoch_now()
    _fill(eng, 3000, ets_of=lambda i: (now - 10 if i % 10 == 0 else 0))
    assert eng.lsm.bulk_compact_eligible()      # a lone L0: eligible too
    _merge_compact(eng)             # the per-record path over the L0
    assert eng.lsm.bulk_compact_eligible()
    first = [(k, v, e) for k, v, e in eng.iterate()]
    assert len(first) == 2700       # 10% expired dropped
    eng.manual_compact()            # bulk path
    second = [(k, v, e) for k, v, e in eng.iterate()]
    assert first == second
    eng.close()


def test_untouched_blocks_survive_verbatim(tmp_path):
    """A no-op bulk compact preserves every record and the columnar
    layout (hash_lo carried over, values byte-identical)."""
    eng = StorageEngine(str(tmp_path / "e"))
    _fill(eng, 2500)
    eng.manual_compact()
    before = [(k, v, e) for k, v, e in eng.iterate()]
    runs_before = [t.path for t in eng.lsm.l1_runs]
    eng.manual_compact()            # bulk, nothing to drop
    after = [(k, v, e) for k, v, e in eng.iterate()]
    assert before == after
    # files were rewritten (new names), blocks intact with hash_lo
    assert [t.path for t in eng.lsm.l1_runs] != runs_before
    for run in eng.lsm.l1_runs:
        for i in range(len(run.blocks)):
            assert run.read_block(i).hash_lo is not None
    eng.close()


def test_run_capacity_rolling(tmp_path):
    """Bulk rewrite honors the L1 run size cap: many blocks roll into
    multiple output runs, in key order, nothing lost."""
    eng = StorageEngine(str(tmp_path / "e"), block_capacity=128)
    eng.lsm._l1_run_capacity = 500
    _fill(eng, 4000)
    eng.manual_compact()
    eng.manual_compact()            # bulk path with rolling
    assert len(eng.lsm.l1_runs) >= 8
    keys = [k for k, _v, _e in eng.iterate()]
    assert len(keys) == 4000
    assert keys == sorted(keys)
    # runs are non-overlapping and ordered
    for a, b in zip(eng.lsm.l1_runs, eng.lsm.l1_runs[1:]):
        assert a.last_key < b.first_key
    eng.close()


def test_default_ttl_patches_headers_only_for_server_tables(tmp_path):
    """The expire column is authoritative at the engine layer; the
    embedded value header is patched only when the engine is told values
    are pegasus-encoded (PartitionServer tables set the flag)."""
    now = epoch_now()
    # raw engine: values opaque -> header untouched, column updated
    eng = StorageEngine(str(tmp_path / "raw"))
    key = generate_key(b"h", b"s")
    eng.write_batch([WriteBatchItem(OP_PUT, key, b"xy", 0)], decree=1)
    eng.manual_compact(default_ttl=100, now=now)
    eng.manual_compact(default_ttl=100, now=now)  # bulk path too
    v, ets = eng.get(key)
    assert v == b"xy" and ets == now + 100
    eng.close()

    # encoded-values engine: both the column AND the header move
    eng2 = StorageEngine(str(tmp_path / "enc"),
                         values_carry_expire_header=True)
    eng2.write_batch([WriteBatchItem(
        OP_PUT, key, generate_value(1, b"payload", 0), 0)], decree=1)
    _merge_compact(eng2, default_ttl=100, now=now)
    v, ets = eng2.get(key)
    assert ets == now + 100 and extract_expire_ts(1, v) == now + 100
    eng2.manual_compact(default_ttl=0, now=now)     # bulk no-op keeps it
    v, ets = eng2.get(key)
    assert extract_expire_ts(1, v) == now + 100
    eng2.close()


def test_bulk_ttl_header_patch_and_reopen(tmp_path):
    """Bulk-path default-TTL rewrite patches the BE-u32 header via the
    vectorized scatter, and the result survives a cold reopen."""
    now = epoch_now()
    path = str(tmp_path / "e")
    eng = StorageEngine(path, values_carry_expire_header=True)
    _fill(eng, 1500)
    eng.manual_compact()                        # L0 -> pure L1
    eng.manual_compact(default_ttl=500, now=now)  # BULK ttl rewrite
    key = generate_key(b"hk000007", b"s")
    v, ets = eng.get(key)
    assert ets == now + 500 and extract_expire_ts(1, v) == now + 500
    eng.close()
    eng2 = StorageEngine(path, values_carry_expire_header=True)
    v, ets = eng2.get(key)
    assert ets == now + 500 and extract_expire_ts(1, v) == now + 500
    assert sum(1 for _ in eng2.iterate()) == 1500
    eng2.close()


def test_mixed_key_widths_bucket_correctly(tmp_path):
    """Blocks with different key-width buckets share one compaction wave
    without cross-contamination."""
    eng = StorageEngine(str(tmp_path / "e"))
    now = epoch_now()
    short = [WriteBatchItem(OP_PUT, generate_key(b"a%d" % i, b"s"),
                            generate_value(1, b"s%d" % i, 0), 0)
             for i in range(400)]
    long_ = [WriteBatchItem(
        OP_PUT, generate_key(b"zzzz-%064d" % i, b"sort-%032d" % i),
        generate_value(1, b"L%d" % i, now - 5 if i % 2 else 0),
        now - 5 if i % 2 else 0)
        for i in range(400)]
    eng.write_batch(short, decree=1)
    eng.write_batch(long_, decree=2)
    eng.flush()
    eng.manual_compact()
    eng.manual_compact()   # bulk across two width buckets
    rows = list(eng.iterate())
    assert sum(1 for k, _v, _e in rows if k[2:3] == b"a") == 400
    # half the long keys were expired and dropped
    assert len(rows) == 400 + 200
    eng.close()


def test_rules_and_stale_split_through_bulk(tmp_path):
    """Ruleset delete + stale-split drop both work through the bulk
    path (fused program), matching host-side expectations."""
    from pegasus_tpu.base.key_schema import key_hash
    from pegasus_tpu.ops.compaction_rules import compile_rules

    eng = StorageEngine(str(tmp_path / "e"))
    keys = [generate_key(b"user_%d" % i, b"s") for i in range(300)]
    eng.write_batch([WriteBatchItem(OP_PUT, k,
                                    generate_value(1, b"v", 0), 0)
                     for k in keys], decree=1)
    eng.flush()
    eng.manual_compact()
    # stale-split: keep only partition 3 of 8
    eng.manual_compact(validate_hash=True, pidx=3, partition_version=7)
    for k in keys:
        mine = (key_hash(k) & 7) == 3
        assert (eng.get(k) is not None) == mine
    # ruleset: delete hashkey prefix user_1 (bulk path again)
    rules = compile_rules([{"op": "delete_key", "rules": [
        {"type": "hashkey_pattern", "match": "prefix",
         "pattern": "user_1"}]}])
    eng.manual_compact(rules_filter=rules)
    for k, _v, _e in eng.iterate():
        assert not k[2:].startswith(b"user_1")
    eng.close()

"""The pattern window of match_filter: parity and the absence of a gather.

`match_filter` reads a row's prefix, postfix or any window with shifted
byte compares and a start selector. Parity: against the scalar
`host_match_filter` on ragged hashkeys and sortkeys, through the
multi-flavor (vmapped) program against the single-flavor one, and
through the compaction rules against the plain reference's rule.
Structure: no compiled mask, scan or rules program holds a gather.
"""

import zlib

import jax
import numpy as np
import pytest

from benchmarks.reference_rules import _rule_matches
from pegasus_tpu.base.key_schema import generate_key
from pegasus_tpu.ops.compaction import make_compaction_eval
from pegasus_tpu.ops.compaction_rules import compile_rules
from pegasus_tpu.ops.predicates import (
    FT_MATCH_ANYWHERE,
    FT_MATCH_POSTFIX,
    FT_MATCH_PREFIX,
    FT_NO_FILTER,
    FilterSpec,
    _multi_static_block_predicate,
    _scan_block_predicate,
    _static_block_predicate,
    host_match_filter,
    match_filter,
    multi_static_block_predicate,
    static_block_predicate,
)
from pegasus_tpu.ops.record_block import build_record_block

_ROWS = 256
# zero is a real key byte: a window must never read the row's padding
_ALPHABET = np.array([0, 97, 98], dtype=np.uint8)
_FTYPES = {"none": FT_NO_FILTER, "anywhere": FT_MATCH_ANYWHERE,
           "prefix": FT_MATCH_PREFIX, "postfix": FT_MATCH_POSTFIX}
_MATCH = {FT_MATCH_ANYWHERE: "anywhere", FT_MATCH_PREFIX: "prefix",
          FT_MATCH_POSTFIX: "postfix"}

_jit_match = jax.jit(match_filter, static_argnames=("filter_type",))


def _bytes(rng, n):
    return rng.choice(_ALPHABET, size=n).tobytes()


def _case(name, ftype, k, target, seed):
    """(hashkeys, sortkeys, pattern) of one parity case: the `target`
    region of each row, with the pattern written where `ftype` looks
    for it in half the rows that can hold it; every key fits in `k`."""
    rng = np.random.default_rng(seed)
    room = k - 2
    plen = {"planted": 3, "empty": 0, "longer_than_region": 10,
            "ends_at_width": 4, "postfix_before_row": 5,
            "bucket_wide": 32}[name]
    pattern = _bytes(rng, plen)
    hks, sks = [], []
    for _ in range(_ROWS):
        if name == "ends_at_width":
            # every key exactly `k` bytes: the region ends at the width
            other = int(rng.integers(0, 12)) if target == "sort" else 0
            n = room - other
        elif name == "postfix_before_row":
            # regions of 0-2 bytes: a postfix start 2 + len - 5 below 0
            other, n = int(rng.integers(0, 2)), int(rng.integers(0, 3))
        elif name == "bucket_wide":
            other = int(rng.integers(0, 4))
            n = int(rng.integers(0, room - other + 1))
        else:
            other, n = int(rng.integers(0, 12)), int(rng.integers(0, 12))
        region = bytearray(_bytes(rng, n))
        if plen and n >= plen and rng.random() < 0.5:
            at = {FT_MATCH_PREFIX: 0, FT_MATCH_POSTFIX: n - plen}.get(
                ftype, int(rng.integers(0, n - plen + 1)))
            region[at:at + plen] = pattern
        region, other = bytes(region), _bytes(rng, other)
        hks.append(region if target == "hash" else other)
        sks.append(other if target == "hash" else region)
    return hks, sks, pattern


_CASES = ["planted", "empty", "longer_than_region", "ends_at_width",
          "postfix_before_row", "bucket_wide"]


@pytest.mark.parametrize("k", [32, 64])
@pytest.mark.parametrize("target", ["hash", "sort"])
@pytest.mark.parametrize("case", _CASES)
@pytest.mark.parametrize("ftype", list(_FTYPES), ids=list(_FTYPES))
def test_window_matches_host(ftype, case, target, k):
    ft = _FTYPES[ftype]
    seed = zlib.crc32(f"{ftype}/{case}/{target}/{k}".encode())
    hks, sks, pattern = _case(case, ft, k, target, seed)
    keys = [generate_key(hk, sk) for hk, sk in zip(hks, sks)]
    block = build_record_block(keys, [0] * len(keys), key_width=k)
    assert block.key_width == k
    if case == "ends_at_width":
        assert all(len(key) == k for key in keys)
    spec = FilterSpec.make(ft, pattern)
    hkl = np.asarray(block.hashkey_len)
    kl = np.asarray(block.key_len)
    if target == "hash":
        start, length = np.full_like(kl, 2), hkl
    else:
        start, length = 2 + hkl, kl - 2 - hkl
    got = np.asarray(_jit_match(block.keys, start, length, spec.pattern,
                                spec.pattern_len, filter_type=ft))
    want = [host_match_filter(hk if target == "hash" else sk, ft, pattern)
            for hk, sk in zip(hks, sks)]
    assert got.tolist() == want
    if ft != FT_NO_FILTER and (case == "planted"
                               or case == "bucket_wide" and k == 64):
        assert 0 < sum(want) < len(want)


_FLAVORS = [(b"a", b""), (b"ab", b"b"), (b"", b"a\x00"), (b"\x00a", b"ba"),
            (b"b" * 32, b"a")]


@pytest.mark.parametrize("k", [32, 64])
@pytest.mark.parametrize("hash_ft,sort_ft", [
    (FT_MATCH_PREFIX, FT_NO_FILTER), (FT_MATCH_POSTFIX, FT_MATCH_ANYWHERE),
    (FT_MATCH_ANYWHERE, FT_MATCH_PREFIX), (FT_NO_FILTER, FT_MATCH_POSTFIX)])
def test_multi_flavor_matches_single(hash_ft, sort_ft, k):
    rng = np.random.default_rng(k + 7 * hash_ft + sort_ft)
    keys = [generate_key(_bytes(rng, int(rng.integers(0, 8))),
                         _bytes(rng, int(rng.integers(0, 8))))
            for _ in range(_ROWS)]
    block = build_record_block(keys, [0] * len(keys), key_width=k)
    filters = [(FilterSpec.make(hash_ft, hp), FilterSpec.make(sort_ft, sp))
               for hp, sp in _FLAVORS]
    multi = multi_static_block_predicate(block, filters, True, 0, 1)
    for i, (hf, sf) in enumerate(filters):
        single = np.asarray(static_block_predicate(
            block, hf, sf, validate_hash=True, pidx=0, partition_version=1))
        assert multi[i].tolist() == single.tolist()
        hkl = np.asarray(block.hashkey_len)
        owned = (np.asarray(block.hash_lo) & 1) == 0
        want = [bool(owned[r]) and host_match_filter(key[2:2 + hkl[r]],
                                                     hash_ft, hf.raw)
                and host_match_filter(key[2 + hkl[r]:], sort_ft, sf.raw)
                for r, key in enumerate(keys)]
        assert single.tolist() == want


@pytest.mark.parametrize("pattern", ["a", "ba\x00", ""],
                         ids=["one_byte", "three_bytes", "empty"])
@pytest.mark.parametrize("match", ["anywhere", "prefix", "postfix"])
@pytest.mark.parametrize("kind", ["hashkey_pattern", "sortkey_pattern"])
def test_rule_matches_reference(kind, match, pattern):
    rng = np.random.default_rng(len(pattern) + 3 * len(match) + len(kind))
    rows = [(_bytes(rng, int(rng.integers(0, 10))),
             _bytes(rng, int(rng.integers(0, 10)))) for _ in range(_ROWS)]
    rule = {"type": kind, "pattern": pattern, "match": match}
    rules_filter = compile_rules([{"op": "delete_key", "rules": [rule]}])
    keys = [generate_key(hk, sk) for hk, sk in rows]
    drop, _ets = rules_filter(keys, np.zeros(len(keys), np.uint32), 100)
    want = [_rule_matches(rule, hk, sk, 0, 100) for hk, sk in rows]
    assert drop.tolist() == want
    if not pattern:
        assert not any(want)      # an empty rule pattern matches nothing


def _compiled_text(name, ft):
    """The CPU-compiled HLO of one predicate program over a block of
    256 rows, each filter `ft` with a 6-byte pattern."""
    keys = [generate_key(b"user%04d" % i, b"field%d" % (i % 10))
            for i in range(256)]
    block = build_record_block(keys, [0] * len(keys), key_width=32)
    pat = FilterSpec.make(ft, b"user00")
    u32 = np.uint32
    cols = (block.keys, block.key_len, block.hashkey_len)
    if name == "static":
        lowered = _static_block_predicate.lower(
            *cols, block.valid, pat.pattern, pat.pattern_len,
            pat.pattern, pat.pattern_len, u32(0), u32(63),
            hash_filter_type=ft, sort_filter_type=ft, validate_hash=True,
            hash_lo=block.hash_lo, use_hash_lo=True, pack=True)
    elif name == "scan":
        lowered = _scan_block_predicate.lower(
            *cols, block.expire_ts, block.valid, u32(5), pat.pattern,
            pat.pattern_len, pat.pattern, pat.pattern_len, u32(0), u32(63),
            hash_filter_type=ft, sort_filter_type=ft, validate_hash=True,
            hash_lo=block.hash_lo, use_hash_lo=True)
    elif name == "multi":
        pats = np.stack([np.asarray(pat.pattern)] * 4)
        plens = np.full(4, 6, np.int32)
        lowered = _multi_static_block_predicate.lower(
            *cols, block.valid, pats, plens, pats, plens, u32(0), u32(63),
            hash_filter_type=ft, sort_filter_type=ft, validate_hash=True,
            hash_lo=block.hash_lo, use_hash_lo=True)
    else:
        rules = compile_rules([{"op": "delete_key", "rules": [
            {"type": "hashkey_pattern", "pattern": "user00",
             "match": _MATCH[ft]},
            {"type": "sortkey_pattern", "pattern": "field9",
             "match": _MATCH[ft]}]}])
        lowered = make_compaction_eval(rules.operations).lower(
            *cols, block.expire_ts, block.valid, block.hash_lo, u32(5),
            u32(0), u32(0), u32(63), validate_hash=True, use_hash_lo=True,
            want_ets=False, pack=True)
    return lowered.compile().as_text()


@pytest.mark.parametrize("ftype", ["anywhere", "prefix", "postfix"])
@pytest.mark.parametrize("program", ["static", "scan", "multi", "rules"])
def test_compiled_program_has_no_gather(program, ftype):
    text = _compiled_text(program, _FTYPES[ftype])
    assert "compare" in text          # the window compares are there
    assert " gather(" not in text

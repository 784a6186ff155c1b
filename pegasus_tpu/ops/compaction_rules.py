"""User-specified compaction: declarative retention rules on device.

Parity: src/server/compaction_filter_rule.{h,cpp} +
compaction_operation.{h,cpp} (design doc
rfcs/2021-05-27-user-specified-compaction.md):

- rules: hashkey_pattern / sortkey_pattern (SMT match anywhere/prefix/
  postfix) and ttl_range (matches records whose expire_ts lies in
  [now+start_ttl, now+stop_ttl]; start==stop==0 matches no-TTL records,
  compaction_filter_rule.cpp:75-90).
- operations AND their rules (compaction_operation.h:77):
  delete_key drops matching records; update_ttl rewrites expire_ts with
  op types FROM_NOW (now+value), FROM_CURRENT (current expire_ts+value,
  no-op on no-TTL records), TIMESTAMP (expire at unix ts `value`)
  (compaction_operation.cpp:77-103).
- evaluation order: operations run in sequence; the first matching
  delete wins; updates apply where matched and not deleted.

The reference evaluates these per record in scalar C++ inside RocksDB's
compaction callback; here one jitted program evaluates an entire columnar
batch per ruleset. Rulesets are parsed from the same kind of JSON the
reference stores in the `user_specified_compaction` table env.
"""

from __future__ import annotations

import functools
import json
from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pegasus_tpu.base.value_schema import PEGASUS_EPOCH_BEGIN
from pegasus_tpu.ops.compaction import note_filter_program
from pegasus_tpu.ops.predicates import (
    FT_MATCH_ANYWHERE,
    FT_MATCH_POSTFIX,
    FT_MATCH_PREFIX,
    FilterSpec,
    match_filter,
)
from pegasus_tpu.ops.record_block import build_record_block

_MATCH_TYPES = {
    "anywhere": FT_MATCH_ANYWHERE,
    "prefix": FT_MATCH_PREFIX,
    "postfix": FT_MATCH_POSTFIX,
    # reference enum spellings (SMT_MATCH_*) accepted too
    "SMT_MATCH_ANYWHERE": FT_MATCH_ANYWHERE,
    "SMT_MATCH_PREFIX": FT_MATCH_PREFIX,
    "SMT_MATCH_POSTFIX": FT_MATCH_POSTFIX,
}

UTOT_FROM_NOW = "from_now"
UTOT_FROM_CURRENT = "from_current"
UTOT_TIMESTAMP = "timestamp"
_UTOT_ALIASES = {
    "from_now": UTOT_FROM_NOW, "UTOT_FROM_NOW": UTOT_FROM_NOW,
    "from_current": UTOT_FROM_CURRENT, "UTOT_FROM_CURRENT": UTOT_FROM_CURRENT,
    "timestamp": UTOT_TIMESTAMP, "UTOT_TIMESTAMP": UTOT_TIMESTAMP,
}


class Rule:
    """One predicate; device-evaluated over a whole block."""

    def __init__(self, spec: dict) -> None:
        self.kind = spec["type"]
        if self.kind in ("hashkey_pattern", "FRT_HASHKEY_PATTERN",
                         "sortkey_pattern", "FRT_SORTKEY_PATTERN"):
            self.kind = ("hashkey_pattern" if "hash" in self.kind.lower()
                         else "sortkey_pattern")
            pattern = spec["pattern"]
            if isinstance(pattern, str):
                pattern = pattern.encode()
            self.filter = FilterSpec.make(_MATCH_TYPES[spec["match"]],
                                          pattern)
        elif self.kind in ("ttl_range", "FRT_TTL_RANGE"):
            self.kind = "ttl_range"
            self.start_ttl = int(spec["start_ttl"])
            self.stop_ttl = int(spec["stop_ttl"])
        else:
            raise ValueError(f"unknown rule type {spec['type']!r}")

    def evaluate(self, keys, key_len, hashkey_len, expire_ts, now):
        if self.kind in ("hashkey_pattern", "sortkey_pattern"):
            # an empty pattern matches NOTHING here — the reference's
            # string_pattern_match returns false for empty patterns
            # (compaction_filter_rule.cpp:35), the OPPOSITE of the scan
            # path's validate_filter; without this, an empty-pattern
            # delete_key rule would wipe the table
            if int(self.filter.pattern_len) == 0:
                return jnp.zeros(keys.shape[0], dtype=bool)
        if self.kind == "hashkey_pattern":
            return match_filter(keys, jnp.full_like(key_len, 2), hashkey_len,
                                self.filter.pattern, self.filter.pattern_len,
                                self.filter.filter_type)
        if self.kind == "sortkey_pattern":
            start = 2 + hashkey_len
            return match_filter(keys, start, key_len - start,
                                self.filter.pattern, self.filter.pattern_len,
                                self.filter.filter_type)
        # ttl_range (compaction_filter_rule.cpp:75-90)
        no_ttl_match = ((expire_ts == 0)
                        & (self.start_ttl == 0) & (self.stop_ttl == 0))
        in_range = ((expire_ts >= now + jnp.uint32(self.start_ttl))
                    & (expire_ts <= now + jnp.uint32(self.stop_ttl)))
        return no_ttl_match | (in_range & (expire_ts != 0))


class Operation:
    def __init__(self, spec: dict) -> None:
        op = spec["op"] if "op" in spec else spec["type"]
        if op in ("delete_key", "COT_DELETE"):
            self.op = "delete_key"
        elif op in ("update_ttl", "COT_UPDATE_TTL"):
            self.op = "update_ttl"
            self.utot = _UTOT_ALIASES[spec["update_ttl_type"]]
            self.value = int(spec["value"])
        else:
            raise ValueError(f"unknown compaction op {op!r}")
        self.rules = [Rule(r) for r in spec["rules"]]
        if not self.rules:
            raise ValueError("compaction operation requires >= 1 rule")


def parse_rules(spec) -> List[Operation]:
    """Accepts a JSON string or a parsed list of operation dicts."""
    if isinstance(spec, (str, bytes)):
        spec = json.loads(spec)
    return [Operation(s) for s in spec]


def apply_rules_ops(operations, keys, key_len, hashkey_len, expire_ts,
                    valid, now):
    """Apply a parsed ruleset inside a jit: (drop, new_ets).

    Every operation evaluates against the ORIGINAL (pre-rules)
    expire_ts — the reference fixes existing_value before its op loop
    (key_ttl_compaction_filter.h:94-108); only the output ets
    accumulates updates. Shared by the per-batch wrapper below and the
    fused bulk-compaction program (ops/compaction.py)."""
    drop = jnp.zeros_like(valid)
    ets = expire_ts
    for op in operations:  # static unroll: ruleset structure is fixed
        matched = valid & ~drop
        for rule in op.rules:
            matched = matched & rule.evaluate(keys, key_len, hashkey_len,
                                              expire_ts, now)
        if op.op == "delete_key":
            drop = drop | matched
        else:
            if op.utot == UTOT_FROM_NOW:
                new_ts = now + jnp.uint32(op.value)
            elif op.utot == UTOT_FROM_CURRENT:
                # no-op for records without a TTL, judged on the
                # original value (compaction_operation.cpp:93-96)
                matched = matched & (expire_ts != 0)
                new_ts = expire_ts + jnp.uint32(op.value)
            else:  # UTOT_TIMESTAMP: expire at unix ts `value`
                new_ts = jnp.uint32(max(0, op.value - PEGASUS_EPOCH_BEGIN))
            ets = jnp.where(matched, new_ts, ets)
    return drop, ets


def compile_rules(spec) -> Callable:
    """Returns `rules_filter(keys, expire_ts, now) -> (drop, new_ets)`
    matching StorageEngine.manual_compact's hook signature; the predicate
    pipeline for the whole ruleset is one jitted device program. The
    parsed ruleset is exposed as `rules_filter.operations` so the bulk
    block-level compactor can fuse it into its own program.

    Compiled once per content: every replica of a table gets the same
    env, again at every config sync, and a jitted program of its own
    for each would be traced and lowered anew by each compaction."""
    if not isinstance(spec, (str, bytes)):
        spec = json.dumps(spec, sort_keys=True)
    return _compile_rules_text(spec)


@functools.lru_cache(maxsize=32)
def _compile_rules_text(spec) -> Callable:
    operations = parse_rules(spec)

    @jax.jit
    def _eval(keys, key_len, hashkey_len, expire_ts, valid, now):
        with jax.named_scope("pegasus_compact_rules_filter"):
            return apply_rules_ops(operations, keys, key_len, hashkey_len,
                                   expire_ts, valid, now)

    def rules_filter(keys: Sequence[bytes], expire_ts, now: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
        n = len(keys)
        # power-of-two capacity bucket: every distinct batch size would
        # otherwise compile its own XLA program — 64 partitions with 64
        # different record counts meant 64 compiles (observed 35x slower
        # than the TTL-only path on identical data)
        cap = 1024
        while cap < n:
            cap <<= 1
        block = build_record_block(list(keys), list(np.asarray(expire_ts)),
                                   capacity=cap)
        drop, ets = _eval(jnp.asarray(block.keys), jnp.asarray(block.key_len),
                          jnp.asarray(block.hashkey_len),
                          jnp.asarray(block.expire_ts),
                          jnp.asarray(block.valid), jnp.uint32(now))
        # key matrix + key_len, hashkey_len, expire_ts (4 B each) +
        # valid in; the mask and the rewritten expire_ts back
        note_filter_program(cap, cap * (block.keys.shape[1] + 13 + 5))
        return np.asarray(drop)[:n], np.asarray(ets)[:n]

    rules_filter.operations = tuple(operations)
    return rules_filter

"""The plain reference of user-specified compaction (Apache Pegasus
rfcs/2021-05-27-user-specified-compaction.md; compaction_filter_rule.cpp,
compaction_operation.cpp): what a correct compaction filter decides for
one row.

Imports nothing from pegasus_tpu. A ruleset is the JSON the table env
`user_specified_compaction` holds: a list of operations, each with the
rules it ANDs.

    {"op": "delete_key", "rules": [...]}
    {"op": "update_ttl", "update_ttl_type": "from_now" | "from_current"
                         | "timestamp", "value": seconds, "rules": [...]}
    {"type": "hashkey_pattern" | "sortkey_pattern", "pattern": "...",
     "match": "anywhere" | "prefix" | "postfix"}
    {"type": "ttl_range", "start_ttl": seconds, "stop_ttl": seconds}
"""

from __future__ import annotations

import json

PEGASUS_EPOCH_BEGIN = 1451606400  # 2016-01-01 00:00:00 UTC


def _pattern_matches(value: bytes, pattern: bytes, match: str) -> bool:
    if not pattern:
        return False        # an empty pattern matches nothing
    if match == "anywhere":
        return pattern in value
    if match == "prefix":
        return value.startswith(pattern)
    if match == "postfix":
        return value.endswith(pattern)
    raise ValueError(f"unknown match {match!r}")


def _rule_matches(rule: dict, hk: bytes, sk: bytes, ets: int,
                  now: int) -> bool:
    kind = rule["type"]
    if kind == "hashkey_pattern":
        return _pattern_matches(hk, rule["pattern"].encode(), rule["match"])
    if kind == "sortkey_pattern":
        return _pattern_matches(sk, rule["pattern"].encode(), rule["match"])
    if kind == "ttl_range":
        start, stop = int(rule["start_ttl"]), int(rule["stop_ttl"])
        if ets == 0:        # a row without TTL: only the range 0/0
            return start == 0 and stop == 0
        return now + start <= ets <= now + stop
    raise ValueError(f"unknown rule type {kind!r}")


class Rules:
    """A parsed ruleset."""

    def __init__(self, spec):
        if isinstance(spec, (str, bytes)):
            spec = json.loads(spec)
        self.operations = list(spec)
        for op in self.operations:
            if op["op"] not in ("delete_key", "update_ttl"):
                raise ValueError(f"unknown operation {op['op']!r}")
            if not op["rules"]:
                raise ValueError("an operation needs at least one rule")

    def matched(self, hk: bytes, sk: bytes, ets: int, now: int = 0):
        """"delete", the row's new expire_ts, or None (untouched).
        `ets` and `now` are seconds since the Pegasus epoch, 0 = no
        TTL. Every operation is judged on the row's original
        expire_ts; the first matching delete wins; of the matching
        updates of a row not deleted, the last one stands."""
        new_ets = None
        for op in self.operations:
            if not all(_rule_matches(r, hk, sk, ets, now)
                       for r in op["rules"]):
                continue
            if op["op"] == "delete_key":
                return "delete"
            how, value = op["update_ttl_type"], int(op["value"])
            if how == "from_now":
                new_ets = now + value
            elif how == "from_current":
                if ets != 0:        # no TTL to move
                    new_ets = ets + value
            elif how == "timestamp":    # a unix time
                new_ets = max(0, value - PEGASUS_EPOCH_BEGIN)
            else:
                raise ValueError(f"unknown update_ttl_type {how!r}")
        return new_ets

    def deletes(self, hk: bytes, sk: bytes, ets: int = 0,
                now: int = 0) -> bool:
        return self.matched(hk, sk, ets, now) == "delete"

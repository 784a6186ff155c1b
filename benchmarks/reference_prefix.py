"""The plain reference of a scan under a hashkey-prefix filter: the
rows a correct store returns to get_unordered_scanners(...,
hash_key_filter_type=FT_MATCH_PREFIX, hash_key_filter_pattern=p), one
list a partition, in the store's key order.

Imports nothing of the program: routing, key order and TTL are
reference.py's. Hashkeys are numbered (`user%08d`), so a pattern that
is `user` and some digits names a range of records, and the rows are
looked up record by record: the cost is that of the rows returned, not
of the table.
"""

from __future__ import annotations

from benchmarks.reference import Model, hashkey_of

_STEM = b"user"
_DIGITS = 8         # of hashkey_of's number


def prefix_of(tenant: int, prefix_bytes: int) -> bytes:
    """The hashkey prefix of `prefix_bytes` bytes that tenant number
    `tenant` owns: `user` and the leading digits of its records."""
    digits = prefix_bytes - len(_STEM)
    if not 0 < digits <= _DIGITS:
        raise ValueError(f"a tenant prefix has 5 to 12 bytes, not "
                         f"{prefix_bytes}")
    return _STEM + b"%0*d" % (digits, tenant)


def records_per_tenant(prefix_bytes: int) -> int:
    return 10 ** (_DIGITS - (prefix_bytes - len(_STEM)))


def n_tenants(n_records: int, prefix_bytes: int) -> int:
    """Tenants that own at least one of records 0..n_records-1."""
    return -(-n_records // records_per_tenant(prefix_bytes))


def records_of(pattern: bytes) -> range:
    """The record numbers whose hashkey starts with `pattern`."""
    digits = pattern[len(_STEM):]
    if (not pattern.startswith(_STEM) or not digits.isdigit()
            or len(digits) > _DIGITS):
        raise ValueError(f"{pattern!r} is not `user` and 1 to 8 digits")
    span = records_per_tenant(len(pattern))
    return range(int(digits) * span, (int(digits) + 1) * span)


def prefix_rows(model: Model, pattern: bytes, now: int) -> dict:
    """{partition: [(hashkey, sortkey, value)]} of every unexpired row
    whose hashkey starts with `pattern`, each partition's rows in the
    store's key order; a partition without such a row is left out.
    Numbered hashkeys are of one length, so records taken in rising
    number, each one's rows in order, are in key order already."""
    rows = {}
    for r in records_of(pattern):
        hk = hashkey_of(r)
        mine = model.record_rows(hk, now)
        if mine:
            rows.setdefault(model.partition_of(hk), []).extend(
                (hk, sk, value) for sk, value in mine)
    return rows

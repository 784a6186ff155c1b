"""The plain reference: what a correct store would answer.

Imports nothing from pegasus_tpu and takes nothing the program has made.
The routing hash (crc64 of the hashkey, rDSN's polynomial) and the
store's key order are written out here, so a client or server that
routes or sorts differently disagrees with this file.
"""

from __future__ import annotations

import bisect
import struct
import time

import numpy as np

PEGASUS_EPOCH_BEGIN = 1451606400  # 2016-01-01 00:00:00 UTC

_CRC64_BITS = (63, 61, 59, 58, 56, 55, 52, 49, 48, 47, 46, 44, 41, 37, 36,
               34, 32, 31, 28, 26, 23, 22, 19, 16, 13, 12, 10, 9, 6, 4, 3, 0)
_POLY = 0
for _n in _CRC64_BITS:
    _POLY |= 1 << (63 - _n)
_TABLE = []
for _i in range(256):
    _k = _i
    for _ in range(8):
        _k = (_k >> 1) ^ _POLY if _k & 1 else _k >> 1
    _TABLE.append(_k)
_M64 = (1 << 64) - 1


def crc64(data: bytes) -> int:
    crc = _M64
    for b in data:
        crc = _TABLE[(crc ^ b) & 0xFF] ^ (crc >> 8)
    return ~crc & _M64


def partition_of(hk: bytes, n_partitions: int) -> int:
    """The partition a hashkey routes to."""
    return crc64(hk) % n_partitions


def epoch_now() -> int:
    """Seconds since the Pegasus epoch: the unit of expire_ts."""
    return max(0, int(time.time()) - PEGASUS_EPOCH_BEGIN)


def model_key(hk: bytes, sk: bytes) -> bytes:
    """The store's key order: big-endian u16 hashkey length, hashkey,
    sortkey, compared as bytes."""
    return struct.pack(">H", len(hk)) + hk + sk


def hashkey_of(record: int) -> bytes:
    return b"user%08d" % record


def sortkey_of(field: int) -> bytes:
    return b"field%d" % field


class Model:
    """Sorted (hashkey, sortkey) -> (value, expire_ts), per partition."""

    def __init__(self, n_partitions: int):
        self.n_partitions = n_partitions
        self.rows = [dict() for _ in range(n_partitions)]   # key -> row
        self._order = [None] * n_partitions                 # sorted keys
        self._partition = {}                                # hk -> pidx

    def partition_of(self, hk: bytes) -> int:
        p = self._partition.get(hk)
        if p is None:
            p = self._partition[hk] = partition_of(hk, self.n_partitions)
        return p

    def put(self, hk: bytes, sk: bytes, value: bytes, ets: int) -> None:
        p = self.partition_of(hk)
        key = model_key(hk, sk)
        order = self._order[p]
        if order is not None and key not in self.rows[p]:
            if order and key > order[-1]:
                order.append(key)       # inserts arrive past the loaded range
            else:
                bisect.insort(order, key)
        self.rows[p][key] = (value, ets)

    @staticmethod
    def expired(ets: int, now: int) -> bool:
        return 0 < ets <= now

    def get(self, hk: bytes, sk: bytes, now: int):
        row = self.rows[self.partition_of(hk)].get(model_key(hk, sk))
        if row is None or self.expired(row[1], now):
            return None
        return row[0]

    def _sorted(self, pidx: int):
        order = self._order[pidx]
        if order is None:
            order = self._order[pidx] = sorted(self.rows[pidx])
        return order

    def scan(self, pidx: int, start_key: bytes, n: int, now: int):
        """First n unexpired rows of the partition at or after
        start_key: [(key, value)]."""
        order = self._sorted(pidx)
        i = bisect.bisect_left(order, start_key)
        rows = self.rows[pidx]
        out = []
        while i < len(order) and len(out) < n:
            value, ets = rows[order[i]]
            if not self.expired(ets, now):
                out.append((order[i], value))
            i += 1
        return out

    def record_rows(self, hk: bytes, now: int, sk_prefix: bytes = b""):
        """The unexpired rows of one hashkey whose sortkey starts with
        sk_prefix, in order: [(sortkey, value)]."""
        prefix = model_key(hk, b"")
        keep = prefix + sk_prefix
        rows = self.rows[self.partition_of(hk)]
        order = self._sorted(self.partition_of(hk))
        out = []
        for i in range(bisect.bisect_left(order, keep), len(order)):
            key = order[i]
            if not key.startswith(keep):
                break
            value, ets = rows[key]
            if not self.expired(ets, now):
                out.append((key[len(prefix):], value))
        return out


def make_values(rng: np.random.Generator, n: int, length: int):
    """n values of `length` printable bytes each, as YCSB's random
    ASCII fields."""
    flat = rng.integers(32, 127, size=n * length, dtype=np.uint8).tobytes()
    return [flat[i * length:(i + 1) * length] for i in range(n)]


def make_records(seed: int, n_records: int, fields: int, length: int,
                 expired_share: float, now: int):
    """The loaded table: n_records YCSB records (hashkey `user%08d`) of
    `fields` rows (`field<j>`), `expired_share` of the rows carrying an
    expire_ts already in the past. Yields (hk, sk, value, expire_ts)."""
    rng = np.random.default_rng([seed, 0x10ad])
    past = max(1, now - 100)
    chunk = 10_000
    for lo in range(0, n_records, chunk):
        hi = min(n_records, lo + chunk)
        values = make_values(rng, (hi - lo) * fields, length)
        expired = rng.random((hi - lo) * fields) < expired_share
        i = 0
        for r in range(lo, hi):
            hk = hashkey_of(r)
            for j in range(fields):
                yield hk, sortkey_of(j), values[i], past if expired[i] else 0
                i += 1

"""Per-SSTable bloom filters — the missing filter layer under point reads.

Role parity: RocksDB's full-file bloom filters behind
`pegasus_server_impl` (the reference rides
`BlockBasedTableOptions::filter_policy`); CompassDB (PAPERS.md) is the
measured case for how far a per-run membership structure moves
point-read tails. Every SST writer builds one filter over the table's
FULL keys at finish — vectorized: the per-block key matrices are hashed
with ONE `crc64_rows` pass each (the same batched crc64 the hash_lo
column and the probe path use), and the k bit positions per key derive
by double hashing from that single 64-bit value, so no per-key Python
runs at any table size.

Probe contract: `may_contain*` returning False is definitive (the key
is NOT in the table — a run/block lookup can be skipped); True means
"maybe" at the configured false-positive rate (~0.8% at the default
10 bits/key with k=7). Files written before this layer existed carry no
filter and degrade to the unfiltered path.

Knobs (`[pegasus.server]`): `bloom_bits_per_key` (build-time; 0 turns
filter building off), `bloom_probe` (mutable probe-time kill switch).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from pegasus_tpu.base.crc import crc64
from pegasus_tpu.utils.flags import FLAGS, define_flag

define_flag("pegasus.server", "bloom_bits_per_key", 10,
            "bloom filter bits per key for new SST files (0 = no filters)",
            mutable=True)
define_flag("pegasus.server", "bloom_probe", True,
            "consult SST bloom filters on the point-read path",
            mutable=True)


def bloom_build_bits() -> int:
    return int(FLAGS.get("pegasus.server", "bloom_bits_per_key"))


def bloom_probe_enabled() -> bool:
    return bool(FLAGS.get("pegasus.server", "bloom_probe"))


def _num_probes(bits_per_key: int) -> int:
    # k = bits_per_key * ln2, the standard optimum; clamped like RocksDB
    return max(1, min(30, int(round(bits_per_key * 0.69))))


class BloomFilter:
    """m bits + k double-hashed probes per key.

    Bit positions: g_i = (h + i * delta) mod m with h = crc64(full key)
    and delta = (h >> 17) | 1 (odd — coprime with the power-of-two m,
    so the probe sequence walks the whole bit space). m is rounded UP
    to a power of two: every mod becomes a mask, and the scalar probe
    (the 1-4-key flush shape) walks `idx = (idx + delta) & mask` with
    no multiplies — measured ~3x cheaper per probe than the general-m
    form, and the extra bits only lower the false-positive rate. Both
    the build and the batch probe are single vectorized numpy passes
    over uint64 hash columns.
    """

    __slots__ = ("bits", "m", "k", "_scalar_bits")

    def __init__(self, bits: np.ndarray, m: int, k: int) -> None:
        self.bits = bits  # uint8[m // 8]
        self.m = m
        self.k = k
        # lazily-materialized bytes twin for scalar probes (python
        # bytes indexing returns an int with no numpy boxing — the
        # 1-4-key flush shape probes scalar)
        self._scalar_bits: Optional[bytes] = None

    @staticmethod
    def build(hashes: np.ndarray, bits_per_key: int) -> "BloomFilter":
        """One filter over `hashes` (uint64[n] crc64 of each full key)."""
        n = int(hashes.shape[0])
        m = 64
        while m < n * bits_per_key:  # next power of two >= n * bpk
            m <<= 1
        k = _num_probes(bits_per_key)
        bits = np.zeros(m // 8, dtype=np.uint8)
        h = hashes.astype(np.uint64, copy=False)
        delta = (h >> np.uint64(17)) | np.uint64(1)
        mask = np.uint64(m - 1)
        for i in range(k):
            idx = (h + np.uint64(i) * delta) & mask
            np.bitwise_or.at(
                bits, (idx >> np.uint64(3)).astype(np.int64),
                (np.uint8(1) << (idx & np.uint64(7)).astype(np.uint8)))
        return BloomFilter(bits, m, k)

    def may_contain_hashes(self, hashes: np.ndarray) -> np.ndarray:
        """bool[n] for a batch of full-key crc64 hashes — ONE vectorized
        pass answers every probe of a read flush against this table.
        All k probe positions evaluate as one [k, n] broadcast chain
        (~8 numpy dispatches total, k-independent — the per-k loop form
        paid ~5 dispatches per probe and lost to scalar code below
        ~50 keys)."""
        h = hashes.astype(np.uint64, copy=False)
        delta = (h >> np.uint64(17)) | np.uint64(1)
        ks = np.arange(self.k, dtype=np.uint64)
        idx = (h[None, :] + ks[:, None] * delta[None, :]) \
            & np.uint64(self.m - 1)
        probes = (self.bits[(idx >> np.uint64(3)).astype(np.int64)]
                  >> (idx & np.uint64(7)).astype(np.uint8)) & 1
        return probes.all(axis=0)

    def may_contain_hash(self, h: int) -> bool:
        """Scalar probe (solo gets and small flush prunes;
        h = crc64(full key) as a python int). The masked incremental
        walk is the same g_i sequence as the vectorized form: with m a
        power of two, (h + i*delta) mod m == ((h mod m) + i*(delta mod
        m)) mod m."""
        h = int(h)
        mask = self.m - 1
        delta = ((h >> 17) | 1) & mask
        idx = h & mask
        bits = self._scalar_bits
        if bits is None:
            bits = self._scalar_bits = self.bits.tobytes()
        for _ in range(self.k):
            if not (bits[idx >> 3] >> (idx & 7)) & 1:
                return False
            idx = (idx + delta) & mask
        return True

    def may_contain(self, key: bytes) -> bool:
        return self.may_contain_hash(crc64(key))

    def to_bytes(self) -> bytes:
        return self.bits.tobytes()

    @property
    def contiguous_bits(self) -> np.ndarray:
        """C-contiguous bits for the native multi-probe (a view over an
        encrypted-store read buffer may be fine already; mmap-backed
        frombuffer views are contiguous by construction)."""
        if not self.bits.flags["C_CONTIGUOUS"]:
            self.bits = np.ascontiguousarray(self.bits)
        return self.bits

    @staticmethod
    def from_bytes(raw, m: int, k: int) -> Optional["BloomFilter"]:
        bits = np.frombuffer(raw, dtype=np.uint8)
        if bits.shape[0] * 8 != m or k < 1:
            return None  # torn/mismatched filter: degrade to unfiltered
        return BloomFilter(bits, m, k)


class MultiProbe:
    """Every filter of one partition's run set, probed in ONE pass.

    The planner's flush carries 1-4 disk-bound keys per partition, and
    a deep-L0 store holds 8-16+ filters — per-(key, filter) python
    probe walks cost ~1.4 us each, rivaling the block probes they
    exist to skip. This precomputes the filters' geometry columns
    (bit-array addresses, masks, k's) once per store generation, and
    `probe` answers the whole (keys x filters) matrix with ONE native
    call (`pegasus_bloom_probe_multi`, ~20 ns per pair). Holding
    `filters` keeps every bit array alive for the address column.

    Returns row-major bytes: result[key_i * n + filter_t] is 1 iff
    key i may be present in filter t (indexable at python-int speed).
    """

    __slots__ = ("filters", "n", "_native", "_addrs", "_masks", "_ks")

    def __init__(self, filters) -> None:
        self.filters = list(filters)
        self.n = len(self.filters)
        try:
            from pegasus_tpu.native import bloom_probe_multi_fn

            self._native = bloom_probe_multi_fn()
        except Exception:  # noqa: BLE001 - scalar fallback below
            self._native = None
        if self._native is not None:
            self._addrs = np.array(
                [f.contiguous_bits.ctypes.data for f in self.filters],
                dtype=np.uint64)
            self._masks = np.array([f.m - 1 for f in self.filters],
                                   dtype=np.uint64)
            self._ks = np.array([f.k for f in self.filters],
                                dtype=np.int32)

    def probe(self, hashes: np.ndarray) -> bytes:
        n_keys = len(hashes)
        if self._native is not None:
            out = np.empty(n_keys * self.n, dtype=np.uint8)
            self._native(self._addrs, self._masks, self._ks, self.n,
                         np.ascontiguousarray(hashes, dtype=np.uint64),
                         n_keys, out)
            return out.tobytes()
        out = bytearray(n_keys * self.n)
        for i in range(n_keys):
            h = int(hashes[i])
            base = i * self.n
            for t, f in enumerate(self.filters):
                out[base + t] = f.may_contain_hash(h)
        return bytes(out)

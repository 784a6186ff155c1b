"""Native host runtime (C++ via ctypes).

The reference's host hot loops are C++ (rDSN runtime + server codecs);
ours live here. The library builds on first use with the toolchain in
the image (g++). The pure-Python paths stay for the tests that compare
against them; a failed build prints the compiler's stderr once, and
`available()` says which mode is active (chip_smoke.py and
benchmarks/run.py refuse to run without the library).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading
from typing import Optional, Sequence

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "packer.cpp")
_SO = os.path.join(_DIR, "libpegasus_native.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def compile_shared(src: str, so_path: str, opt: str,
                   libs: Sequence[str] = (), timeout: float = 180) -> bool:
    """g++ `src` into the shared library `so_path` (through a
    per-process temp name, so concurrent builders cannot interleave). A
    failure prints what the compiler said — callers try at most once
    per process — and returns False."""
    tmp = f"{so_path}.{os.getpid()}.tmp"
    cmd = ["g++", opt, "-shared", "-fPIC", "-std=c++17", src, "-o", tmp,
           *libs]
    try:
        result = subprocess.run(cmd, capture_output=True, timeout=timeout)
    except (OSError, subprocess.TimeoutExpired) as exc:
        print(f"native build of {os.path.basename(so_path)} failed: "
              f"{exc!r}", file=sys.stderr, flush=True)
        return False
    if result.returncode != 0:
        print(f"native build of {os.path.basename(so_path)} failed "
              f"(exit {result.returncode}):\n"
              f"{result.stderr.decode(errors='replace')}",
              file=sys.stderr, flush=True)
        return False
    os.replace(tmp, so_path)
    return True


def _build() -> bool:
    return compile_shared(_SRC, _SO, "-O3", libs=("-ldl",), timeout=120)


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not os.path.exists(_SO) or (
                os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
            if not _build():
                return None
        def bind():
            lib = ctypes.CDLL(_SO)
            lib.pegasus_crc64.restype = ctypes.c_uint64
            lib.pegasus_crc64.argtypes = [ctypes.c_char_p, ctypes.c_int64]
            lib.pegasus_crc32.restype = ctypes.c_uint32
            lib.pegasus_crc32.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                          ctypes.c_uint32]
            lib.pegasus_crc64_rows.restype = None
            lib.pegasus_crc64_rows.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_void_p]
            lib.pegasus_bloom_probe_multi.restype = None
            lib.pegasus_bloom_probe_multi.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p]
            lib.pegasus_phash_build.restype = ctypes.c_int32
            lib.pegasus_phash_build.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_uint64, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_void_p]
            lib.pegasus_phash_probe_multi.restype = None
            lib.pegasus_phash_probe_multi.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_void_p]
            lib.pegasus_pack_records.restype = ctypes.c_int32
            lib.pegasus_pack_records.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
            lib.pegasus_cblock_decode_keys.restype = None
            lib.pegasus_cblock_decode_keys.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p]
            lib.pegasus_region_filter.restype = None
            lib.pegasus_region_filter.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
                ctypes.c_void_p]
            lib.pegasus_cblock_subset.restype = ctypes.c_int64
            lib.pegasus_cblock_subset.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p,
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p]
            lib.pegasus_gather_page.restype = None
            lib.pegasus_gather_page.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
            lib.pegasus_scan_serve_batch.restype = None
            lib.pegasus_scan_serve_batch.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_int32, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
            return lib

        try:
            _lib = bind()
        except (OSError, AttributeError):
            # unloadable, or a STALE prebuilt .so missing a newer symbol
            # (mtime-preserving restore tools defeat the rebuild check):
            # one rebuild attempt, else degrade to the Python paths
            try:
                os.remove(_SO)
            except OSError:
                pass
            if not _build():
                return None
            try:
                _lib = bind()
            except (OSError, AttributeError) as exc:
                print(f"native library rebuilt but not loadable: {exc!r}",
                      file=sys.stderr, flush=True)
                return None
        return _lib


def available() -> bool:
    return _load() is not None


def crc64_native(data: bytes) -> int:
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    return int(lib.pegasus_crc64(data, len(data)))


def crc64_rows_fn():
    """The batched crc64-over-padded-rows function, or None when the
    native library is unavailable (base.crc.crc64_rows falls back to
    the vectorized numpy loop)."""
    lib = _load()
    if lib is None:
        return None

    def crc64_rows_native(rows, lens, out) -> None:
        # rows: C-contiguous uint8[n, width]; lens: int64[n];
        # out: uint64[n] — filled in place
        lib.pegasus_crc64_rows(
            rows.ctypes.data, lens.ctypes.data, rows.shape[0],
            rows.shape[1], out.ctypes.data)

    return crc64_rows_native


def bloom_probe_multi_fn():
    """The multi-filter bloom probe, or None when the native library is
    unavailable (storage.bloom.MultiProbe falls back to scalar walks)."""
    lib = _load()
    if lib is None:
        return None

    def probe(addrs, masks, ks, n_filters, hashes, n_keys, out) -> None:
        # addrs/masks uint64[n_filters], ks int32[n_filters],
        # hashes uint64[n_keys], out uint8[n_keys * n_filters]
        lib.pegasus_bloom_probe_multi(
            addrs.ctypes.data, masks.ctypes.data, ks.ctypes.data,
            n_filters, hashes.ctypes.data, n_keys, out.ctypes.data)

    return probe


def phash_build_fn():
    """The CHD perfect-hash index build (see packer.cpp
    pegasus_phash_build), or None when the native library is
    unavailable (storage.phash falls back to the Python CHD loop —
    bit-identical output, per-bucket interpreter cost)."""
    import numpy as np

    lib = _load()
    if lib is None:
        return None

    def build(hashes, locs, seed: int, ts: int, nb: int):
        """(slots uint32[ts], disp uint16[nb]) or None when this seed
        cannot place every bucket (the caller reseeds)."""
        slots = np.empty(ts, dtype=np.uint32)
        disp = np.empty(nb, dtype=np.uint16)
        rc = lib.pegasus_phash_build(
            hashes.ctypes.data, locs.ctypes.data, hashes.shape[0],
            seed, ts, nb, disp.ctypes.data, slots.ctypes.data)
        if rc != 0:
            return None
        return slots, disp

    return build


def phash_probe_multi_fn():
    """The multi-index perfect-hash probe (the bloom multi-probe's
    sibling), or None when the native library is unavailable
    (storage.phash.PHashMultiProbe falls back to per-index vectorized
    numpy probes)."""
    lib = _load()
    if lib is None:
        return None

    def probe(fixed_ptrs, n_tables, hashes, n_keys, out,
              hit_out) -> None:
        # fixed_ptrs: the five per-table geometry pointers
        # (slots_addrs/disp_addrs/ts/nb/seeds uint64[n_tables]),
        # pre-resolved by the caller — .ctypes.data costs ~0.4 us per
        # access and the probe runs once per read flush; hashes
        # uint64[n_keys], out uint32[n_keys * n_tables], hit_out
        # uint8[n_keys * n_tables]
        lib.pegasus_phash_probe_multi(
            *fixed_ptrs, n_tables, hashes.ctypes.data, n_keys,
            out.ctypes.data, hit_out.ctypes.data)

    return probe


def crc32_fn():
    """The CRC-32C buffer function, or None when the native library is
    unavailable (base.crc falls back to its Python loop)."""
    lib = _load()
    if lib is None:
        return None

    def crc32_native(data: bytes, init_crc: int = 0) -> int:
        return int(lib.pegasus_crc32(bytes(data), len(data),
                                     init_crc & 0xFFFFFFFF))

    return crc32_native


def pack_records(keys, key_width: int):
    """Pack a list of encoded keys into columnar arrays in one native call.

    Returns (keys[n, key_width] uint8, key_len int32[n], hashkey_len
    int32[n], hash_lo uint32[n], valid bool[n]) or None when the native
    library is unavailable (callers fall back to the Python packer).
    """
    import numpy as np

    lib = _load()
    if lib is None:
        return None
    n = len(keys)
    heap = b"".join(keys)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum([len(k) for k in keys], out=offsets[1:])
    heap_arr = np.frombuffer(heap, dtype=np.uint8)
    keys_out = np.empty((n, key_width), dtype=np.uint8)
    key_len = np.empty(n, dtype=np.int32)
    hkl = np.empty(n, dtype=np.int32)
    hash_lo = np.empty(n, dtype=np.uint32)
    valid = np.empty(n, dtype=np.uint8)
    rc = lib.pegasus_pack_records(
        heap_arr.ctypes.data if n else None,
        offsets.ctypes.data, n, key_width,
        keys_out.ctypes.data, key_len.ctypes.data, hkl.ctypes.data,
        hash_lo.ctypes.data, valid.ctypes.data)
    if rc != 0:
        return None
    return keys_out, key_len, hkl, hash_lo, valid.astype(bool)


def cblock_decode_keys_fn():
    """Key-matrix rebuild for dcz-encoded blocks, or None when the
    native library is unavailable (block_codec falls back to numpy
    ragged scatters)."""
    lib = _load()
    if lib is None:
        return None

    def decode_keys(dict_heap, dict_offs, hk_idx, sk_heap, sk_offs,
                    key_len, n, width, out) -> None:
        lib.pegasus_cblock_decode_keys(
            dict_heap.ctypes.data if dict_heap.size else None,
            dict_offs.ctypes.data, hk_idx.ctypes.data,
            sk_heap.ctypes.data if sk_heap.size else None,
            sk_offs.ctypes.data, key_len.ctypes.data, n, width,
            out.ctypes.data)

    return decode_keys


def cblock_subset_fn():
    """Encoded-domain block subsetting for the compaction drop path
    (see packer.cpp pegasus_cblock_subset), or None when the native
    library is unavailable (bulk compaction falls back to the Python
    decode -> gather -> re-encode path)."""
    import numpy as np

    lib = _load()
    if lib is None:
        return None

    def subset(raw, raw_heap_len: int, key_width: int, keep, new_ets,
               patch_value_headers: bool, want_hashes: bool):
        """(encoded bytes, crc64 hashes|None, kept n, subset raw heap
        len, first_key, last_key), or None when the kernel cannot take
        this block (compressed heap with no zlib/zstd resolvable)."""
        a = raw if isinstance(raw, np.ndarray) \
            else np.frombuffer(raw, dtype=np.uint8)
        a = np.ascontiguousarray(a)
        keep_u8 = np.ascontiguousarray(keep, dtype=np.uint8)
        if new_ets is not None:
            new_ets = np.ascontiguousarray(new_ets, dtype=np.uint32)
        # margin covers v2 column growth: a subset can widen a
        # FOR-encoded expire_ts section back to raw u32 (new_ets
        # spreading past u16) — up to +4 bytes/row over the input
        out = np.empty(a.size + raw_heap_len + 4 * keep_u8.size + 4096,
                       dtype=np.uint8)
        hashes = (np.empty(keep_u8.size, dtype=np.uint64)
                  if want_hashes else None)
        out_keys = np.zeros(2 * key_width, dtype=np.uint8)
        out_meta = np.zeros(4, dtype=np.int64)
        rc = lib.pegasus_cblock_subset(
            a.ctypes.data, a.size, keep_u8.ctypes.data,
            new_ets.ctypes.data if new_ets is not None else None,
            1 if patch_value_headers else 0, out.ctypes.data, out.size,
            hashes.ctypes.data if hashes is not None else None,
            out_keys.ctypes.data, out_meta.ctypes.data)
        if rc < 0:
            return None
        m, vsub, fkl, lkl = (int(x) for x in out_meta)
        return (out[:rc].tobytes(),
                hashes[:m].copy() if hashes is not None else None,
                m, vsub, out_keys[:fkl].tobytes(),
                out_keys[key_width:key_width + lkl].tobytes())

    return subset


def region_filter_fn():
    """Ragged-region pattern filter (the encoded-probe primitive), or
    None when the native library is unavailable (predicates falls back
    to the scalar host_match_filter loop)."""
    lib = _load()
    if lib is None:
        return None

    def region_filter(heap, offs, n, pattern: bytes, ftype: int,
                      out) -> None:
        lib.pegasus_region_filter(
            heap.ctypes.data if heap.size else None, offs.ctypes.data,
            n, pattern, len(pattern), ftype, out.ctypes.data)

    return region_filter


def gather_page_fn():
    """The raw page-gather entry point (see packer.cpp
    pegasus_gather_page), or None when the native library is
    unavailable. server/page.py owns the calling convention."""
    lib = _load()
    return None if lib is None else lib.pegasus_gather_page


def scan_serve_fn():
    """The whole-batch scan-assembly entry point (see packer.cpp
    pegasus_scan_serve_batch), or None when the native library is
    unavailable. server/page.py owns the calling convention."""
    lib = _load()
    return None if lib is None else lib.pegasus_scan_serve_batch

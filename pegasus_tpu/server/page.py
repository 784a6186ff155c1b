"""Columnar response-page assembly.

The response-assembly half of the scan hot path: given the surviving
row indices of each planned block (the device/static mask AND the host
TTL mask, already applied), pack every survivor's key and user-data
into ONE ScanPage — a single native call per block
(native/packer.cpp pegasus_gather_page) instead of a per-record Python
loop building KeyValue objects.

Parity role: src/server/pegasus_server_impl.cpp:2434-2489
(append_key_value_for_multi_get / validate_key_value_for_scan) — the
reference's C++ per-record response append. Ours is batch-shaped
because the survivors are already columnar in the SST block.

Falls back to a per-record Python gather when the native library is
unavailable (same output, slower).
"""

from __future__ import annotations

import threading
from typing import List, Optional, Tuple

import numpy as np

from pegasus_tpu import native
from pegasus_tpu.server.types import ScanPage

_scratch_tls = threading.local()


def _scratch(name: str, size: int, dtype, alloc=np.empty):
    """Grow-only per-thread scratch array + cached base pointer.

    The assembly arenas are consumed within one serve_batch call (pages
    cut out by copy), so reusing them across flushes avoids an
    mmap/page-fault round per flush for the multi-MB value arena —
    and caching `.ctypes.data` (a ~µs property that builds a fresh
    ctypes view per access) with the buffer trims the per-call ctypes
    overhead. Per-thread because onebox nodes serve from their own
    dispatch threads. `alloc` fills the buffer at (re)allocation
    (np.arange for the identity block table)."""
    pool = getattr(_scratch_tls, "pool", None)
    if pool is None:
        pool = _scratch_tls.pool = {}
    hit = pool.get(name)
    if hit is None or hit[0].size < size:
        arr = alloc(int(size * 3 // 2) + 64, dtype=dtype)
        hit = pool[name] = (arr, arr.ctypes.data)
    return hit


def block_native_ptrs(blk):
    """Cached static pointer row for one Block: (keys, key_len, voffs,
    heap, ets, width). `.ctypes.data` costs ~a µs per access, so the
    serving path resolves each block's pointers once per process, not
    once per request."""
    nat = getattr(blk, "_nat", None)
    if nat is None:
        heap = blk.value_heap
        if not isinstance(heap, np.ndarray):
            heap = np.frombuffer(heap, dtype=np.uint8)
        nat = (blk.keys.ctypes.data, blk.key_len.ctypes.data,
               blk.value_offs.ctypes.data,
               heap.ctypes.data if heap.size else 0,
               blk.expire_ts.ctypes.data, blk.keys.shape[1], heap)
        blk._nat = nat
    return nat


def probe_nat(blk):
    """Cached point-probe entry table for one Block: the contiguous key
    matrix, an int64 key-length column, and the memcmp-ordered void
    view the batched searchsorted probes run over — resolved once per
    block lifetime (like block_native_ptrs for the scan path) so the
    point-get path's vectorized probes skip per-call dtype/contiguity
    work."""
    nat = blk._probe
    if nat is None:
        km = np.ascontiguousarray(blk.keys)
        vt = np.dtype((np.void, km.shape[1]))
        nat = (km, np.asarray(blk.key_len, dtype=np.int64),
               km.view(vt).ravel())
        blk._probe = nat
        blk.recount()  # the int64 length column is the block's now
    return nat


def probe_rows(blk, probe_keys) -> np.ndarray:
    """int64[P] row indices of exact-match probe keys in `blk` (-1 =
    absent): one vectorized searchsorted over the cached probe table
    instead of P Python bisects."""
    from pegasus_tpu.ops.predicates import point_probe_rows

    km, kl, bv = probe_nat(blk)
    return point_probe_rows(km, kl, probe_keys, block_void=bv)


def plan_geometry(plan):
    """(total_rows, value-heap span upper bound, max key width) of a
    plan — the native assembly's arena sizing. Computed once per cached
    plan (partition_server.plan_scan_batch) and carried in the window
    tuple; recomputed here only for callers without a cache."""
    total_rows = 0
    span = 0
    max_w = 2
    for _ckey, blk, lo, hi in plan:
        total_rows += hi - lo
        vo = blk.value_offs
        span += int(vo[hi]) - int(vo[lo])
        if blk.keys.shape[1] > max_w:
            max_w = blk.keys.shape[1]
    return total_rows, span, max_w


def plan_nat(plan):
    """Per-plan native entry table, cached WITH the plan
    (partition_server.plan_scan_batch): the pointer rows (keys, width,
    key_len, value_offs, heap, expire_ts) for every entry as one
    uint64[6, n] plus int64 lo/hi bounds and the ckey tuple. Plans are
    pure over the immutable run set, so these arrays are too —
    serve_batch concatenates them instead of re-resolving per-entry
    pointer rows through Python dicts on every flush."""
    n = len(plan)
    ptr6 = np.empty((6, n), dtype=np.uint64)
    lo_arr = np.empty(n, dtype=np.int64)
    hi_arr = np.empty(n, dtype=np.int64)
    ckeys = []
    for j, (ckey, blk, lo, hi) in enumerate(plan):
        kp, lp, vp, hp, ep, w, _heap = block_native_ptrs(blk)
        ptr6[0, j] = kp
        ptr6[1, j] = w
        ptr6[2, j] = lp
        ptr6[3, j] = vp
        ptr6[4, j] = hp
        ptr6[5, j] = ep
        lo_arr[j] = lo
        hi_arr[j] = hi
        ckeys.append(ckey)
    return ptr6, lo_arr, hi_arr, tuple(ckeys), ptr6[1].astype(np.int64)


def serve_batch(req_windows, unique, byte_cap: int, hdr: int):
    """Whole-BATCH base-path assembly in ONE native call.

    req_windows: per fast-path request (plan, want, no_value,
    want_ets, live_masks, geom[, nat[, live_ptrs]]) where plan is
    [(ckey, Block, lo, hi)] in key order, live_masks maps ckey ->
    bool[count] (that request's static keep AND host TTL — PER WINDOW,
    because filter flavors sharing a block carry different masks),
    geom is plan_geometry(plan), nat is plan_nat(plan) and live_ptrs
    maps ckey -> live-mask base pointer (resolved once per (block,
    flavor, second) in prepare_serve). Trailing elements may be
    omitted — recomputed then; the serving path passes 8-tuples, which
    ride a fully vectorized bookkeeping path (no per-window numpy
    scalar stores). `unique` is unused (kept for caller compatibility;
    the entry table is per-entry now, so no flush-wide block dedup is
    needed).

    Packs every request's surviving rows into shared arenas via
    packer.cpp pegasus_scan_serve_batch — the C++ twin of the
    reference's per-record serving loop
    (src/server/pegasus_server_impl.cpp:643) — then cuts per-request
    ScanPages out of the arenas.

    Returns [(page, size, last_key, truncated) | None] per request
    (None = re-serve that request in Python: arena capacity hit), or
    None entirely when the native library is unavailable.
    """
    fn = native.scan_serve_fn()
    if fn is None or not req_windows:
        return None
    want_ets = any(w[3] for w in req_windows)
    n_reqs = len(req_windows)
    mask_refs = []  # keep ad-hoc mask arrays alive across the call
    if all(len(w) > 7 for w in req_windows):
        # serving fast path: every per-window quantity comes cached
        # (geom + nat with the plan, live_ptrs with the second's live
        # masks), so the bookkeeping is pure array math over the flush
        nats = [w[6] for w in req_windows]
        geoms = np.array([w[5] for w in req_windows], dtype=np.int64)
        wants_in = np.fromiter((w[1] for w in req_windows),
                               dtype=np.int64, count=n_reqs)
        no_vals = np.fromiter((bool(w[2]) for w in req_windows),
                              dtype=np.bool_, count=n_reqs)
        counts = np.fromiter((len(n[3]) for n in nats),
                             dtype=np.int64, count=n_reqs)
        entry_start = np.zeros(n_reqs + 1, dtype=np.int64)
        np.cumsum(counts, out=entry_start[1:])
        e = int(entry_start[-1])
        entry_mask = np.fromiter(
            (w[7][ck] for w in req_windows for ck in w[6][3]),
            dtype=np.uint64, count=e)
        wants = np.minimum(wants_in, geoms[:, 0])
        rows_total = int(wants.sum())
        row_base = np.zeros(n_reqs, dtype=np.int64)
        np.cumsum(wants[:-1], out=row_base[1:])
        row_base += np.arange(n_reqs)  # +r: offset windows are count+1
        key_cap = int((wants * geoms[:, 2]).sum())
        val_cap = int(np.where(
            no_vals, 0,
            np.minimum(byte_cap + (64 << 10), geoms[:, 1])).sum())
        no_values = no_vals.astype(np.uint8)
    else:
        # ad-hoc callers (tests, fallbacks) may omit nat/live_ptrs
        nats = []
        mask_arrays = []
        entry_start = np.zeros(n_reqs + 1, dtype=np.int64)
        wants = np.empty(n_reqs, dtype=np.int64)
        no_values = np.empty(n_reqs, dtype=np.uint8)
        row_base = np.empty(n_reqs, dtype=np.int64)
        e = 0
        rows_total = 0
        key_cap = 0
        val_cap = 0
        for r, window in enumerate(req_windows):
            plan, want, no_value, _we, live_masks = window[:5]
            geom = (window[5] if len(window) > 5
                    and window[5] is not None else plan_geometry(plan))
            nat = window[6] if len(window) > 6 else plan_nat(plan)
            masks = [live_masks[ck] for ck in nat[3]]
            mask_refs.extend(masks)
            mask_arrays.append(np.fromiter(
                (m.ctypes.data for m in masks),
                dtype=np.uint64, count=len(masks)))
            nats.append(nat)
            e += len(nat[3])
            entry_start[r + 1] = e
            total_rows, span, max_w = geom
            row_base[r] = rows_total + r
            cap_rows = min(want, total_rows)
            wants[r] = cap_rows
            no_values[r] = no_value
            rows_total += cap_rows
            key_cap += cap_rows * max_w
            val_cap += 0 if no_value else min(byte_cap + (64 << 10),
                                              span)
        entry_mask = (mask_arrays[0] if n_reqs == 1
                      else np.concatenate(mask_arrays))
    if key_cap >= 1 << 32 or val_cap >= 1 << 32:
        # running arena offsets are uint32: a flush whose combined
        # spans pass 4 GiB must take the per-request Python path (which
        # enforces its own per-request caps) instead of wrapping
        return None
    if n_reqs == 1:
        ptr6, entry_lo, entry_hi = nats[0][:3]
        widths = nats[0][4]
    else:
        ptr6 = np.concatenate([n[0] for n in nats], axis=1)
        entry_lo = np.concatenate([n[1] for n in nats])
        entry_hi = np.concatenate([n[2] for n in nats])
        widths = np.concatenate([n[4] for n in nats])
    # grow-only arenas + outputs (the C call writes every cell the
    # result loop reads — no zeroing needed); entry_block is a cached
    # arange prefix (the per-entry block table is identity now)
    _entry_block, eb_ptr = _scratch("entry_block", e, np.int64,
                                    alloc=np.arange)
    key_blob, kb_ptr = _scratch("key_blob", max(1, key_cap), np.uint8)
    val_blob, vb_ptr = _scratch("val_blob", max(1, val_cap), np.uint8)
    n_offs = rows_total + n_reqs + 1
    key_offs, ko_ptr = _scratch("key_offs", n_offs, np.uint32)
    val_offs, vo_ptr = _scratch("val_offs", n_offs, np.uint32)
    if want_ets:
        ets_arena, ets_ptr = _scratch("ets", max(1, rows_total),
                                      np.uint32)
    else:
        ets_arena, ets_ptr = None, None
    out_count, oc_ptr = _scratch("out_count", n_reqs, np.int64)
    out_bytes, ob_ptr = _scratch("out_bytes", n_reqs, np.int64)
    out_state, os_ptr = _scratch("out_state", n_reqs, np.int32)
    fn(ptr6[0].ctypes.data, widths.ctypes.data, ptr6[2].ctypes.data,
       entry_mask.ctypes.data, ptr6[3].ctypes.data, ptr6[4].ctypes.data,
       ptr6[5].ctypes.data, n_reqs, entry_start.ctypes.data,
       eb_ptr, entry_lo.ctypes.data,
       entry_hi.ctypes.data, wants.ctypes.data, no_values.ctypes.data,
       byte_cap, hdr, kb_ptr, key_cap,
       vb_ptr, val_cap, ko_ptr,
       vo_ptr, row_base.ctypes.data,
       ets_ptr,
       oc_ptr, ob_ptr, os_ptr)

    results = []
    for r in range(n_reqs):
        state = int(out_state[r])
        if state == 3:
            results.append(None)  # arena full: Python re-serves
            continue
        count = int(out_count[r])
        truncated = state == 2
        if count == 0:
            results.append((ScanPage(), 0, None, truncated))
            continue
        base = int(row_base[r])
        ko = key_offs[base:base + count + 1]
        vo = val_offs[base:base + count + 1]
        k0, k1 = int(ko[0]), int(ko[count])
        v0, v1 = int(vo[0]), int(vo[count])
        page = ScanPage(
            key_offs=(ko - np.uint32(k0)).tobytes(),
            key_blob=key_blob[k0:k1].tobytes(),
            val_offs=(vo - np.uint32(v0)).tobytes(),
            val_blob=val_blob[v0:v1].tobytes())
        if req_windows[r][3]:
            page.ets = ets_arena[base - r:base - r + count].astype(
                "<u4").tobytes()
        last_key = key_blob[int(ko[count - 1]):k1].tobytes()
        results.append((page, int(out_bytes[r]), last_key, truncated))
    return results


def build_page(chunks: List[Tuple[object, np.ndarray]], hdr: int,
               no_value: bool = False, want_ets: bool = False,
               ) -> Tuple[ScanPage, int, Optional[bytes]]:
    """Pack survivors into one page.

    chunks: [(Block, ascending int64 row indices)] in key order across
    blocks. Returns (page, byte_size, last_key) where byte_size is the
    capacity-unit accounting sum (key bytes + user-data bytes) and
    last_key is the final packed key (resume cursor) or None for an
    empty page.
    """
    chunks = [(blk, take) for blk, take in chunks if len(take)]
    n = sum(len(take) for _b, take in chunks)
    if n == 0:
        return ScanPage(), 0, None

    # UPPER-BOUND blob capacities from scalar offset reads (takes are
    # ascending, so a chunk's value bytes fit in [offs[first],
    # offs[last+1])); the gather writes the exact running offsets and
    # the blobs are trimmed afterwards — O(1) sizing per chunk instead
    # of per-take vector math on this per-request path
    key_cap = 0
    val_cap = 0
    for blk, take in chunks:
        key_cap += len(take) * blk.keys.shape[1]
        if not no_value:
            vo = blk.value_offs
            val_cap += int(vo[int(take[-1]) + 1]) - int(vo[int(take[0])])
    if key_cap >= 1 << 32 or val_cap >= 1 << 32:
        # offsets are uint32 (here and in pegasus_gather_page); callers
        # cap batch_size (SCAN_BATCH_CAP) so this only trips on a bug
        raise ValueError(
            f"scan page exceeds 4GiB blob limit "
            f"(keys={key_cap}, values={val_cap}); split the batch")

    key_offs = np.zeros(n + 1, dtype=np.uint32)
    val_offs = np.zeros(n + 1, dtype=np.uint32)
    key_buf = bytearray(key_cap)
    val_buf = bytearray(val_cap)
    kb = np.frombuffer(key_buf, dtype=np.uint8)
    vb = np.frombuffer(val_buf, dtype=np.uint8) if val_cap else None

    fn = native.gather_page_fn()
    pos = 0
    for blk, take in chunks:
        m = len(take)
        take = np.ascontiguousarray(take, dtype=np.int64)
        if fn is not None:
            heap = blk.value_heap
            if not isinstance(heap, np.ndarray):
                heap = np.frombuffer(heap, dtype=np.uint8)
            fn(blk.keys.ctypes.data, blk.keys.shape[1],
               blk.key_len.ctypes.data, blk.value_offs.ctypes.data,
               heap.ctypes.data if heap.size else None,
               take.ctypes.data, m, hdr,
               kb.ctypes.data, key_offs[pos:].ctypes.data,
               (vb.ctypes.data if not no_value and vb is not None
                else None),
               val_offs[pos:].ctypes.data)
        else:
            _gather_python(blk, take, hdr, no_value, kb, key_offs,
                           vb, val_offs, pos)
        pos += m

    key_total = int(key_offs[n])
    val_total = int(val_offs[n])
    last_i = int(key_offs[n - 1])
    page = ScanPage(
        key_offs=key_offs.tobytes(), key_blob=bytes(key_buf[:key_total]),
        val_offs=val_offs.tobytes(), val_blob=bytes(val_buf[:val_total]))
    if want_ets:
        page.ets = np.concatenate(
            [np.asarray(blk.expire_ts)[take]
             for blk, take in chunks]).astype("<u4").tobytes()
    return page, key_total + val_total, bytes(key_buf[last_i:key_total])


def _gather_python(blk, take, hdr, no_value, kb, key_offs, vb, val_offs,
                   pos) -> None:
    """Pure-Python twin of pegasus_gather_page (no toolchain)."""
    kpos = int(key_offs[pos])
    vpos = int(val_offs[pos])
    vo = blk.value_offs
    heap = blk.value_heap
    for j, row in enumerate(take):
        row = int(row)
        kl = int(blk.key_len[row])
        kb[kpos:kpos + kl] = blk.keys[row, :kl]
        kpos += kl
        key_offs[pos + j + 1] = kpos
        v0, v1 = int(vo[row]), int(vo[row + 1])
        vl = max(0, v1 - v0 - hdr)
        if not no_value:
            if vl:
                if not isinstance(heap, np.ndarray):
                    heap = np.frombuffer(heap, dtype=np.uint8)
                vb[vpos:vpos + vl] = heap[v0 + hdr:v1]
            vpos += vl
        val_offs[pos + j + 1] = vpos

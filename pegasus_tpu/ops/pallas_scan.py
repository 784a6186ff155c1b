"""Fused Pallas TPU kernel for the scan predicate hot path.

One VMEM-resident program fuses everything the scan loop needs per record
block: TTL expiry, partition-ownership check (against the precomputed
crc64 lo column — no byte loop on device), and sortkey filter matching —
the fully-fused form of ops.predicates._scan_block_predicate for the
no-hash-filter fast path the YCSB-E workload takes.

Layout: keys are TRANSPOSED to uint8[K + P, B] so the record dimension
(B = block capacity, a multiple of 128) rides the TPU lane dimension and
the byte-position dimension rides sublanes — pattern matching becomes P
shifted row-compares on the VPU, with zero gathers. Per-record scalar
columns travel as [1, B] rows. The dynamic per-record sortkey offset is
resolved with iota masks (position == offset) instead of gathers, which
TPUs hate.

Compiles for the TPU through Mosaic. `interpret=True` is an explicit
argument that only the CPU tests pass.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pegasus_tpu.ops.predicates import (
    FT_MATCH_ANYWHERE,
    FT_MATCH_POSTFIX,
    FT_MATCH_PREFIX,
    FT_NO_FILTER,
    FilterSpec,
)
from pegasus_tpu.ops.record_block import RecordBlock

_PATTERN_WIDTH = 32  # pattern buffer rows appended below the key rows


def _kernel(pattern_ref, scalar_ref, keys_ref, klen_ref, hklen_ref,
            ets_ref, valid_ref, hashlo_ref, keep_ref, expired_ref, *,
            key_rows: int, sort_filter_type: int, validate_hash: bool):
    now = scalar_ref[0]
    plen = scalar_ref[1]
    pidx = scalar_ref[2]
    pv = scalar_ref[3]

    valid = valid_ref[...] != 0                       # [1, B]
    ets = ets_ref[...]
    expired = (ets > 0) & (ets <= now.astype(jnp.uint32)) & valid

    if validate_hash:
        hash_ok = ((hashlo_ref[...] & pv.astype(jnp.uint32))
                   == pidx.astype(jnp.uint32))
    else:
        hash_ok = jnp.ones_like(valid)

    if sort_filter_type == FT_NO_FILTER:
        sk_ok = jnp.ones_like(valid)
    else:
        b = valid.shape[1]
        # window_ok[t, b] = pattern matches starting at byte t of record b
        window_ok = jnp.ones((key_rows, b), dtype=jnp.bool_)
        for j in range(_PATTERN_WIDTH):  # static unroll on the VPU
            pat_j = pattern_ref[j]
            cmp = (keys_ref[j:j + key_rows, :].astype(jnp.int32)
                   == pat_j) | (j >= plen)
            window_ok = window_ok & cmp
        iota_t = jax.lax.broadcasted_iota(jnp.int32, (key_rows, b), 0)
        sort_start = 2 + hklen_ref[...]               # [1, B]
        sort_len = klen_ref[...] - sort_start
        if sort_filter_type == FT_MATCH_PREFIX:
            t_sel = iota_t == sort_start
        elif sort_filter_type == FT_MATCH_POSTFIX:
            t_sel = iota_t == sort_start + sort_len - plen
        else:  # FT_MATCH_ANYWHERE
            t_sel = ((iota_t >= sort_start)
                     & (iota_t <= sort_start + sort_len - plen))
        matched = jnp.any(window_ok & t_sel, axis=0, keepdims=True)
        fits = sort_len >= plen
        sk_ok = (matched & fits) | (plen == 0)

    keep = valid & ~expired & hash_ok & sk_ok
    keep_ref[...] = keep.astype(jnp.int32)
    expired_ref[...] = expired.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("key_rows", "sort_filter_type",
                                             "validate_hash", "interpret"))
def _fused_call(pattern, scalars, keys_t, klen, hklen, ets, valid, hashlo,
                key_rows: int, sort_filter_type: int, validate_hash: bool,
                interpret: bool):
    b = keys_t.shape[1]
    kernel = functools.partial(_kernel, key_rows=key_rows,
                               sort_filter_type=sort_filter_type,
                               validate_hash=validate_hash)
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((1, b), jnp.int32),
                   jax.ShapeDtypeStruct((1, b), jnp.int32)),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # pattern int32[P]
            pl.BlockSpec(memory_space=pltpu.SMEM),   # scalars int32[4]
            pl.BlockSpec(memory_space=pltpu.VMEM),   # keys_t uint8[K+P, B]
            pl.BlockSpec(memory_space=pltpu.VMEM),   # key_len int32[1, B]
            pl.BlockSpec(memory_space=pltpu.VMEM),   # hashkey_len int32[1, B]
            pl.BlockSpec(memory_space=pltpu.VMEM),   # expire_ts uint32[1, B]
            pl.BlockSpec(memory_space=pltpu.VMEM),   # valid int32[1, B]
            pl.BlockSpec(memory_space=pltpu.VMEM),   # hash_lo uint32[1, B]
        ],
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM)),
        interpret=interpret,
    )(pattern, scalars, keys_t, klen, hklen, ets, valid, hashlo)


def prepare_transposed(block: RecordBlock) -> Tuple[jax.Array, ...]:
    """Host-side one-time prep: transpose keys to [K+P, B] and lift scalar
    columns to [1, B] rows (cacheable alongside the device block cache)."""
    keys = np.asarray(block.keys)
    b, k = keys.shape
    keys_t = np.zeros((k + _PATTERN_WIDTH, b), dtype=np.uint8)
    keys_t[:k, :] = keys.T
    hash_lo = (np.zeros(b, dtype=np.uint32) if block.hash_lo is None
               else np.asarray(block.hash_lo))
    return (jnp.asarray(keys_t),
            jnp.asarray(np.asarray(block.key_len,
                                   dtype=np.int32).reshape(1, b)),
            jnp.asarray(np.asarray(block.hashkey_len,
                                   dtype=np.int32).reshape(1, b)),
            jnp.asarray(np.asarray(block.expire_ts,
                                   dtype=np.uint32).reshape(1, b)),
            jnp.asarray(np.asarray(block.valid,
                                   dtype=np.int32).reshape(1, b)),
            jnp.asarray(hash_lo.reshape(1, b)))


def fused_scan_block(block: RecordBlock, now: int,
                     sort_filter: Optional[FilterSpec] = None,
                     pidx: int = 0, partition_version: int = -1,
                     validate_hash: bool = False,
                     interpret: bool = False,
                     prepared: Optional[Tuple] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Returns (keep, expired) bool arrays for the block.

    Requires block.hash_lo when validate_hash (the fused path exists
    because the hash column is precomputed). `prepared` short-circuits
    the transpose for cached blocks.
    """
    sort_filter = sort_filter or FilterSpec.none()
    if validate_hash and block.hash_lo is None:
        raise ValueError("fused kernel needs a precomputed hash_lo column")
    if validate_hash and (partition_version < 0 or pidx > partition_version):
        # invalid ownership state: keep nothing, report expiry only — the
        # same reject-all gate as scan_block_predicate (split safety)
        valid = np.asarray(block.valid)
        ets = np.asarray(block.expire_ts)
        expired = (ets > 0) & (ets <= np.uint32(now)) & valid
        return np.zeros_like(valid), expired
    if prepared is None:
        prepared = prepare_transposed(block)
    keys_t, klen, hklen, ets, valid, hashlo = prepared
    pattern = np.zeros(_PATTERN_WIDTH, dtype=np.int32)
    pat_np = np.asarray(sort_filter.pattern)[:_PATTERN_WIDTH]
    pattern[:pat_np.shape[0]] = pat_np
    plen = int(sort_filter.pattern_len)
    if plen > _PATTERN_WIDTH:
        raise ValueError(f"pattern longer than {_PATTERN_WIDTH} bytes")
    scalars = np.asarray([now, plen, pidx,
                          max(partition_version, 0) & 0xFFFFFFFF],
                         dtype=np.int32)
    key_rows = keys_t.shape[0] - _PATTERN_WIDTH
    keep, expired = _fused_call(
        jnp.asarray(pattern), jnp.asarray(scalars), keys_t, klen, hklen,
        ets, valid, hashlo, key_rows=key_rows,
        sort_filter_type=sort_filter.filter_type,
        validate_hash=validate_hash, interpret=bool(interpret))
    return (np.asarray(keep[0]).astype(bool),
            np.asarray(expired[0]).astype(bool))

"""crc64 on device, in two uint32 lanes.

Bit-identical to pegasus_tpu.base.crc (and therefore to the reference's
dsn::utils::crc64_calc, src/utils/crc.cpp:464). JAX disables uint64 by
default, so the 64-bit CRC state is carried as (hi, lo) uint32 lanes:

    crc' = table[(crc ^ byte) & 0xff] ^ (crc >> 8)

with crc >> 8 computed as lo' = (lo >> 8) | (hi << 24), hi' = hi >> 8, and
the 256-entry table split into hi/lo halves. The byte loop runs over the
padded key width, vectorized across the whole record block — the same
loop order as the numpy batch implementation.

Used for on-device partition-hash validation during scans
(reference: check_pegasus_key_hash, src/base/pegasus_key_schema.h:176 —
`crc64(hashkey) & partition_version == partition_index`). Since real
partition counts fit in 32 bits, the `&`-check needs only the lo lane.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pegasus_tpu.base.crc import TABLE64_HI_NP, TABLE64_LO_NP


def crc64_device(data: jax.Array, lengths: jax.Array,
                 start: jax.Array | int = 0) -> tuple[jax.Array, jax.Array]:
    """crc64 over per-row byte regions of a padded block.

    data:    uint8[B, K]
    lengths: int32[B] — region byte count
    start:   int32[B] or scalar — region start offset
    Returns (hi, lo): uint32[B] lanes of the 64-bit CRC.
    """
    # materialized per call, NOT at module scope: importing the library
    # must never initialize a jax backend (an admin CLI would take the
    # chip from a server just by importing). Under jit these
    # become compile-time constants; the rare un-jitted call pays a
    # 64KB transfer.
    table_hi = jnp.asarray(TABLE64_HI_NP)
    table_lo = jnp.asarray(TABLE64_LO_NP)
    b, k = data.shape
    data32 = data.astype(jnp.uint32)
    starts = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,))
    hi0 = jnp.full((b,), 0xFFFFFFFF, jnp.uint32)  # ~init with init=0
    lo0 = jnp.full((b,), 0xFFFFFFFF, jnp.uint32)

    def body(j, carry):
        hi, lo = carry
        pos = jnp.clip(starts + j, 0, k - 1)
        byte = jnp.take_along_axis(data32, pos[:, None].astype(jnp.int32),
                                   axis=1)[:, 0]
        idx = ((lo ^ byte) & jnp.uint32(0xFF)).astype(jnp.int32)
        nhi = (hi >> 8) ^ table_hi[idx]
        nlo = ((lo >> 8) | (hi << 24)) ^ table_lo[idx]
        active = j < lengths
        return jnp.where(active, nhi, hi), jnp.where(active, nlo, lo)

    hi, lo = jax.lax.fori_loop(0, k, body, (hi0, lo0))
    return ~hi, ~lo


def key_hash_device(keys: jax.Array, key_len: jax.Array,
                    hashkey_len: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-record pegasus_key_hash (src/base/pegasus_key_schema.h:150):
    crc64 of the hashkey region, falling back to the sortkey region when the
    hashkey is empty. Returns (hi, lo) uint32 lanes."""
    region_len = jnp.where(hashkey_len > 0, hashkey_len, key_len - 2)
    return crc64_device(keys, region_len, start=2)


def check_partition_hash_device(keys: jax.Array, key_len: jax.Array,
                                hashkey_len: jax.Array, pidx,
                                partition_version) -> jax.Array:
    """bool[B]: does this partition serve each record (post-split check)?
    partition_version < 0 or pidx > partition_version must be handled by the
    caller (reference treats those as invalid, pegasus_server_impl.cpp:2399)."""
    _, lo = key_hash_device(keys, key_len, hashkey_len)
    pv = jnp.asarray(partition_version, jnp.uint32)
    return (lo & pv) == jnp.asarray(pidx, jnp.uint32)

"""Partition-sharded predicate evaluation over a jax Mesh.

The reference's parallelism axes (SURVEY §2.6) map to the device mesh as:

- hash partitioning ("dp"): a table's partitions are the natural shard
  dimension — partition p's record blocks live on device p % dp. The
  reference fans scans out across partitions via unordered scanners
  (src/include/pegasus/client.h:1164); here the fan-out IS the mesh axis.
- request batching ("sp"): within one partition's block, the record-batch
  dimension shards across the second mesh axis — the "long dimension"
  (SURVEY §5.7: record-batch length plays the role sequence length plays
  in ML workloads; predicates are elementwise over records, so batch
  sharding needs no halo exchange; only the final count reduction crosses
  devices via psum over both axes).

The stacked layout is [P, B, K] uint8 keys + [P, B] columns, sharded
PartitionSpec("dp", "sp", None). One jitted program evaluates scan
predicates for every partition at once and psum-reduces global match
counts over ICI — replacing the reference's per-partition scalar loops
with a single SPMD program.
"""

from __future__ import annotations

import functools
import warnings
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pegasus_tpu.ops.predicates import FilterSpec
from pegasus_tpu.ops.record_block import RecordBlock


class PartitionMesh(NamedTuple):
    mesh: Mesh
    dp: int  # partition-parallel axis size
    sp: int  # record-batch-parallel axis size


def make_mesh(n_devices: Optional[int] = None, dp: Optional[int] = None,
              devices: Optional[Sequence] = None) -> PartitionMesh:
    """2D mesh (dp, sp) over the available devices; dp defaults to all.

    Pass `devices` to build over an explicit device set. On a
    single-device host any requested dp degrades to a (1, 1) mesh with
    a warning instead of raising — solo-dev boxes must never crash the
    import path just because dp defaulted to a multi-device shape.
    """
    if devices is None:
        devices = jax.devices()[:n_devices] if n_devices else jax.devices()
    devices = list(devices)
    n = len(devices)
    if dp is None:
        dp = n
    if n == 1 and dp != 1:
        warnings.warn(f"single-device host: degrading mesh dp={dp} to a "
                      f"(1, 1) mesh", RuntimeWarning, stacklevel=2)
        dp = 1
    if n % dp:
        raise ValueError(f"{n} devices not divisible by dp={dp}")
    sp = n // dp
    arr = np.asarray(devices).reshape(dp, sp)
    return PartitionMesh(Mesh(arr, axis_names=("dp", "sp")), dp, sp)


class StackedBlocks(NamedTuple):
    """P partitions × B records, padded columnar — a pytree of arrays."""

    keys: jax.Array         # uint8[P, B, K]
    key_len: jax.Array      # int32[P, B]
    hashkey_len: jax.Array  # int32[P, B]
    expire_ts: jax.Array    # uint32[P, B]
    valid: jax.Array        # bool[P, B]
    pidx: jax.Array         # uint32[P] partition index per row


def stack_blocks(blocks: Sequence[RecordBlock],
                 pidx: Optional[Sequence[int]] = None) -> StackedBlocks:
    """Stack per-partition RecordBlocks (equal capacity/width) to [P, ...]."""
    if not blocks:
        raise ValueError("no blocks")
    caps = {(b.capacity, b.key_width) for b in blocks}
    if len(caps) > 1:
        raise ValueError(f"blocks must share shape, got {caps}")
    if pidx is None:
        pidx = list(range(len(blocks)))
    return StackedBlocks(
        keys=jnp.asarray(np.stack([np.asarray(b.keys) for b in blocks])),
        key_len=jnp.asarray(np.stack([np.asarray(b.key_len) for b in blocks])),
        hashkey_len=jnp.asarray(
            np.stack([np.asarray(b.hashkey_len) for b in blocks])),
        expire_ts=jnp.asarray(
            np.stack([np.asarray(b.expire_ts) for b in blocks])),
        valid=jnp.asarray(np.stack([np.asarray(b.valid) for b in blocks])),
        pidx=jnp.asarray(np.asarray(pidx, dtype=np.uint32)),
    )


def _scan_step(stacked: StackedBlocks, now, sort_pattern, sort_pattern_len,
               partition_version, partition_allowed,
               sort_filter_type: int, validate_hash: bool):
    """The sharded 'step': per-record keep masks + global aggregates.

    Reuses the SAME predicate program as the single-device path
    (_scan_block_predicate) by flattening [P, B] -> [P*B] and passing a
    per-record pidx vector, so the two paths cannot drift. Elementwise
    over records; the only cross-device communication is the final global
    reductions, which jit lowers to psums over the mesh.

    `partition_allowed` is bool[P]: False for partitions whose ownership
    check must reject everything (partition_version < 0 or
    pidx > partition_version — parity with scan_block_predicate's
    invalid-state gate).
    """
    from pegasus_tpu.ops.predicates import _scan_block_predicate

    p, b, k = stacked.keys.shape
    pidx_rows = jnp.repeat(stacked.pidx, b)
    no_pattern = jnp.zeros_like(sort_pattern)
    masks = _scan_block_predicate(
        stacked.keys.reshape(p * b, k),
        stacked.key_len.reshape(p * b),
        stacked.hashkey_len.reshape(p * b),
        stacked.expire_ts.reshape(p * b),
        stacked.valid.reshape(p * b),
        now, no_pattern, jnp.int32(0), sort_pattern, sort_pattern_len,
        pidx_rows, partition_version,
        hash_filter_type=0, sort_filter_type=sort_filter_type,
        validate_hash=validate_hash)
    expired = masks.expired.reshape(p, b)
    keep = masks.keep.reshape(p, b) & partition_allowed[:, None]

    total_kept = keep.sum()
    total_expired = expired.sum()
    per_partition_kept = keep.sum(axis=1)
    return keep, total_kept, total_expired, per_partition_kept


def sharded_scan_step(pmesh: PartitionMesh, stacked: StackedBlocks, now: int,
                      sort_filter: Optional[FilterSpec] = None,
                      partition_version: int = -1,
                      validate_hash: bool = False):
    """Place the stacked blocks on the mesh and run one sharded scan step.

    Returns (keep[P, B] sharded, total_kept, total_expired, per_partition
    kept counts). Shardings: data P("dp", "sp"), reductions replicated.
    """
    sort_filter = sort_filter or FilterSpec.none()
    mesh = pmesh.mesh
    data_sharding = NamedSharding(mesh, P("dp", "sp"))
    key_sharding = NamedSharding(mesh, P("dp", "sp", None))
    pid_sharding = NamedSharding(mesh, P("dp"))

    placed = StackedBlocks(
        keys=jax.device_put(stacked.keys, key_sharding),
        key_len=jax.device_put(stacked.key_len, data_sharding),
        hashkey_len=jax.device_put(stacked.hashkey_len, data_sharding),
        expire_ts=jax.device_put(stacked.expire_ts, data_sharding),
        valid=jax.device_put(stacked.valid, data_sharding),
        pidx=jax.device_put(stacked.pidx, pid_sharding),
    )

    # invalid-ownership-state gate, host-side (parity with
    # scan_block_predicate: pv < 0 or pidx > pv rejects the partition)
    pidx_np = np.asarray(stacked.pidx)
    if validate_hash and partition_version < 0:
        allowed = np.zeros(len(pidx_np), dtype=bool)
    elif validate_hash:
        allowed = pidx_np <= partition_version
    else:
        allowed = np.ones(len(pidx_np), dtype=bool)
    allowed = jax.device_put(jnp.asarray(allowed), pid_sharding)

    step = _jitted_scan_step(mesh, sort_filter.filter_type, validate_hash)
    return step(placed, jnp.uint32(now), sort_filter.pattern,
                sort_filter.pattern_len,
                jnp.uint32(max(partition_version, 0) & 0xFFFFFFFF), allowed)


@functools.lru_cache(maxsize=64)
def _jitted_scan_step(mesh: Mesh, sort_filter_type: int, validate_hash: bool):
    """One compiled program per (mesh, statics) — repeated steps hit the
    jit cache instead of re-tracing."""
    data_sharding = NamedSharding(mesh, P("dp", "sp"))
    pid_sharding = NamedSharding(mesh, P("dp"))
    return jax.jit(
        functools.partial(_scan_step, sort_filter_type=sort_filter_type,
                          validate_hash=validate_hash),
        out_shardings=(data_sharding, NamedSharding(mesh, P()),
                       NamedSharding(mesh, P()), pid_sharding),
    )

"""Device-side geo predicates: batched haversine distance filtering.

The radius-search hot loop (geo_client.h:295-335 filters every candidate
record by exact distance after the cell cover narrows the set) is a
classic per-record predicate — exactly the shape this framework
dispatches to the accelerator: one fused kernel evaluates the distance
mask for a whole candidate batch.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

EARTH_RADIUS_M = 6_371_000.0


@partial(jax.jit, static_argnames=())
def _haversine_mask(lats, lngs, valid, center_lat, center_lng, radius_m):
    lat1 = jnp.radians(center_lat)
    lat2 = jnp.radians(lats)
    dp = lat2 - lat1
    dl = jnp.radians(lngs) - jnp.radians(center_lng)
    a = (jnp.sin(dp / 2.0) ** 2
         + jnp.cos(lat1) * jnp.cos(lat2) * jnp.sin(dl / 2.0) ** 2)
    dist = 2.0 * EARTH_RADIUS_M * jnp.arcsin(
        jnp.minimum(1.0, jnp.sqrt(a)))
    return valid & (dist <= radius_m), dist


def radius_filter(lats: np.ndarray, lngs: np.ndarray,
                  center_lat: float, center_lng: float,
                  radius_m: float, valid=None):
    """(keep_mask, distances_m) for a candidate batch. Arrays are padded
    to a power-of-two bucket so repeated searches reuse one compiled
    program (the same static-shape discipline as the scan kernels).

    Every search moves its whole candidate batch to the eval device and
    the mask back, so placement follows the shared link probe
    (ops/placement.py): an accelerator on this host runs it on-chip;
    where a round-trip costs milliseconds the same program runs on the
    host XLA backend instead of paying two of them per query."""
    import contextlib

    from pegasus_tpu.ops.placement import choose_eval_device

    n = len(lats)
    if n == 0:
        return np.zeros(0, dtype=bool), np.zeros(0, dtype=np.float64)
    cap = 1 << max(6, (n - 1).bit_length())
    la = np.zeros(cap, dtype=np.float32)
    lo = np.zeros(cap, dtype=np.float32)
    va = np.zeros(cap, dtype=bool)
    la[:n] = lats
    lo[:n] = lngs
    va[:n] = True if valid is None else valid
    # per-query latency-bound movement (two link round-trips per search):
    # "ttl"-class placement — host XLA unless the accelerator's
    # round-trip is sub-millisecond-cheap
    dev = choose_eval_device(workload="ttl")
    ctx = contextlib.nullcontext()
    if dev is not None:
        ctx = jax.default_device(dev)
    with ctx:
        keep, dist = _haversine_mask(
            jnp.asarray(la), jnp.asarray(lo), jnp.asarray(va),
            jnp.float32(center_lat), jnp.float32(center_lng),
            jnp.float32(radius_m))
        return np.asarray(keep)[:n], np.asarray(dist)[:n]

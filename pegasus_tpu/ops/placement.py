"""Adaptive device placement for data-movement-bound programs.

Serving scans keep their inputs DEVICE-RESIDENT (uploaded once, masks
cached), so accelerator latency never sits on the steady-state path.
But some programs must move their whole input per call — compaction
filters (every key byte), geo distance batches (fresh candidates per
search). Placement is decided per WORKLOAD SHAPE from one link probe
taken at first use in this process (round-trip of a tiny buffer, plus
host->device and device->host rates of a 16 MiB one), which splits
these programs into two classes:

- "ttl" / "probe" — compute-trivial per byte (a compare against `now`;
  a crc/bisect over short key regions for the point-read batch gate).
  The host XLA backend streams these at memory speed with zero
  movement; the accelerator only wins when a round-trip to it is
  sub-millisecond-cheap (a device on this host's PCIe).
- "rules" / "match" — compute-dense per byte (multi-pattern substring
  matching over wide key rows, K-flavor batches). Upload cost buys K
  patterns of compute, results return bit-packed; these stay on the
  ambient accelerator unless a round-trip takes seconds.

The SAME jitted code runs either way (jax.default_device does the
placement; nothing is duplicated).
"""

from __future__ import annotations

import time
from typing import NamedTuple, Optional

import numpy as np


class LinkProbe(NamedTuple):
    """What one probe of the host<->accelerator link measured."""

    rtt_s: float      # tiny-buffer put + fetch
    h2d_gbps: float   # 16 MiB host->device
    d2h_gbps: float   # 16 MiB device->host
    device: object    # the probed (default, non-cpu) jax device


_PROBE: object = ...           # ... = unprobed; None = default device is cpu
_PROBE_BYTES = 16 << 20

# a round-trip under this means the device sits on this host: even
# compute-trivial movement-bound programs can ride the accelerator
LINK_RTT_COLOCATED_S = 0.005

# a round-trip above this means the link is pathological: nothing
# movement-bound belongs on the accelerator, however compute-dense
LINK_RTT_BROKEN_S = 2.0


def probe_link() -> Optional[LinkProbe]:
    """Measure the link to the ambient accelerator once per process.
    None when the default device is the CPU (nothing to route). A probe
    that fails on a non-CPU default device RAISES: reading it as "no
    accelerator" would silently route every program to the host."""
    global _PROBE
    if _PROBE is not ...:
        return _PROBE
    import jax
    import jax.numpy as jnp

    default = jnp.zeros(1).devices().pop()
    if default.platform == "cpu":
        _PROBE = None
        return None
    small = np.zeros(1024, dtype=np.uint8)
    np.asarray(jax.device_put(small, default))  # warm lazy client setup
    t0 = time.perf_counter()
    np.asarray(jax.device_put(small, default))
    rtt = time.perf_counter() - t0
    big = np.zeros(_PROBE_BYTES, dtype=np.uint8)
    jax.device_put(big, default).block_until_ready()  # warm allocation
    t0 = time.perf_counter()
    on_dev = jax.device_put(big, default).block_until_ready()
    h2d = time.perf_counter() - t0
    t0 = time.perf_counter()
    np.asarray(on_dev)
    d2h = time.perf_counter() - t0
    _PROBE = LinkProbe(rtt, _PROBE_BYTES / h2d / 1e9,
                       _PROBE_BYTES / d2h / 1e9, default)
    return _PROBE


def choose_eval_device(workload: str = "rules"):
    """jax.Device to place a movement-bound program on, or None to keep
    the ambient default.

    workload: "ttl"/"probe"/"scan_pushdown" (compute-trivial per byte —
    scan-pushdown value filters and aggregate folds stream the value
    heap once, host-side, because value heaps are never
    device-resident) or "rules"/"match" (compute-dense). See the module
    docstring for the policy.
    """
    import jax

    probe = probe_link()
    if probe is None:
        return None  # ambient default is already the host
    if workload in ("ttl", "probe", "scan_pushdown"):
        route_host = probe.rtt_s > LINK_RTT_COLOCATED_S
    else:
        route_host = probe.rtt_s > LINK_RTT_BROKEN_S
    if route_host:
        try:
            cpus = jax.local_devices(backend="cpu")
        except RuntimeError:  # no cpu backend registered
            return None
        return cpus[0] if cpus else None
    return None


def reset_probe() -> None:
    """Forget the cached probe (tests / backend swaps)."""
    global _PROBE
    _PROBE = ...


# modeled host constants: used for the offload BREAKDOWN and the mesh
# gates — the device-side terms come from the probe above
HOST_FILTER_GBPS_EST = 2.0  # host-side TTL/hash compare streams near
#                             memory speed (no movement at all)
HOST_DISPATCH_S_EST = 0.002  # fixed per-program dispatch cost on the
#                              host backend (jit call + mask fetch) —
#                              part of the PREDICTION so the drift
#                              gauge compares model vs measurement on
#                              the same footing for small batches


# mesh topology constants (the third placement class): a resident-mesh
# round needs no H2D movement at all — the blocks already live sharded
# on the mesh — so its cost is the dispatch floor, the cross-device
# collectives (packbits gather + psum counts travel ICI-neighbor hops),
# and the sharded predicate stream
ICI_NEIGHBOR_S_EST = 0.0002   # per-hop collective cost on the mesh
MESH_ICI_HOPS_EST = 8         # nominal ring hops per whole-table round
MESH_EVAL_GBPS_EST = 8.0      # aggregate predicate stream across shards

# a compaction row's resident predicate bytes: the same accounting the
# slab/stack builders use (key matrix ~32 B + 9 B of len/expiry
# columns) — offload_breakdown models window counts from it
MESH_COMPACT_ROW_BYTES_EST = 41


def mesh_round_fixed_s() -> float:
    """Fixed cost of one whole-table mesh dispatch: the probed
    round-trip on an accelerator mesh, the host jit-call floor on a
    mesh of CPU devices."""
    probe = probe_link()
    return probe.rtt_s if probe is not None else HOST_DISPATCH_S_EST


def _mask_download_s(mask_bytes: int) -> float:
    """Device->host return cost for a mesh result of `mask_bytes`: the
    probed downlink rate on an accelerator mesh, memory speed on a mesh
    of CPU devices."""
    probe = probe_link()
    gbps = probe.d2h_gbps if probe is not None else HOST_FILTER_GBPS_EST
    return mask_bytes / (gbps * 1e9)


def predict_mesh_compact_seconds(batch_bytes: int,
                                 mask_bytes: Optional[int] = None) -> float:
    """The model's claim for ONE whole-table mesh compaction-filter
    dispatch: the mesh round floor + ICI collectives + the sharded
    predicate stream over the resident bytes + downloading the packed
    drop masks (and rewritten-TTL column) back to the write stage.

    Unlike the scan shape, compaction's result is not just a bitmask:
    the rewritten expire_ts column rides home too when TTLs can
    change, so the downlink term is first-class here. `mask_bytes`
    defaults to the modeled 1 bit/row + 4 B/row from the row-bytes
    estimate."""
    if mask_bytes is None:
        rows = batch_bytes / MESH_COMPACT_ROW_BYTES_EST
        mask_bytes = int(rows / 8 + 4 * rows)
    return (mesh_round_fixed_s()
            + ICI_NEIGHBOR_S_EST * MESH_ICI_HOPS_EST
            + batch_bytes / (MESH_EVAL_GBPS_EST * 1e9)
            + _mask_download_s(int(mask_bytes)))


def mesh_compact_pays(n_windows: int, batch_bytes: int,
                      mask_bytes: Optional[int] = None) -> bool:
    """Does ONE resident-mesh compaction-filter round beat the host
    filter stage's `n_windows` per-window dispatches over the same
    bytes? The compaction twin of mesh_wave_pays: a solo small
    compaction (one window, one partition) has nothing to amortize the
    mesh round + mask download against and honestly stays on
    encoded_drop_mask / the host kernels; a table-wide bulk compaction
    collapses every partition's windows into one dispatch and wins."""
    host_s = (HOST_DISPATCH_S_EST * max(1, int(n_windows))
              + batch_bytes / (HOST_FILTER_GBPS_EST * 1e9))
    return predict_mesh_compact_seconds(batch_bytes, mask_bytes) < host_s


def placement_verdict(workload: str = "rules") -> str:
    """The compute class the policy routes `workload` to, as the
    PerfContext `placement` string: "device" (ambient accelerator),
    "host-XLA" (host backend — either because the ambient default IS
    the host or because the policy re-routed there), or "mesh" (the
    resident whole-table SPMD program)."""
    if workload == "mesh":
        return "mesh"
    if probe_link() is None or choose_eval_device(workload) is not None:
        return "host-XLA"
    return "device"


def predict_kernel_seconds(workload: str, batch_bytes: int) -> float:
    """The cost model's prediction for one mask-evaluation batch on the
    device the policy actually routes it to — what the workload
    profiler's drift gauge compares the measured wall time against.
    Mirrors offload_breakdown's estimates plus the fixed host dispatch
    cost (a prediction of 3µs for a 6KB batch would make every
    measurement look like 1000x drift; the model's claim includes the
    per-call floor)."""
    if workload == "mesh":
        return (mesh_round_fixed_s()
                + ICI_NEIGHBOR_S_EST * MESH_ICI_HOPS_EST
                + batch_bytes / (MESH_EVAL_GBPS_EST * 1e9))
    if workload == "mesh_compact":
        return predict_mesh_compact_seconds(batch_bytes)
    if placement_verdict(workload) == "device":
        probe = probe_link()
        return probe.rtt_s + batch_bytes / (probe.h2d_gbps * 1e9)
    return (HOST_DISPATCH_S_EST
            + batch_bytes / (HOST_FILTER_GBPS_EST * 1e9))


def mesh_wave_pays(n_programs: int, batch_bytes: int) -> bool:
    """Does ONE resident-mesh round beat the host path's `n_programs`
    per-chunk dispatches over the same bytes? The mesh routing gate:
    single-chunk waves stay on the host (same dispatch floor, nothing to
    amortize); multi-chunk / multi-partition waves collapse to one
    round and win."""
    host_s = (HOST_DISPATCH_S_EST * max(1, int(n_programs))
              + batch_bytes / (HOST_FILTER_GBPS_EST * 1e9))
    return predict_kernel_seconds("mesh", batch_bytes) < host_s


def offload_breakdown(workload: str, batch_bytes: int) -> dict:
    """Quantified pays/doesn't-pay verdict for one movement-bound
    filter batch — the compaction pipeline's filter stage logs this,
    and `shell placement` prints it. The verdict mirrors
    choose_eval_device exactly; the accelerator cost estimate is the
    probed round-trip plus the bytes at the probed upload rate."""
    probe = probe_link()
    routed_host = choose_eval_device(workload) is not None
    out = {
        "workload": workload,
        "batch_bytes": int(batch_bytes),
        "accelerator_present": probe is not None,
        "link_rtt_s": round(probe.rtt_s, 6) if probe else None,
        "link_h2d_gbps": round(probe.h2d_gbps, 3) if probe else None,
        "link_d2h_gbps": round(probe.d2h_gbps, 3) if probe else None,
        "offload_pays": probe is not None and not routed_host,
        "routed": ("host" if (probe is None or routed_host)
                   else str(probe.device)),
    }
    if probe is not None:
        out["accel_batch_s_est"] = round(
            probe.rtt_s + batch_bytes / (probe.h2d_gbps * 1e9), 6)
        out["host_batch_s_est"] = round(
            batch_bytes / (HOST_FILTER_GBPS_EST * 1e9), 6)
    out["compact"] = compact_breakdown(batch_bytes)
    return out


def compact_breakdown(batch_bytes: int,
                      n_windows: Optional[int] = None,
                      mask_bytes: Optional[int] = None) -> dict:
    """Quantified verdict for the compaction FILTER stage over
    `batch_bytes` of resident predicate columns — the mesh-vs-host twin
    of the scan-wave breakdown, so `shell placement` (and the drift
    auditor reading the `mesh_compact` class) cover the compaction
    dispatch site exactly like the wave one. Window count defaults to
    the modeled pipeline geometry (compact_pipeline.PIPELINE_WINDOW
    blocks of BLOCK_CAPACITY rows at ~MESH_COMPACT_ROW_BYTES_EST per row)."""
    rows = batch_bytes / MESH_COMPACT_ROW_BYTES_EST
    if n_windows is None:
        window_rows = 128 * 1024  # pipeline window x block capacity
        n_windows = max(1, int(-(-rows // window_rows)))
    if mask_bytes is None:
        mask_bytes = int(rows / 8 + 4 * rows)
    host_s = (HOST_DISPATCH_S_EST * max(1, int(n_windows))
              + batch_bytes / (HOST_FILTER_GBPS_EST * 1e9))
    mesh_s = predict_mesh_compact_seconds(batch_bytes, mask_bytes)
    return {
        "workload": "mesh_compact",
        "batch_bytes": int(batch_bytes),
        "n_windows": int(n_windows),
        "mask_bytes": int(mask_bytes),
        "mesh_pays": bool(mesh_s < host_s),
        "mesh_batch_s_est": round(mesh_s, 6),
        "host_batch_s_est": round(host_s, 6),
    }

"""Mesh-resident compaction filtering acceptance: ONE whole-table SPMD
dispatch must hand every sibling partition's bulk compaction its drop
masks (and rewritten-TTL column) BYTE-IDENTICALLY to the host filter
stages, run inline or on the stage threads, over every store shape — mixed
none/dcz/dcz2 histories, empty-hashkey overflow rows, verbatim-carry
blocks, default-TTL rewrites and user rulesets — degrade through the
dispatch watchdog to host filtering with identical published files, and
close the publish loop by survivor-gathering residency (reuse counter)
instead of restaging every block (rebuild counter)."""

import hashlib
import os
import shutil

# idempotent with conftest: the virtual 8-device CPU mesh must exist
# before jax initializes (standalone runs of this module included)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

import pytest

from pegasus_tpu.base.value_schema import epoch_now
from pegasus_tpu.client.client import PegasusClient
from pegasus_tpu.client.table import Table
from pegasus_tpu.ops.compaction_rules import compile_rules
from pegasus_tpu.parallel.mesh_resident import MESH_SERVING
from pegasus_tpu.storage import compact_pipeline
from pegasus_tpu.utils.flags import FLAGS

N_PARTS = 8
FROZEN_FINISH = 400_000_000  # finish-time stamp lands in the SST index

RULES = ('[{"op":"delete_key","rules":[{"type":"hashkey_pattern",'
         '"match":"prefix","pattern":"hk01"}]},'
         '{"op":"update_ttl","update_ttl_type":"from_now","value":1234,'
         '"rules":[{"type":"sortkey_pattern","match":"anywhere",'
         '"pattern":"s001"}]}]')


@pytest.fixture
def mesh_guard(monkeypatch):
    """Flag + singleton isolation, plus a frozen compaction finish-time
    stamp: manual_compact_finish_time = epoch_now() is written into the
    SST index, so two arms straddling a wall-clock second boundary
    would diverge on bytes that have nothing to do with the filter."""
    import pegasus_tpu.storage.engine as engine_mod

    saved = [(sec, name, FLAGS.get(sec, name)) for sec, name in (
        ("pegasus.storage", "block_codec"),
        ("pegasus.mesh", "serving_enabled"),
        ("pegasus.mesh", "dispatch_deadline_s"),
    )]
    monkeypatch.setattr(engine_mod, "epoch_now", lambda: FROZEN_FINISH)
    MESH_SERVING.reset()
    yield
    MESH_SERVING.reset()
    for sec, name, val in saved:
        FLAGS.set(sec, name, val)


def force_compact_pays(monkeypatch):
    """Tiny fixtures never amortize a dispatch; identity tests pin the
    gate open so every compaction exercises the mesh path (the honest
    gate has its own unit test)."""
    from pegasus_tpu.ops import placement
    monkeypatch.setattr(placement, "mesh_compact_pays",
                        lambda *_a, **_k: True)


def build_store(tmp_path, final_codec="none", rows_per_codec=200):
    """8 partitions crossing every storage shape: rows written under
    three codec generations, TTL'd rows that will expire at the arms'
    fixed filter timestamp, empty-hashkey overflow rows — then
    compacted to the pure L1 the bulk path requires (under
    `final_codec`, so dcz/dcz2 arms exercise the encoded-domain
    verbatim/subset write paths)."""
    base = str(tmp_path / "base")
    table = Table(base, partition_count=N_PARTS)
    c = PegasusClient(table)
    i = 0
    for codec in ("none", "dcz", "dcz2"):
        FLAGS.set("pegasus.storage", "block_codec", codec)
        for _ in range(rows_per_codec):
            rc = c.set(b"hk%03d" % (i % 40), b"s%05d" % i, b"v%05d" % i,
                       ttl_seconds=7 if i % 3 == 0 else 0)
            assert rc == 0
            i += 1
        assert c.set(b"", b"osk%02d" % (i % 7), b"ovf-%d" % i) == 0
        i += 1
        table.flush_all()
    FLAGS.set("pegasus.storage", "block_codec", final_codec)
    for s in table.partitions.values():
        s.engine.flush()
        s.engine.manual_compact()
    for s in table.partitions.values():
        assert s.engine.lsm.bulk_compact_eligible()
    table.close()
    return base


def digest(d):
    """(relpath, sha256) of every published SST under the table dir."""
    out = []
    for root, _dirs, files in os.walk(d):
        for f in sorted(files):
            if f.endswith(".sst"):
                p = os.path.join(root, f)
                with open(p, "rb") as fh:
                    out.append((os.path.relpath(p, d),
                                hashlib.sha256(fh.read()).hexdigest()))
    return sorted(out)


def compact_arm(base, name, now, *, mesh=False, wedge=False,
                window=compact_pipeline.PIPELINE_WINDOW, default_ttl=0,
                rules=None):
    """Copy the base store, compact every partition at the shared
    fixed `now`, return (sst digests, iterated rows, serving status).
    `window`: the pipeline window the engine weighs each snapshot
    against (more entries than it: the stage threads run)."""
    d = base + "_" + name
    shutil.rmtree(d, ignore_errors=True)
    shutil.copytree(base, d)
    MESH_SERVING.reset()
    t = Table(d, partition_count=N_PARTS)
    try:
        if mesh:
            for s in t.partitions.values():
                MESH_SERVING.attach(s)
        if wedge:
            MESH_SERVING.watchdog.deadline_s = 1e-9
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(compact_pipeline, "PIPELINE_WINDOW", window)
            for s in t.partitions.values():
                s.manual_compact(default_ttl=default_ttl,
                                 rules_filter=rules, now=now)
        st = MESH_SERVING.status()
        rows = {p: list(s.engine.lsm.iterate())
                for p, s in sorted(t.partitions.items())}
        return digest(d), rows, st
    finally:
        t.close()
        MESH_SERVING.reset()


@pytest.mark.parametrize("codec", ["none", "dcz", "dcz2"])
def test_identity_host_serial_pipelined_mesh(tmp_path, mesh_guard,
                                             monkeypatch,
                                             started_threads, codec):
    """The tentpole gate: the host filter run inline, the host filter
    on the stage threads (the engine picks by the snapshot's size),
    and the mesh filter publish the exact same bytes, and the mesh mode
    really serves the whole table from ONE dispatch (7 sibling cache
    hits)."""
    # 2-3 blocks a partition: above a window of 1, inside the module's
    base = build_store(tmp_path, final_codec=codec, rows_per_codec=4000)
    now = epoch_now() + 3600  # every ttl_seconds=7 row is expired
    serial, s_rows, _ = compact_arm(base, "serial", now)
    assert "compact-read" not in started_threads
    piped, p_rows, _ = compact_arm(base, "piped", now, window=1)
    assert started_threads.count("compact-read") == N_PARTS
    force_compact_pays(monkeypatch)
    meshed, m_rows, st = compact_arm(base, "mesh", now, mesh=True)
    assert serial == piped == meshed
    assert s_rows == p_rows == m_rows
    assert any(s_rows.values()), "degenerate fixture: nothing survived"
    assert st["compact_dispatches"] == 1
    assert st["compact_mask_serves"] == N_PARTS
    assert st["compact_mesh_fallback_count"] == 0


def test_identity_default_ttl_and_rules(tmp_path, mesh_guard,
                                        monkeypatch):
    """want_ets leg: a default-TTL rewrite plus a user ruleset
    (delete_key + update_ttl) must patch TTL headers identically
    whether the new-ets column came off the mesh or the host."""
    base = build_store(tmp_path, final_codec="dcz2")
    now = epoch_now() + 3600
    host, h_rows, _ = compact_arm(base, "host", now, default_ttl=500,
                                  rules=compile_rules(RULES))
    force_compact_pays(monkeypatch)
    meshed, m_rows, st = compact_arm(base, "mesh", now, mesh=True,
                                     default_ttl=500,
                                     rules=compile_rules(RULES))
    assert host == meshed
    assert h_rows == m_rows
    assert st["compact_dispatches"] == 1
    assert st["compact_mask_serves"] == N_PARTS


def test_wedged_watchdog_publishes_identical_files(tmp_path, mesh_guard,
                                                   monkeypatch):
    """A tripped mesh mid-compaction degrades to host filtering and
    still publishes byte-identical files — zero masks served off the
    mesh, the fallback counter proves the degradation was exercised."""
    base = build_store(tmp_path)
    now = epoch_now() + 3600
    host, h_rows, _ = compact_arm(base, "host", now)
    force_compact_pays(monkeypatch)
    wedged, w_rows, st = compact_arm(base, "wedged", now, mesh=True,
                                     wedge=True)
    assert host == wedged
    assert h_rows == w_rows
    assert st["compact_dispatches"] == 0
    assert st["compact_mesh_fallback_count"] >= 1
    assert st["watchdog"]["trips"] >= 1


def test_publish_refresh_reuses_survivor_masks(tmp_path, mesh_guard,
                                               monkeypatch):
    """Satellite pin: a compaction publish on a mesh-filtered table
    must refresh residency by survivor-gather (reuse counter, no slab
    build), while a publish the mesh did NOT filter takes the rebuild
    path — the counter split proves which happened."""
    base = build_store(tmp_path)
    now = epoch_now() + 3600
    force_compact_pays(monkeypatch)
    d = base + "_refresh"
    shutil.copytree(base, d)
    MESH_SERVING.reset()
    t = Table(d, partition_count=N_PARTS)
    try:
        for s in t.partitions.values():
            MESH_SERVING.attach(s)
        assert MESH_SERVING.ensure_current()
        builds0 = MESH_SERVING.slab_builds
        for s in t.partitions.values():
            s.manual_compact(now=now)
        assert MESH_SERVING.ensure_current()
        st = MESH_SERVING.status()
        assert st["compact_dispatches"] == 1
        # instance split (zeroed by reset); the _count twins are the
        # process-global metrics-node counters lint covers
        assert st["refresh_reuses"] == N_PARTS
        assert st["refresh_rebuilds"] == 0
        assert MESH_SERVING.slab_builds == builds0, \
            "survivor reuse must not restage a single slab"
        # the refreshed image matches the store it claims to mirror
        for pidx, s in t.partitions.items():
            tres = MESH_SERVING._tables[s.app_id]
            slab = tres.slabs[pidx]
            assert slab.generation == s.engine.lsm.generation
            assert slab.n_rows == sum(
                int(bm.count) for run in s.engine.lsm.l1_runs
                for bm in run.blocks)
        # control: a publish the mesh did not filter rebuilds
        c = PegasusClient(t)
        assert c.set(b"hk000", b"snew", b"fresh") == 0
        for s in t.partitions.values():
            s.engine.flush()
            s.engine.manual_compact()  # an L0 in the snapshot: no mesh masks
        assert MESH_SERVING.ensure_current()
        st2 = MESH_SERVING.status()
        assert st2["refresh_rebuilds"] >= 1
    finally:
        t.close()
        MESH_SERVING.reset()


def test_compact_gate_honest_and_breakdown():
    """mesh_compact_pays: a solo one-window compaction stays on the
    host; a many-window whole-table batch pays. offload_breakdown grows
    the compaction block `shell placement` renders."""
    from pegasus_tpu.ops import placement

    assert not placement.mesh_compact_pays(1, 64 * 1024)
    assert placement.mesh_compact_pays(64, 512 * 1024 * 1024)
    bd = placement.offload_breakdown("rules", 1 << 20)
    c = bd["compact"]
    assert c["workload"] == "mesh_compact"
    assert {"n_windows", "mask_bytes", "mesh_pays",
            "mesh_batch_s_est", "host_batch_s_est"} <= set(c)
    # explicit window-count override (shell placement --windows)
    c64 = placement.compact_breakdown(1 << 28, n_windows=64)
    assert c64["n_windows"] == 64
    assert c64["host_batch_s_est"] > c["host_batch_s_est"]


def test_compact_counters_lint_and_status(mesh_guard):
    """The new dispatch-site counters register through the metrics
    node (metrics_lint coverage) and surface in MESH_SERVING.status()
    for the shell placement/mesh blocks."""
    from pegasus_tpu.tools.metrics_lint import _PKG_ROOT, lint, scan_tree

    regs = scan_tree(_PKG_ROOT)
    for name in ("compact_mesh_dispatch_count",
                 "compact_mesh_fallback_count",
                 "mesh_refresh_reuse_count",
                 "mesh_refresh_rebuild_count"):
        assert name in regs, name
    assert not [c for c in lint() if "compact_mesh" in c
                or "mesh_refresh" in c]
    st = MESH_SERVING.status()
    for key in ("compact_mesh_dispatch_count",
                "compact_mesh_fallback_count",
                "mesh_refresh_reuse_count",
                "mesh_refresh_rebuild_count",
                "compact_dispatches", "compact_mask_serves",
                "refresh_reuses", "refresh_rebuilds"):
        assert key in st, key

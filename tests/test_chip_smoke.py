"""chip_smoke.py's stage functions at a tiny size on the CPU backend, and
the start-up rules the chip run relies on: no CPU mode, one process per
chip, a compile cache at a fixed path, device failures left readable."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_stage_a_tiny_matches_the_plain_model(tmp_path, monkeypatch):
    import pegasus_tpu.storage.sstable  # noqa: F401 - defines the flag
    from pegasus_tpu.ops import placement
    from pegasus_tpu.parallel.mesh_resident import MESH_SERVING
    from pegasus_tpu.utils.flags import FLAGS

    # at 250 rows a partition the cost model keeps the mesh program off;
    # the stage insists that it runs
    monkeypatch.setattr(placement, "mesh_wave_pays", lambda *a, **k: True)
    monkeypatch.setattr(placement, "mesh_compact_pays",
                        lambda *a, **k: True)
    codec = FLAGS.get("pegasus.storage", "block_codec")
    # the counters are the process's: another test file of this worker
    # may have driven a fallback on purpose before this one ran
    fallbacks = chip_smoke._fallback_counters()
    facts = chip_smoke.stage_a(
        str(tmp_path), seed=7, n_records=2000, n_partitions=8, n_nodes=3,
        n_scans=64, n_gets=64, n_sets=30, pallas_interpret=True)
    assert facts["platform"] == "cpu" and facts["records"] == 2000
    assert facts["mesh"]["wave_dispatches"] >= 1
    assert facts["mesh"]["agg_dispatches"] >= 1
    assert facts["mesh"]["compact_dispatches"] >= 1
    assert facts["mesh_image"]["partitions_per_device"] * len(
        facts["mesh_image"]["devices"]) == 8
    # raw blocks reach the device; the default codec answers scan_multi
    # masks on the host from the encoded form
    assert facts["serve_raw_blocks"]["audited_device_waves"] >= 1
    assert facts["serve_raw_blocks"]["encoded_host_probes"] == 0
    assert facts["serve_default_codec"]["encoded_host_probes"] >= 1
    assert facts["fallback_counters"] == fallbacks
    assert facts["prefresher"]["errors"] == 0
    assert set(facts["kernel_first_call_s"]) >= {
        "pallas_ft1", "static_predicate_ft2", "multi_flavor_predicate",
        "crc64_device", "compaction_eval_256k_rows"}
    # what the stage set process-wide is put back
    assert FLAGS.get("pegasus.storage", "block_codec") == codec
    assert not MESH_SERVING.enabled


def test_stage_b_tiny_over_tcp_on_cpu_nodes(tmp_path):
    facts = chip_smoke.stage_b(
        str(tmp_path), seed=7, n_records=2000, n_partitions=8, n_scans=24,
        n_gets=64, chip_node=None)
    assert "platform=cpu" in facts["node0_boot"]
    assert facts["placement"]["breakdown"]["accelerator_present"] is False
    assert facts["node0_prefresh_errors"] == 0
    with pytest.raises(chip_smoke.SmokeFailure, match="not on a TPU"):
        chip_smoke.check_stage_b_on_chip(facts)


def test_node_given_the_chip_refuses_to_boot_without_one(tmp_path):
    from pegasus_tpu.tools import onebox_cluster as ob

    d = str(tmp_path / "onebox")
    try:
        with pytest.raises(RuntimeError, match="node0 exited"):
            ob.start(d, n_replica=1, chip_node="node0")
        with open(os.path.join(d, "cluster.json")) as f:
            nodes = json.load(f)["nodes"]
        assert nodes["node0"]["device"] == "tpu"
        assert "device" not in nodes["meta"]
        with open(os.path.join(d, "logs", "node0.log")) as f:
            log = f.read()
        assert "jax found platform 'cpu'" in log and "refusing" in log
    finally:
        ob.stop(d)


def test_no_cpu_mode():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == "", "a failed run must print no result"
    assert "jax found platform 'cpu'" in r.stderr


def test_last_stdout_line_is_ok_and_device_only(tmp_path, monkeypatch,
                                                capsys):
    class Facts(dict):
        def __missing__(self, key):
            return Facts()

    stage = Facts(platform="tpu", device_kind="TPU v5 lite", n_devices=1)
    monkeypatch.setattr(chip_smoke, "run_stage", lambda *a: stage)
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    chip_smoke.main()
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    summary = json.loads(lines[-2])
    assert summary["ok"] is True and list(summary)[-1] == "claim"
    assert summary["claim"] is None
    with open(tmp_path / "summary.json") as f:
        assert json.load(f) == summary


_CACHE_PROBE = """
import jax
calls = []
real = jax.config.update
jax.config.update = lambda k, v: (calls.append(k), real(k, v))[1]
from pegasus_tpu.utils.compile_cache import configure_compile_cache
print(configure_compile_cache())
print("jax_compilation_cache_dir" in calls)
print(jax.config.jax_persistent_cache_min_compile_time_secs)
"""


@pytest.mark.parametrize("preset", [None, "/tmp/some_preset_cache"])
def test_compile_cache_helper(preset):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if preset:
        env["JAX_COMPILATION_CACHE_DIR"] = preset
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    path, updated, min_secs = r.stdout.split()
    if preset:
        assert path == preset and updated == "False"
    else:
        assert path == os.path.join(REPO, ".jax_cache")
        assert updated == "True"
    assert float(min_secs) == 0.0


def test_failed_dispatch_stays_readable_on_the_watchdog():
    from pegasus_tpu.parallel.mesh_resident import DispatchWatchdog

    wd = DispatchWatchdog(deadline_s=5.0)

    def boom():
        raise ValueError("mosaic said no")

    assert wd.run(boom) is None
    assert isinstance(wd.last_error, ValueError)
    assert "mosaic said no" in str(wd.last_error)
    assert wd.failures == 1 and wd.trips == 0
    assert wd.run(lambda: 41 + 1) == 42
    assert wd.failures == 0

"""chip_smoke.py on the CPU: the script refuses to run, its legs pass at
tiny sizes with the interpreter asked for explicitly, and the two helpers
it leans on (cache placement, roofline table) answer exactly."""

import os
import pathlib
import subprocess
import sys

import pytest

import chip_smoke

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_script_fails_without_a_chip_and_names_what_it_found():
    out = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout  # no result line


def test_device_plane_leg_tiny_interpreted():
    from brpc_tpu.ops.echo_kernel import _BLOCK

    facts = chip_smoke.leg_device_plane(
        (1 << 10,), (_BLOCK * 4,), interpret=True, chain=2)
    assert [r["step"] for r in facts["steps"]] == [
        "entry()", "single_chip_echo_step", "echo_fused"]
    assert all(r["interpret"] for r in facts["steps"])


def test_served_and_staged_legs_tiny():
    served = chip_smoke.leg_served_path((1 << 10, 1 << 16), depth=4,
                                        sync_calls=1)
    assert {(c["channel"], c["transport"]) for c in served["calls"]} == {
        ("tcp_single", "tcp"), ("tcp_pooled", ""), ("shm", "shm_ring")}
    staged = chip_smoke.leg_staged_path(1 << 16, iters=2)
    assert set(staged["legs"]) == {"ici_ring", "shm_ring", "tcp"}
    assert staged["legs"]["ici_ring"]["payload_covered"]


def test_kv_hybrid_leg_at_the_published_snapshot_record():
    """Two pages of 4 tokens, and the 20 snapshot records at their
    published 2,170,880 B: over the stripe threshold, so one-sided."""
    facts = chip_smoke.leg_kv_hybrid(2, (4, 576), chip_smoke.HYBRID_STATE)
    assert facts["transport"] == "shm_ring"
    assert facts["records"] == {"paged": 14, "snapshot": 20,
                                "published": 34}
    assert facts["record_bytes"] == {"paged": 4608, "snapshot": 2170880}
    assert facts["bytes"] == 14 * 4608 + 20 * 2170880
    assert facts["kvh_one_sided_share"] == 100.0
    assert 0 < facts["kvh_land_copy_share"] <= 100.0


def test_mesh_leg_on_the_virtual_mesh_interpreted():
    facts = chip_smoke.leg_mesh_plane(
        interpret=True, exchange_bytes_per_peer=8 * 8 * 128 * 4)
    assert facts["mesh"] == "verified" and facts["devices"] == 8
    assert facts["ring_all_gather_pallas"] == "interpreted"
    assert facts["exchange"]["sharded_over"] == 8


def test_result_line_has_the_contract_keys_and_no_others(monkeypatch, capsys):
    """The driver reads the last line of stdout and refuses any key beyond
    `ok` and `device`; the facts go on the summary line before it."""
    import json

    legs = {
        "leg_environment": {"backend_start_s": 0.0},
        "leg_native_runtime": {"recipe": "stub", "build_s": 0.0},
        "leg_device_plane": {"compile_s": 0.0,
                             "block_until_ready_waits": True},
        "leg_served_path": {"calls": [{"channel": "shm",
                                       "transport": "shm_ring"}]},
        "leg_staged_path": {"legs": {"ici_ring": {"payload_covered": True}}},
        "leg_kv_hybrid": {"transport": "shm_ring", "bytes": 1,
                          "kvh_one_sided_share": 100.0,
                          "kvh_land_copy_share": 16.0},
        "leg_mesh_plane": {"mesh": "not_run", "devices": 1},
    }
    for name, facts in legs.items():
        monkeypatch.setattr(chip_smoke, name,
                            lambda *a, _facts=facts, **k: _facts)
    assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.splitlines()
    summary, result = json.loads(lines[-2]), json.loads(lines[-1])
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert set(result) == {"ok", "device"} and result["ok"] is True
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert isinstance(result["device"]["count"], int)


def test_compile_cache_placement():
    code = ("from brpc_tpu.compile_cache import enable_compile_cache\n"
            "import jax\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")

    def run(env):
        out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        return out.stdout.split()

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    assert run(env) == [str(REPO / ".jax_cache")] * 2
    env["JAX_COMPILATION_CACHE_DIR"] = "/some/dir"
    assert run(env) == ["/some/dir"] * 2


def test_roofline_table_is_exact():
    from brpc_tpu.ops.roofline import hbm_peak_gbps

    assert hbm_peak_gbps("TPU v5 lite") == 819.0
    for kind in ("TPU v5e", "TPU v5 litepod", "cpu", ""):
        with pytest.raises(KeyError):
            hbm_peak_gbps(kind)

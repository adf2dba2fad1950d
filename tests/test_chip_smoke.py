"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

Each phase function of the chip smoke runs here with the same code path
and checks as on the chip (the kernels take their jnp oracles on CPU),
so a wrong path, argument or control flow fails in tier-1 instead of in
chip time. The four-device mesh phase runs in a child process over four
virtual CPU devices. ``main()`` itself must refuse a non-TPU platform.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = dict(n_clients=24, n_per=64, sample_rate=0.5)


@pytest.fixture(scope="module")
def fed():
    return chip_smoke.federation(TINY["n_clients"], TINY["n_per"], seed=0)


def test_phase_train_scan_tiny(fed, capsys):
    obs = chip_smoke.phase_train_scan(spans=(3, 2), fed=fed, **TINY)
    assert obs["rounds"] == 5 and obs["n_clusters"] == 4
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "train-scan" and len(line["wall_s"]) == 2


def test_phase_train_fused_bf16_tiny(fed):
    obs = chip_smoke.phase_train_fused_bf16(rounds=3, fed=fed, **TINY)
    assert obs["rounds"] == 3 and obs["ari"] >= 0.9


def test_phase_kernels_tiny(capsys):
    chip_smoke.phase_kernels(k=256, d=512, cohort=8)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "kernels" and line["merge_pairs"] > 0


def test_phase_serve_tiny(capsys):
    out = chip_smoke.phase_serve(n_requests=2)
    assert out["tokens"] == 2 * 16
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "serve" and line["requests"] == 2


def test_main_refuses_cpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out and "phase" not in out


def test_phase_mesh_four_virtual_devices():
    """The --four-chips comparison on four virtual CPU devices: identical
    integer bookkeeping, floats within tolerance, arena rows split."""
    code = ("import json, chip_smoke; "
            "print(json.dumps(chip_smoke.phase_mesh(4, n_clients=24, "
            "n_per=64, spans=(3, 2), sample_rate=0.5)))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    mesh = [json.loads(l) for l in res.stdout.splitlines()
            if l.startswith('{"phase": "mesh"')]
    assert mesh and mesh[0]["arena_rows_per_device"] == [6]
    assert mesh[0]["scan_all_reduces"] > 0

"""``chip_smoke.py`` on the CPU: its phases at a tiny size (the Pallas
kernel in interpret mode), and its refusal to report anything when no
TPU is present."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_phases_tiny_interpret():
    devices = chip_smoke.check_device(1, platform="cpu")
    facts = chip_smoke.run(devices, n_docs=400, n_mixed=24, n_stop=24,
                           interpret=True, long_l=1024)
    assert [f["label"] for f in facts] == [
        "raw@1chip", "compressed@1chip", "raw+pallas@1chip",
        "compressed+pallas@1chip"]
    for f in facts:
        assert f["fallbacks"] == {} and "scalar" not in f["routes"]
        assert {"qt1", "qt2", "qt34", "qt5"} <= set(f["routes"])
        assert f["executables"] > 0 and f["bucket_hist"]


def test_serve_phase_refuses_unexpected_pallas_mode():
    """A caller that expects the compiled kernel (the chip run) is
    refused on a mesh where the kernel could only be interpreted."""
    from repro.launch.mesh import mesh_on
    from repro.serving import ServeConfig

    import jax

    table, lex, index = chip_smoke.build_phase(120)
    sets = {"mixed": chip_smoke.query_sets(table, lex, 4, 0)["mixed"]}
    refs = {"mixed": chip_smoke.reference_sets(index, sets["mixed"])}
    with pytest.raises(chip_smoke.SmokeFailure, match="interpret"):
        chip_smoke.serve_phase(index, mesh_on(jax.devices()[:1]), sets, refs,
                               ServeConfig(use_pallas=True), "pallas",
                               interpret=False)


def test_main_fails_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "no tpu device" in err


def test_script_alone_fails(tmp_path):
    """Copied into a directory with nothing else of the repo, the script
    exits non-zero and prints no result line."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout

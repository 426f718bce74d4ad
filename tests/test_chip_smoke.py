"""chip_smoke.py off the chip: the loud failure, and the CPU rehearsal.

The script itself runs only on a TPU.  What can be shown here: with no
accelerator it exits non-zero and prints no result, and its phases (the
same functions ``main`` runs, at a tiny size) hold on the CPU backend
and the virtual 8-device mesh — wrong paths, arguments and control flow
are found here, not on chip time.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(batch=256, queue_capacity=1 << 14,
                        seen_capacity=1 << 17, server_depth=4)


def _cpu_device():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "bytes_limit": None}


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_no_accelerator_exits_nonzero_and_prints_no_result(args):
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")]
                       + args, capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode not in (0, None), r.stdout
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_alone_without_the_repo_fails(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300,
                       env={k: v for k, v in os.environ.items()
                            if k != "PYTHONPATH"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_rehearse_exhaustive_phase(tmp_path, capsys):
    chip_smoke.phase_exhaustive(5, _cpu_device(), str(tmp_path), TINY)
    assert "every level == oracle" in capsys.readouterr().out


def test_rehearse_counterexample_and_swarm_phases(capsys):
    chip_smoke.phase_counterexample()
    chip_smoke.phase_swarm()
    out = capsys.readouterr().out
    assert "NativeTraceStore" in out and "[4 swarm]" in out


def test_rehearse_server_phase(capsys):
    chip_smoke.phase_server("cpu", TINY)
    assert "engine-cache hit" in capsys.readouterr().out


def test_rehearse_mesh_phase(tmp_path, capsys):
    chip_smoke.phase_mesh(5, _cpu_device(), str(tmp_path), TINY)
    assert "same per-level counts" in capsys.readouterr().out

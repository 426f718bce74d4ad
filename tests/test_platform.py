"""utils/platform.py: where the compile cache lives, and that an
accelerator is never given an assumed memory size."""

import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = (
    "import jax\n"
    "from raft_tla_tpu.utils.platform import cache_dir, "
    "enable_persistent_cache\n"
    "enable_persistent_cache()\n"
    "print('DIR', jax.config.jax_compilation_cache_dir, cache_dir())\n")


def _probe(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c", _PROBE], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-1500:]
    return r.stdout.split("DIR", 1)[1].split()


def test_cache_dir_follows_the_environment(tmp_path):
    d = str(tmp_path / "cc")
    assert _probe(d) == [d, d]


def test_cache_dir_unset_is_one_fixed_directory():
    want = os.path.join(REPO, ".jax_cache")
    assert _probe(None) == [want, want]


def test_accelerator_without_memory_stats_is_an_error(monkeypatch):
    import jax

    from raft_tla_tpu.engine import bfs

    def fake(platform, stats):
        return types.SimpleNamespace(platform=platform, device_kind="fake",
                                     memory_stats=lambda: stats)

    monkeypatch.setattr(jax, "devices", lambda *a: [fake("tpu", None)])
    with pytest.raises(RuntimeError, match="bytes_limit"):
        bfs._auto_capacities(473, 2048, True)
    # A reported limit sizes from the device; the CPU gets its defaults.
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [fake("tpu", {"bytes_limit": 16 << 30})])
    q, s = bfs._auto_capacities(473, 2048, True)
    assert q > 1 << 20 and s > 1 << 22
    monkeypatch.setattr(jax, "devices", lambda *a: [fake("cpu", None)])
    assert bfs._auto_capacities(473, 2048, True) == (1 << 20, 1 << 22)

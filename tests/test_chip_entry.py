"""The chip entry point refuses the CPU, and the compile-cache helper puts
the persistent cache where the environment or the checkout says."""

import os
import shutil
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from repro.launch import cache  # noqa: E402


@pytest.mark.parametrize("argv", [[], ["--mesh4"]])
def test_chip_smoke_refuses_cpu(argv, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    assert "platform=cpu" in out


def test_chip_smoke_alone_fails(tmp_path):
    """Without the repository next to it the script fails, printing no
    result."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_cache_dir_follows_environment(monkeypatch, tmp_path):
    monkeypatch.setenv(cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.compile_cache_dir() == str(tmp_path)
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv(cache.ENV, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert cache.compile_cache_dir() == want
    assert cache.compile_cache_dir() == want
    before = jax.config.jax_compilation_cache_dir
    try:
        assert cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert cache.enable_compile_cache() == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

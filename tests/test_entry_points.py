"""What the entry points promise before anything runs: where the
persistent compile cache lives, and that `chip_smoke.py` refuses any run
that is not the compiled path on a TPU.

`enable_compile_cache` is never called for real here (the suite keeps no
persistent cache): the tests watch `jax.config.update` instead. The smoke
runs as a child process with its own environment, on the CPU, where it
must exit non-zero without printing its ``ok`` line.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.utils import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_leaves_env_dir_to_jax(config_updates, monkeypatch,
                                             tmp_path):
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert config_updates == []


def test_compile_cache_defaults_to_ignored_repo_dir(config_updates,
                                                    monkeypatch):
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    path = REPO / ".jax_cache"
    assert compile_cache.enable_compile_cache() == str(path)
    assert config_updates == [("jax_compilation_cache_dir", str(path))]
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def _run_smoke(cwd: Path, tmp_path: Path, **env_extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("REPRO_KERNEL_MODE", "REPRO_SWEEP_ENGINE",
                        "PYTHONPATH")}
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               **env_extra)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def _printed_ok(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if json.loads(line).get("ok"):
                return True
        except (ValueError, AttributeError):
            continue
    return False


@pytest.mark.parametrize("env_extra, reason", [
    ({}, "not 'tpu'"),
    ({"REPRO_KERNEL_MODE": "reference"}, "REPRO_KERNEL_MODE"),
    ({"REPRO_SWEEP_ENGINE": "vmap"}, "REPRO_SWEEP_ENGINE"),
])
def test_chip_smoke_refuses_off_the_compiled_tpu_path(tmp_path, env_extra,
                                                      reason):
    proc = _run_smoke(REPO, tmp_path, **env_extra)
    assert proc.returncode != 0
    assert not _printed_ok(proc.stdout)
    assert reason in proc.stderr


def test_chip_smoke_alone_fails(tmp_path):
    """Without the repo around it the script cannot import the system."""
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    proc = _run_smoke(tmp_path, tmp_path)
    assert proc.returncode != 0
    assert not _printed_ok(proc.stdout)
    assert "ModuleNotFoundError: No module named 'repro'" in proc.stderr

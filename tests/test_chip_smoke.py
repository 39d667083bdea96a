"""`chip_smoke.py` rehearsed on CPU, and the compile-cache helper the
entry points call first.

The smoke script's ``--tiny`` mode runs the same batch and serving
phases as the chip run at n = 2,000, with the Pallas kernels in
interpret mode; every check it makes must pass and its last line must
be the result object.
"""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def no_cache_env(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set to a value JAX never read (it reads
    the variable at import): the helper must leave the config alone,
    so nothing here turns the process's cache on."""
    monkeypatch.setenv(compile_cache.ENV, str(ROOT / "artifacts" / "x"))


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tiny_smoke_passes_on_cpu(no_cache_env, capsys):
    smoke = _load_smoke()
    assert smoke.main(["--tiny"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert "FAIL" not in "\n".join(lines)
    last = json.loads(lines[-1])
    dev = jax.devices()[0]
    assert last == {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": 1}}


def test_smoke_refuses_cpu_without_tiny(no_cache_env, capsys):
    smoke = _load_smoke()
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_cache_helper_leaves_env_dir_to_jax(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_helper_uses_fixed_checkout_dir(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.enable_compile_cache()
        assert first == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        assert compile_cache.enable_compile_cache() == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


def test_library_import_sets_no_cache():
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV}
    code = ("import jax, repro.encoder, repro.serving, repro.transport, "
            "repro.kernels.ops; "
            "print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "None"

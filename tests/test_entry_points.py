"""Entry-point plumbing: compile-cache placement and the chip smoke's
refusal to run without a TPU."""
from __future__ import annotations

import importlib.util
import pathlib
import sys

import jax

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _record_config_updates(monkeypatch) -> list:
    # a real update would initialize JAX's cache in this worker process
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = _record_config_updates(monkeypatch)
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert calls == []


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _record_config_updates(monkeypatch)
    got = compile_cache.setup_compile_cache()
    assert got == str(ROOT / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", got)]
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_chip_smoke_refuses_a_host_without_tpu(capsys):
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    argv, sys.argv = sys.argv, ["chip_smoke.py"]
    try:
        rc = smoke.main()
    finally:
        sys.argv = argv
    out, err = capsys.readouterr()
    assert rc != 0
    assert "no TPU found" in err
    assert '"ok"' not in out

"""The guards around running on the chip, checked on the CPU: the one
interpret-mode switch, the compile-cache placement, and ``chip_smoke.py``
refusing to run (and to report success) without a TPU."""

import importlib.util
from pathlib import Path

import jax
import pytest

from repro.kernels import interpret_mode
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_interpret_mode_is_on_for_the_cpu_backend():
    assert jax.default_backend() == "cpu"
    assert interpret_mode() is True


def test_interpret_mode_refuses_other_backends(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        interpret_mode()


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_uses_the_environment_when_set(monkeypatch,
                                                      cache_dir_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_ignored_path(monkeypatch,
                                                        cache_dir_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.enable_compile_cache() == got     # stable per call
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_chip_smoke_refuses_the_cpu(capsys):
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    with pytest.raises(SystemExit) as exit_:
        chip_smoke.main([])
    assert exit_.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out

"""Where the persistent compilation cache goes, and the chip smoke test's
refusal to run anywhere but a TPU."""
import importlib.util
import json
import shutil
from pathlib import Path

import jax
import pytest

from repro.runtime import compile_cache as cc

ROOT = Path(__file__).resolve().parents[1]
_KEYS = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
         "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def restore_cache_config():
    saved = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_cache_dir_from_environment(monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cc.enable_compile_cache() == str(tmp_path) == cc.cache_dir()
    # JAX reads the variable itself; the program sets no other directory
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_the_checkout(monkeypatch, restore_cache_config):
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    path = cc.enable_compile_cache()
    assert path == cc.cache_dir() == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def _load_smoke(path: Path):
    spec = importlib.util.spec_from_file_location("chip_smoke_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _assert_no_result(out: str):
    for line in out.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"a result line was printed: {line}")


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_chip_smoke_refuses_a_cpu_platform(argv, capsys):
    """In this process JAX has only the CPU: the smoke test must exit
    non-zero and print no result, never fall back."""
    assert jax.devices()[0].platform != "tpu"
    assert _load_smoke(ROOT / "chip_smoke.py").main(argv) != 0
    out = capsys.readouterr()
    _assert_no_result(out.out)
    assert "no TPU" in out.err


def test_chip_smoke_refuses_to_run_without_the_repository(tmp_path, capsys):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(ROOT / "chip_smoke.py", lone)
    assert _load_smoke(lone).main([]) != 0
    out = capsys.readouterr()
    _assert_no_result(out.out)
    assert "no repository source" in out.err

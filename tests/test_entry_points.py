"""The CLI entry points: the serve exit code and the compile-cache helper."""
import os

import jax
import pytest

from repro import compile_cache
from repro.launch import serve as serve_cli
from repro.serve.engine import EngineCore

SERVE_ARGV = ["--arch", "gpt2-117m", "--reduced", "--batch", "2",
              "--requests", "3", "--prompt-len", "16", "--gen", "4"]


@pytest.mark.parametrize("argv,prefill_raises,want", [
    (SERVE_ARGV, False, 0),
    (SERVE_ARGV, True, 1),
    (SERVE_ARGV + ["--replicas", "2"], True, 1),
], ids=["engine-clean", "engine-prefill-raises", "router-prefill-raises"])
def test_serve_main_exit_code(monkeypatch, argv, prefill_raises, want):
    """A prefill that raises (a compile error on the chip lands there)
    retires its requests as errors; main must not report success."""
    monkeypatch.setattr(serve_cli, "enable_compile_cache", lambda: "")
    if prefill_raises:
        init = EngineCore.__init__

        def broken_init(self, *a, **kw):
            init(self, *a, **kw)

            def _prefill(*_):
                raise RuntimeError("prefill failed to compile")
            self._prefill = _prefill
        monkeypatch.setattr(EngineCore, "__init__", broken_init)
    assert serve_cli.main(argv) == want


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_dir_wins(monkeypatch, tmp_path,
                                    restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                    restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert compile_cache.enable_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == os.path.join(root, ".jax_cache")

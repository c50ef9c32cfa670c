"""Where the serving entry points keep JAX's persistent compilation cache:
the directory ``JAX_COMPILATION_CACHE_DIR`` names, else one fixed
directory inside the checkout (ignored by git).  Only the placement is
tested; the cache itself is never turned on here."""
import os

from repro.launch import serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    assert serve.compile_cache_dir() == str(tmp_path / "xla")


def test_compile_cache_dir_defaults_to_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = serve.compile_cache_dir()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert path == serve.compile_cache_dir()  # no pid / time / temp name
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache" in f.read().split()

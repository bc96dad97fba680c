"""The compile-cache helper: placeable from outside, fixed otherwise."""

import os
import subprocess
import sys

from areal_tpu.base import compile_cache


def test_env_var_is_left_to_jax(monkeypatch, tmp_path):
    import jax

    placed = str(tmp_path / "elsewhere")
    monkeypatch.setenv(compile_cache.ENV_VAR, placed)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == placed
    # nothing set in code: jax's own config is untouched
    assert jax.config.jax_compilation_cache_dir == before
    assert os.environ[compile_cache.ENV_VAR] == placed


def test_default_is_one_fixed_path_inside_the_checkout(monkeypatch):
    import jax

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.setup_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(compile_cache.__file__))))
        assert got == os.path.join(repo, ".jax_cache")
        assert got == compile_cache.DEFAULT_CACHE_DIR  # no pid/time/temp
        # children inherit it through the environment
        assert os.environ[compile_cache.ENV_VAR] == got
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        os.environ.pop(compile_cache.ENV_VAR, None)


def test_importing_the_helper_does_not_import_jax():
    code = (
        "import sys; import areal_tpu.base.compile_cache as c; "
        "d = c.setup_compile_cache(); assert 'jax' not in sys.modules; "
        "import jax; assert jax.config.jax_compilation_cache_dir == d"
    )
    env = {k: v for k, v in os.environ.items() if k != compile_cache.ENV_VAR}
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=120)


def test_cache_entry_count(tmp_path):
    assert compile_cache.cache_entry_count(str(tmp_path / "missing")) == 0
    for name in ("jit_f-abc-cache", "jit_f-abc-atime", "jit_g-def-cache"):
        (tmp_path / name).write_bytes(b"x")
    assert compile_cache.cache_entry_count(str(tmp_path)) == 2

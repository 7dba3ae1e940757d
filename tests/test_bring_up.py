"""Bring-up guards: where compiles are cached, how a native build
failure surfaces, and that process-mode workers stay off JAX."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COMPILE = """
import sys
from sparkrdma_tpu.utils.compile_cache import enable_compile_cache
d = enable_compile_cache()
import jax, jax.numpy as jnp
c = float(sys.argv[1])
jax.jit(lambda x: x * c + 1.0)(jnp.arange(8.0)).block_until_ready()
print(d)
"""


def _compile_in_child(tmp_path, env_cache_dir, const):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_cache_dir)
    proc = subprocess.run(
        [sys.executable, "-c", _COMPILE, str(const)],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()[-1]


def _entries(path):
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def test_compile_cache_follows_env_var(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, entries land there and the
    checkout's own cache gains nothing."""
    default = os.path.join(ROOT, ".jax_cache")
    before = _entries(default)
    target = tmp_path / "cache"
    used = _compile_in_child(tmp_path, target, 3.25)
    assert used == str(target)
    assert any(name.startswith("jit_") for name in _entries(target))
    assert _entries(default) - before == set()


def test_compile_cache_defaults_to_checkout(tmp_path):
    """Without the variable the cache is <checkout>/.jax_cache."""
    default = os.path.join(ROOT, ".jax_cache")
    before = _entries(default)
    # a constant no other run compiles, so the entry is new
    used = _compile_in_child(tmp_path, None, 7.0 + os.getpid() / 1e9)
    assert used == default
    new = _entries(default) - before
    try:
        assert any(name.startswith("jit__lambda") for name in new)
    finally:
        for name in new:
            os.unlink(os.path.join(default, name))


def test_native_build_failure_carries_stderr(tmp_path):
    """A source that does not compile raises with g++'s own message,
    and no object is left behind under the cache name."""
    from sparkrdma_tpu.native import transport_lib

    if transport_lib.shutil.which("g++") is None:
        pytest.skip("no g++ here")
    src = tmp_path / "broken.cpp"
    src.write_text("int f( { return 0; }\n")
    with pytest.raises(transport_lib.NativeBuildError, match="error"):
        transport_lib.build_library("_libsrt_test_broken", str(src))
    assert not os.path.exists(
        transport_lib.so_path("_libsrt_test_broken", str(src))
    )


def test_native_cache_name_tracks_source(tmp_path):
    """The cached object's name changes with the source bytes, so a
    binary built from other source never loads."""
    from sparkrdma_tpu.native import transport_lib

    src = tmp_path / "a.cpp"
    src.write_text("int f() { return 1; }\n")
    first = transport_lib.so_path("_libsrt_x", str(src))
    src.write_text("int f() { return 2; }\n")
    assert transport_lib.so_path("_libsrt_x", str(src)) != first


def test_explicit_native_transport_fails_loudly(monkeypatch):
    """transport=native that cannot build raises with the build error
    instead of quietly starting the Python node."""
    from sparkrdma_tpu.native import transport_lib
    from sparkrdma_tpu.transport import create_node
    from sparkrdma_tpu.utils.config import TpuShuffleConf

    monkeypatch.setattr(transport_lib, "available", lambda: False)
    monkeypatch.setattr(
        transport_lib, "build_error", lambda: "g++: injected failure"
    )
    conf = TpuShuffleConf({"tpu.shuffle.transport": "native"})
    with pytest.raises(RuntimeError, match="injected failure"):
        create_node(conf, "127.0.0.1", True, "native-missing")
    auto = TpuShuffleConf({"tpu.shuffle.transport": "auto"})
    assert auto.transport == "python"


def test_engine_worker_import_stays_off_jax():
    """Process-mode cluster workers must never initialise a JAX
    backend (one process per chip): importing the worker module pulls
    in no jax at all."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, sparkrdma_tpu.engine.worker; "
         "print('jax' in sys.modules)"],
        env=dict(os.environ, PYTHONPATH=ROOT),
        cwd=ROOT, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "False"

"""The persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, and otherwise to the fixed in-repo ``.jax_cache/``.  Each case runs in
a fresh interpreter: the cache directory is process-global JAX state."""
import os
import subprocess
import sys

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

_SNIPPET = """
import jax, jax.numpy as jnp
from repro.compile_cache import REPO_CACHE_DIR, enable_compile_cache
path = enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == path
print(path)
print(REPO_CACHE_DIR)
if {compile}:
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
"""


def _run(env_dir, compile_):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        # cache even a sub-second CPU compile, so the directory fills
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-c", _SNIPPET.format(compile=compile_)],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert r.returncode == 0, r.stderr
    path, repo_dir = r.stdout.split()[:2]
    return path, repo_dir


def test_cache_honours_env_dir(tmp_path):
    target = str(tmp_path / "jax-cache")
    path, _ = _run(target, True)
    assert path == target
    assert os.listdir(target), "no cache entry written"


def test_cache_defaults_to_fixed_repo_dir():
    path, repo_dir = _run(None, False)
    assert path == repo_dir
    root = os.path.dirname(SRC)
    assert path == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()

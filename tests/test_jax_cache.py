"""Where the persistent compile cache lives (utils/jax_cache.py).

Placed from outside: with ``JAX_COMPILATION_CACHE_DIR`` set the program
sets no directory in code; unset, it is the fixed ``<checkout>/.jax_cache``
whether or not the checkout is a git repository (the chip tool's copy is
not one). Each case runs in a fresh interpreter, because jax reads the
variable once, at import.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = (
    "import jax\n"
    "from memgraph_tpu.utils.jax_cache import ensure_compile_cache\n"
    "assert ensure_compile_cache()\n"
    "print(jax.config.jax_compilation_cache_dir)\n")


def _cache_dir_seen_by(checkout, env_dir=None) -> str:
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "MEMGRAPH_TPU_COMPILE_CACHE")}
    env["PYTHONPATH"] = str(checkout)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run([sys.executable, "-c", _PROBE], cwd=str(checkout),
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.strip().splitlines()[-1]


@pytest.fixture
def checkout(tmp_path):
    """A checkout that is not this repository: the package, linked."""
    root = tmp_path / "checkout"
    root.mkdir()
    os.symlink(os.path.join(REPO, "memgraph_tpu"), root / "memgraph_tpu")
    return root


def test_environment_places_the_cache(checkout, tmp_path):
    placed = tmp_path / "some" / "dir"
    assert _cache_dir_seen_by(checkout, env_dir=placed) == str(placed)
    # nothing was set, or made, in code
    assert not (checkout / ".jax_cache").exists()


@pytest.mark.parametrize("is_git_repository", [True, False])
def test_default_is_the_checkout(checkout, is_git_repository):
    if is_git_repository:
        (checkout / ".git").mkdir()
    assert _cache_dir_seen_by(checkout) == str(checkout / ".jax_cache")
    assert (checkout / ".jax_cache").is_dir()


def test_the_private_variable_is_gone(checkout, tmp_path, monkeypatch):
    monkeypatch.setenv("MEMGRAPH_TPU_COMPILE_CACHE_DIR",
                       str(tmp_path / "old"))
    assert _cache_dir_seen_by(checkout) == str(checkout / ".jax_cache")

"""Start-up contract of every entry point (utils/backend.py, chip_smoke.py):
the persistent compile cache can be placed from outside
(``JAX_COMPILATION_CACHE_DIR`` set: the code sets no path), otherwise it
lives at ONE fixed path inside the checkout — the path is part of the
cache key, so a directory that moves never hits — and the chip smoke
refuses to run where JAX finds no accelerator."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# jit one trivial program with the size/time floors off, so the cache
# directory in force receives an entry
_COMPILE_ONE = (
    "import sys; sys.path.insert(0, %r)\n"
    "from split_learning_tpu.utils import configure_compile_cache\n"
    "d = configure_compile_cache()\n"
    "import jax, jax.numpy as jnp\n"
    "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
    "jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)\n"
    "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n"
    "print(d)\n"
    "print(jax.config.jax_compilation_cache_dir)\n" % REPO)


def _run(code: str, cwd: str, **env):
    full_env = dict(os.environ, JAX_PLATFORMS="cpu")
    full_env.pop("JAX_COMPILATION_CACHE_DIR", None)
    full_env.update(env)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=full_env, cwd=cwd, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()


def test_env_placed_cache_is_left_to_jax(tmp_path):
    placed = tmp_path / "placed_cache"
    returned, in_force = _run(_COMPILE_ONE, cwd=str(tmp_path),
                              JAX_COMPILATION_CACHE_DIR=str(placed))[-2:]
    assert returned == in_force == str(placed)
    assert os.listdir(placed), "no cache entry written to the placed dir"


def test_unset_uses_the_fixed_checkout_path_in_every_process(tmp_path):
    fixed = os.path.join(REPO, ".jax_cache")
    # two processes, two working directories: the path derives from the
    # checkout, never from cwd, a pid, tempfile or the clock
    for cwd in (REPO, str(tmp_path)):
        returned, in_force = _run(_COMPILE_ONE, cwd=cwd)[-2:]
        assert returned == in_force == fixed
    assert os.listdir(fixed)


def test_chip_smoke_refuses_to_run_without_an_accelerator():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, cwd=REPO, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "'cpu'" in out.stderr and "tpu" in out.stderr
    # no result line, and no leg was started
    assert not out.stdout.strip(), out.stdout

"""Process start-up: where the XLA compile cache lives, and the CPU pin
for measurement scripts that must never take the accelerator.

A chip belongs to one process at a time, and every new process compiles
from nothing unless it finds JAX's persistent compilation cache. The
cache key includes the directory's path, so the directory must not move
between runs: it is either where ``JAX_COMPILATION_CACHE_DIR`` says (JAX
reads that variable itself) or one fixed directory inside the checkout.
"""

from __future__ import annotations

import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the one in-checkout cache location (listed in .gitignore); never derived
# from tempfile, a pid or the clock — a path that moves never hits. For an
# installed copy (pip install .) this is site-packages/.jax_cache, which is
# no checkout and often not writable: such a deployment places the cache
# through JAX_COMPILATION_CACHE_DIR (README, Quickstart)
COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def configure_compile_cache() -> str:
    """Place the persistent XLA compile cache; return its directory.

    Called first thing by every entry point (the CLI, the benchmark,
    chip_smoke.py, __graft_entry__.py). With ``JAX_COMPILATION_CACHE_DIR``
    set this sets nothing — JAX already reads it — so the cache can be
    placed from outside; otherwise it points JAX at
    :data:`COMPILE_CACHE_DIR`. Safe to call more than once."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def reexec_pinned_cpu() -> None:
    """Replace this process with a ``JAX_PLATFORMS=cpu`` copy of itself
    unless it already is one. For CPU-only measurement scripts: the pin
    must exist before jax is imported, so a script that decides on CPU
    from Python re-execs once. Call from ``__main__`` only — importing a
    module must never replace the importing process. Extra env (e.g.
    XLA_FLAGS) belongs after the call: on return the process is pinned
    and jax is not yet imported."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    os.execve(sys.executable, [sys.executable] + sys.argv, env)

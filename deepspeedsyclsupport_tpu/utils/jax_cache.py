"""Where jax's persistent compilation cache lives for this checkout.

The directory is part of every cache key, so it must not move between
processes: a machine that comes with ``JAX_COMPILATION_CACHE_DIR`` set keeps
its cache there (jax reads the variable itself — nothing is set in code),
and everywhere else every entry point shares ONE fixed, git-ignored
directory inside the checkout. ``utils/compile_cache.py`` is the opposite
trade (a per-process staging path that can never hit, for the opt-in CPU
test suite's torn-write safety) and is not used here.
"""
import os

#: fixed fallback, relative to the checkout root (listed in ``.gitignore``)
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_compile_cache() -> str:
    """Point jax at the compile cache; returns the directory in use. Call
    before the first compile (``chip_smoke.py``, ``benchmark/run.py``,
    ``__graft_entry__.py``)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR

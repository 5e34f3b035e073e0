"""Where jax's persistent compilation cache lives for this checkout.

One rule, for every entry point that compiles on the chip
(`chip_smoke.py`, `__graft_entry__.py`) and for the test suite
(`tests/conftest.py`): the cache directory is placed from OUTSIDE the
program. If `JAX_COMPILATION_CACHE_DIR` is set, jax reads it itself and
nothing here touches it. Otherwise the cache sits at a fixed path inside
the checkout, `<checkout>/.jax_cache` (git-ignored) — fixed because the
directory is part of how a later process finds the entries again: a
temp name, a pid or a time in the path never hits.

No other code sets `jax_compilation_cache_dir`; `compile_fresh` below
switches the cache off around one compile and puts back exactly what
it found.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Point jax at the compile cache; call before the first jit.
    Returns the directory in use. Initialises no backend."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def compile_fresh(fn, *args):
    """`fn` (an `instrumented_jit`) lowered and compiled for `args` into
    an executable that is built HERE: not out of the persistent cache,
    and not the one `fn` already holds.

    For executables that will be SERIALIZED (`serving/fleet/export.py`):
    on the installed jax (0.9.0, checked on the CPU backend) an
    executable the cache handed back serializes into a payload that
    loads and then fails at run time with "NOT_FOUND: ... Function
    <fusion> not found", while a freshly compiled one round-trips.
    jax decides once per process whether the cache is in use, so the
    switch-off has to be bracketed with `reset_cache()` both ways.
    And a jit that already ran shares its lowering with `lower()`:
    `compile()` of that lowering hands back the executable the run
    loaded (out of the cache, if the run hit it) without compiling
    anything, so the lowering comes from a jit of its own (`rejit()`)."""
    from jax.experimental.compilation_cache import compilation_cache
    lowered = fn.rejit().lower(*args)
    cache_dir = jax.config.jax_compilation_cache_dir
    if cache_dir is None:
        return lowered.compile()
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()
    try:
        return lowered.compile()
    finally:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        compilation_cache.reset_cache()

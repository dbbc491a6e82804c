"""Where JAX's persistent compilation cache lives — one rule, one place.

Every entry point (trainers, self-play CLI, GTP engine, gateway,
``chipbench/run.py``, ``chip_smoke.py``'s legs and the test suite's
conftest) calls :func:`enable_compile_cache` before its
first compile, so a re-launch of the SAME program loads its executables
instead of compiling them again.

The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set in the environment → the cache is
  placed from outside. JAX reads that variable itself; this code sets
  no directory.
* unset → ``<checkout>/.jax_cache``: a FIXED, git-ignored path next to
  the package. The directory is part of what a run on a fresh machine
  can find again, so it never comes from ``tempfile``, a pid or the
  clock.

One cache option IS set, in every case: metadata is part of the key
(see :func:`enable_compile_cache`), so an edit that only renames a
``jax.named_scope`` — or moves a traced line — compiles once more.

There is no knob of this repo's own: JAX's switches
(``JAX_ENABLE_COMPILATION_CACHE=false``,
``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``, …) cover "off" and the
thresholds.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
#: the fixed in-checkout default (``rocalphago_tpu/runtime/`` is two
#: levels below the checkout root)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    """The directory the rule above puts the cache in (jax-free: the
    smoke's parent process reads it to count entries)."""
    return os.environ.get(ENV) or CHECKOUT_CACHE_DIR


def enable_compile_cache() -> str:
    """Apply the rule above; returns the directory in effect. Safe to
    call from any entry point, any number of times."""
    import jax

    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir",
                          CHECKOUT_CACHE_DIR)
    # a profiler trace is read by the HLO's metadata (the scope names
    # of obs/scopes.py), which JAX leaves out of the cache key by
    # default: a program would then load whichever executable with
    # the same computation was compiled first — the parent commit's,
    # say, without the names — and its trace would carry those
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    return cache_dir()

"""Persistent XLA compilation cache wiring (warm-path leg 1).

In a GSPMD/pjit system the compiled executable IS the program, so every
process historically paid the full trace+compile on step 1 of every run
(engine/steps.instrument_step labels it ``<name>/compile``) and the
serving engine recompiled its ladder on every restart. jax ships a
content-addressed on-disk executable cache behind
``jax_compilation_cache_dir``; this module is the one place that
decides where it lives, so every entrypoint (train.py, test.py,
serve.py, generate.py) behaves identically.

The rule, in this order:

(a) ``JAX_COMPILATION_CACHE_DIR`` set in the environment: that
    directory is used and nothing in the repo sets another.
(b) else a directory given explicitly — the ``cache_dir`` argument, or
    the config section below — is used.
(c) else the cache is ON at ``DEFAULT_CACHE_DIR``: one fixed path
    inside the checkout. The path is part of the cache key's
    neighbourhood (a directory that moves never hits), so it is never
    built from a temporary name, a pid or the time.

    "compile_cache": {
        "dir": "/some/fixed/path",          // rule (b)
        "min_compile_time_secs": 0.0,       // cache everything (jax
                                            // defaults to 1.0 — small
                                            // executables skipped)
        "min_entry_size_bytes": 0,
        "max_size_bytes": 4294967296        // LRU-evict past 4 GiB
                                            // (jax defaults to
                                            // UNBOUNDED growth)
    }

Counters: a hit/miss listener (observability/telemetry) counts every
cache event process-wide — surfaced per-step in the flight recorder's
``compile_events`` and cumulatively via serve.py ``GET /metrics``
(``benchmarks/run.py`` reads its ``cache_misses`` from jax's own
events). Note jax's ``backend_compile_duration``
monitoring event fires on hits AND misses (it wraps
``compile_or_get_cached``), so the cache events are the only honest
"was that a real compile?" signal.
"""
from __future__ import annotations

import logging
import os
from pathlib import Path
from typing import Optional

logger = logging.getLogger(__name__)


DEFAULT_MAX_SIZE_BYTES = 4 << 30    # 4 GiB LRU bound (jax: unbounded)
# rule (c): <checkout>/.cache/xla (listed in .gitignore)
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".cache" / "xla")


def configure_compile_cache(config=None, cache_dir: Optional[str] = None,
                            min_compile_time_secs: Optional[float] = None,
                            min_entry_size_bytes: Optional[int] = None,
                            max_size_bytes: Optional[int] = None,
                            ) -> Optional[str]:
    """Enable the persistent compilation cache by the module's rule;
    returns the active cache dir (None only when the directory cannot
    be used).

    ``config`` is a ConfigParser or plain dict; its ``compile_cache``
    section is read as documented above. An explicit ``cache_dir``
    wins over the section (the ``--compile-cache-dir`` flag of the
    scripts); the environment variable wins over both.

    Never raises: a bad cache dir degrades to an uncached run with a
    warning — compile caching is an optimization, not a dependency.
    """
    section = {}
    if config is not None:
        try:
            section = dict(config.get("compile_cache", None) or {})
        except Exception:
            section = {}
    if min_compile_time_secs is None:
        min_compile_time_secs = section.get("min_compile_time_secs", 0.0)
    if min_entry_size_bytes is None:
        min_entry_size_bytes = section.get("min_entry_size_bytes", 0)
    if max_size_bytes is None:
        max_size_bytes = section.get("max_size_bytes",
                                     DEFAULT_MAX_SIZE_BYTES)

    # counters must exist however the cache was configured — the
    # listener is idempotent and cheap
    from ..observability.telemetry import _install_compile_listener

    _install_compile_listener()

    import jax

    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or cache_dir or section.get("dir") or DEFAULT_CACHE_DIR)

    try:
        cache_dir = os.path.abspath(os.path.expanduser(str(cache_dir)))
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # jax's 1.0 s default skips exactly the small-but-numerous
        # executables (admit/chunk ladders, transforms) whose aggregate
        # cold cost the cache exists to delete; default to caching all
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_time_secs))
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          int(min_entry_size_bytes))
        # ...and because min_compile_time 0 writes EVERY executable,
        # bound the dir: jax LRU-evicts by atime past this size (its
        # own default is -1 = grow forever)
        jax.config.update("jax_compilation_cache_max_size",
                          int(max_size_bytes))
        # jax memoizes the is-cache-used decision at the FIRST compile
        # of the process; enabling the dir after any compile has
        # happened (tests, notebooks, late config) silently does
        # nothing until that memo is cleared
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
        logger.info("persistent compilation cache: %s", cache_dir)
        return cache_dir
    except Exception as e:  # noqa: BLE001 — never fail an entrypoint
        logger.warning("could not enable compilation cache at %r: %s",
                       cache_dir, e)
        return None

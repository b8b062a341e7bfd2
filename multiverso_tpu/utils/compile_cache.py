"""Where compiled programs are kept between processes.

Entry points (the app ``main()``s, ``chip_smoke.py``, ``benchmark/run.py``) call
:func:`enable` before the first backend use; ``MV_Init`` does not, so a
library user's own JAX configuration is left alone.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside; JAX reads
    it itself and no path is set here. Otherwise the cache lives at
    ``<checkout>/.jax_cache`` — a fixed path, because the path is part of
    how a cached program is found again.

    This is where an entry point first imports jax: its seconds join the
    lazy import's in ``mv.import_s``, and the compile ledger starts
    listening here (``telemetry/startup.py``).
    """
    from multiverso_tpu.telemetry import startup
    with startup.phase("mv.import"):
        import jax
        path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not path:
            path = os.path.join(_CHECKOUT, ".jax_cache")
            jax.config.update("jax_compilation_cache_dir", path)
        # JAX keeps only compiles that took over 1 s; the row-verb programs
        # compile in less and there are dozens of them
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        # a Mosaic kernel's body keeps its debug locations inside the
        # cache's key; with call stacks in them the key follows the line
        # numbers of whoever called the row program
        jax.config.update("jax_include_full_tracebacks_in_locations", False)
        startup.listen()
    return path

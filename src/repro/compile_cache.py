"""Where JAX's persistent compilation cache lives.

Every entry point — ``python -m repro.launch.serve``, ``chip_smoke.py``,
the examples, ``benchmarks/run.py`` and the test suite — calls
:func:`enable` once, before it compiles anything.

* ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and wins:
  nothing is set in code.
* Otherwise the cache is ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``). The path is fixed — never derived from a temp name, a
  pid or the time — so a later process finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The repository checkout this package runs from (``src/repro/`` -> root).
CHECKOUT = Path(__file__).resolve().parents[2]
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)

"""Persistent XLA compilation cache at a fixed place.

A process that compiles a full-width train step spends a minute or more
in the compiler; the next process in the same checkout finds the result
here instead, which it can only do if the place does not change between
runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set  -> that directory, nothing else;
  * unset                              -> ``<checkout>/.jax_cache``
    (listed in ``.gitignore``).

The cache key includes the program's metadata (op names, named scopes,
source locations). Without it, two source trees whose programs differ
only there share an entry, and a process loads an executable whose op
names, the ones a profiler trace is read by, come from the other tree.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory, key
    it on the program's metadata too, and return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path

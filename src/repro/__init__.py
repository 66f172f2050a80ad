"""Trident 4PC reproduction (JAX/Pallas).

Importing the package configures JAX once for the whole process:

* 64-bit types: shares of the ring Z_2^64 are ``uint64``;
* the persistent compilation cache.  The party runtime runs op by op, so
  a cold process compiles hundreds of small programs, and every one of
  them is kept (no minimum compile time).  Where
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX uses that directory.
  Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed path,
  because the path is part of the cache key, and the same one for every
  process a run spawns (party daemons, dealer), which import this package
  too.
"""
import os
import pathlib

import jax

jax.config.update("jax_enable_x64", True)
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

"""compilecache/ — kill cold-start: persistent XLA compilation cache
wiring, ahead-of-time (AOT) precompilation, and compile observability.

Every other subsystem makes the steady state fast; this one makes the
FIRST step fast. Three rails, all composing with the existing stack:

- :mod:`compilecache.cache` — wires JAX's persistent compilation cache
  (``jax_compilation_cache_dir`` + the min-entry-size / min-compile-time
  admission knobs) into the live process, and keeps process-wide
  :class:`CompileStats` fed by ``jax.monitoring`` events, so every XLA
  compile in the process is counted, timed, and attributed as a
  cache HIT (deserialized from the persistent cache) or MISS (a real
  backend compile). Synthetic ``compile.trace`` / ``compile.lower`` /
  ``compile.backend`` spans land in the monitor/ tracer ring.
- :mod:`compilecache.aot` — the AOT dispatch layer:
  ``SameDiff.precompile()`` and ``ParallelInference(warmup_buckets=...)``
  lower-and-compile programs from *abstract shapes* before the first
  batch/request, and :class:`AOTDispatch` routes matching dispatches to
  the prebuilt executables (falling back to lazy ``jax.jit`` for shapes
  nobody predicted).

See docs/cold_start.md for the operational story (what is and is not
cacheable across JAX/libtpu versions, cache invalidation, sizing).
"""
from deeplearning4j_tpu.compilecache.aot import (AOTDispatch, AOTOutput,
                                                 ph_shape_sig)
from deeplearning4j_tpu.compilecache.cache import (COMPILE_STATS,
                                                   CompileStats,
                                                   cache_dir,
                                                   configure_cache,
                                                   install_compile_watcher)

__all__ = ["AOTDispatch", "AOTOutput", "ph_shape_sig", "COMPILE_STATS",
           "CompileStats", "cache_dir", "configure_cache",
           "install_compile_watcher"]

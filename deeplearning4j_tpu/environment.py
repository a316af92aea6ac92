"""Runtime environment: the framework's flag/property catalog.

Reference parity: org.nd4j.common.config.ND4JSystemProperties (the
documented catalog of system properties) and libnd4j
include/system/Environment.h:41 (the runtime toggle singleton —
verbose/debug mode, max memory, workspace behavior, blas threads).

TPU-native redesign: properties map to environment variables read once
at first access and overridable programmatically; device/platform rows
are live queries against JAX (there is no native env struct to mirror —
XLA owns execution), and memory caps surface the XLA client options
instead of workspace byte counts.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, Optional

_TRUE = ("1", "true", "yes", "on")

# Where the persistent compilation cache lives when
# $JAX_COMPILATION_CACHE_DIR does not say: one fixed directory in the
# checkout, next to pyproject.toml — never a temporary name, a pid or a
# time, because a cache that moves between runs never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def _as_bool(v: str) -> bool:
    return str(v).strip().lower() in _TRUE


@dataclasses.dataclass(frozen=True)
class PropertySpec:
    key: str                 # environment variable
    type: Callable
    default: Any
    description: str
    # read once by JAX/XLA at backend initialization; a set() after that
    # point cannot affect the running process
    startup_only: bool = False


# The documented property catalog (reference: ND4JSystemProperties.java —
# every toggle is listed with its doc string so `describe()` can print
# the same kind of reference table).
PROPERTIES: Dict[str, PropertySpec] = {
    "verbose": PropertySpec(
        "DL4J_TPU_VERBOSE", _as_bool, False,
        "Print per-fit compile/dispatch diagnostics (Environment.h "
        "verbose mode)."),
    "debug": PropertySpec(
        "DL4J_TPU_DEBUG", _as_bool, False,
        "Debug execution mode: every fit() checks fetched losses for "
        "NaN/Inf regardless of TrainingConfig.nan_panic, and compile "
        "logging turns on (Environment.h debug mode; per-op localization "
        "stays on sd.exec_debug())."),
    "nan_panic": PropertySpec(
        "DL4J_TPU_NAN_PANIC", _as_bool, False,
        "Default TrainingConfig.nan_panic: raise on non-finite loss "
        "(PerformanceListener/NaN panic rails)."),
    "default_dtype": PropertySpec(
        "DL4J_TPU_DTYPE", str, "float32",
        "Default floating dtype for new networks (ND4JSystemProperties "
        "dtype property)."),
    "log_compiles": PropertySpec(
        "DL4J_TPU_LOG_COMPILES", _as_bool, False,
        "Ask JAX to log every XLA compilation (jax_log_compiles)."),
    "mem_fraction": PropertySpec(
        "XLA_PYTHON_CLIENT_MEM_FRACTION", float, 0.75,
        "Fraction of device HBM the XLA client may preallocate (the "
        "workspace-size analogue; read by JAX at process start).",
        startup_only=True),
    "preallocate": PropertySpec(
        "XLA_PYTHON_CLIENT_PREALLOCATE", _as_bool, True,
        "Whether the XLA client preallocates the memory pool at startup.",
        startup_only=True),
    "compilation_cache_dir": PropertySpec(
        "JAX_COMPILATION_CACHE_DIR", str, DEFAULT_CACHE_DIR,
        "Persistent XLA compilation cache directory (first-compile "
        "latency amortization across process restarts). The environment "
        "variable places it from outside; unset, it is .jax_cache/ in "
        "the checkout. Applied LIVE through jax.config by fit(), "
        "precompile() and serving warmup (docs/cold_start.md)."),
    "compilation_cache_min_entry_size": PropertySpec(
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", int, 0,
        "Smallest executable (bytes) worth persisting to the "
        "compilation cache; -1 caches everything. Applied live."),
    "compilation_cache_min_compile_time": PropertySpec(
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", float, 1.0,
        "Shortest compile (seconds) worth persisting to the "
        "compilation cache; 0 caches everything. Applied live."),
    "host_device_count": PropertySpec(
        "DL4J_TPU_HOST_DEVICES", int, 0,
        "Virtual CPU device count for mesh testing (0 = leave XLA_FLAGS "
        "alone); mirrors --xla_force_host_platform_device_count.",
        startup_only=True),
}


# properties whose set()/reset() must touch live jax.config state
_SIDE_EFFECT_PROPS = ("log_compiles", "compilation_cache_dir",
                      "compilation_cache_min_entry_size",
                      "compilation_cache_min_compile_time")

# cache properties additionally export their env var on set() so child
# processes (restart probes, multihost workers) inherit the cache
_CACHE_PROPS = ("compilation_cache_dir",
                "compilation_cache_min_entry_size",
                "compilation_cache_min_compile_time")


class Environment:
    """Singleton runtime toggles (reference: Environment.getInstance()).

    Values resolve in order: programmatic ``set()`` > environment
    variable > catalog default.
    """

    _instance: Optional["Environment"] = None

    def __init__(self):
        self._overrides: Dict[str, Any] = {}
        # original env-var values before startup_only set()s, so reset()
        # can restore the documented 'set > env > default' resolution
        self._env_saved: Dict[str, Optional[str]] = {}

    @classmethod
    def get_instance(cls) -> "Environment":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    # -- generic access ----------------------------------------------------
    def get(self, name: str):
        spec = PROPERTIES.get(name)
        if spec is None:
            raise KeyError(f"unknown property {name!r}; "
                           f"have {sorted(PROPERTIES)}")
        if name in self._overrides:
            return self._overrides[name]
        raw = os.environ.get(spec.key)
        if raw is None or raw == "":
            return spec.default
        try:
            return spec.type(raw)
        except (TypeError, ValueError):
            return spec.default

    def set(self, name: str, value, for_restart: bool = False
            ) -> "Environment":
        if name not in PROPERTIES:
            raise KeyError(f"unknown property {name!r}")
        spec = PROPERTIES[name]
        coerced = spec.type(value)     # validate before any write
        if spec.startup_only:
            # startup-only properties are read by JAX/XLA at backend
            # init: once the backend is up a set() CANNOT affect the
            # running process, so it raises instead of silently
            # accepting the write. ``for_restart=True`` opts into the
            # write-the-env-var behavior for child processes / the next
            # start.
            from jax._src import xla_bridge
            if xla_bridge._backends and not for_restart:
                raise RuntimeError(
                    f"property {name!r} (${spec.key}) is read once at "
                    f"backend initialization and the backend is already "
                    f"up — setting it now cannot affect this process. "
                    f"Set the env var before importing jax, or pass "
                    f"for_restart=True to write it for child processes "
                    f"/ the next start.")
            if spec.key not in self._env_saved:
                self._env_saved[spec.key] = os.environ.get(spec.key)
            os.environ[spec.key] = str(coerced)
            return self
        self._overrides[name] = coerced
        # the compilation-cache properties also export their env var
        # (original saved for reset()) so child processes inherit the
        # cache — matching what the old startup_only declaration of
        # compilation_cache_dir provided. Ordinary toggles stay
        # process-local: set("debug", True) must not leak into every
        # subprocess spawned afterwards.
        if name in _CACHE_PROPS:
            if spec.key not in self._env_saved:
                self._env_saved[spec.key] = os.environ.get(spec.key)
            os.environ[spec.key] = str(coerced)
        self._apply_side_effects(name)
        return self

    def reset(self, name: Optional[str] = None) -> "Environment":
        def _restore_env(key):
            if key in self._env_saved:
                old = self._env_saved.pop(key)
                if old is None:
                    os.environ.pop(key, None)
                else:
                    os.environ[key] = old

        # only properties that were actually set() have live jax.config
        # side effects to undo — re-applying a never-touched one would
        # clobber state the user configured directly via jax.config
        # (e.g. a cache dir enabled the standard JAX way)
        if name is None:
            touched = [n for n in _SIDE_EFFECT_PROPS
                       if n in self._overrides]
            self._overrides.clear()
            for key in list(self._env_saved):
                _restore_env(key)
            for n in touched:
                self._apply_side_effects(n)
        else:
            was_set = name in self._overrides
            self._overrides.pop(name, None)
            if name in PROPERTIES:
                _restore_env(PROPERTIES[name].key)
            if name in _SIDE_EFFECT_PROPS and was_set:
                # re-apply from the now-resolved env/default value, so a
                # reset() actually undoes the live jax.config change
                self._apply_side_effects(name)
        return self

    def _source(self, name: str) -> str:
        if name in self._overrides:
            return "set"
        return "env" if os.environ.get(PROPERTIES[name].key) else "default"

    def _apply_side_effects(self, name: str) -> None:
        if name == "log_compiles":
            import jax
            jax.config.update("jax_log_compiles", bool(self.get(name)))
        elif name == "compilation_cache_dir":
            from deeplearning4j_tpu.compilecache import configure_cache
            configure_cache(str(self.get(name)) or None)
        elif name == "compilation_cache_min_entry_size":
            import jax
            jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                              int(self.get(name)))
        elif name == "compilation_cache_min_compile_time":
            import jax
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              float(self.get(name)))

    def apply_compilation_cache(self) -> "Environment":
        """Push the resolved compilation-cache properties into the live
        JAX config — the ONE place the cache directory is decided:
        ``$JAX_COMPILATION_CACHE_DIR`` where set, else
        :data:`DEFAULT_CACHE_DIR`. Every path that compiles a model
        program calls this first (``SameDiff.fit()``/``precompile()``,
        serving warmup, ``chip_smoke.py``), so no run is
        without a cache and none sets a directory of its own. The
        admission knobs still at their catalog default are left alone
        (a direct ``jax.config.update`` by the user wins)."""
        self._apply_side_effects("compilation_cache_dir")
        for n in ("compilation_cache_min_entry_size",
                  "compilation_cache_min_compile_time"):
            if self._source(n) != "default":
                self._apply_side_effects(n)
        return self

    def compilation_cache_dir(self) -> str:
        return str(self.get("compilation_cache_dir"))

    # -- named accessors (Environment.h style) -----------------------------
    def is_verbose(self) -> bool:
        return bool(self.get("verbose"))

    def set_verbose(self, v: bool):
        return self.set("verbose", v)

    def is_debug(self) -> bool:
        return bool(self.get("debug"))

    def set_debug(self, v: bool):
        return self.set("debug", v)

    def default_dtype(self) -> str:
        return str(self.get("default_dtype"))

    # -- live platform rows (reference: Environment.h backend queries) -----
    def platform(self) -> str:
        import jax
        try:
            return jax.default_backend()
        except Exception:
            return "uninitialized"

    def device_count(self) -> int:
        import jax
        try:
            return jax.device_count()
        except Exception:
            return 0

    def describe(self) -> str:
        """Render the property catalog with current values (the
        ND4JSystemProperties doc table, live)."""
        lines = [f"deeplearning4j_tpu runtime environment "
                 f"(platform={self.platform()}, "
                 f"devices={self.device_count()})"]
        for name, spec in sorted(PROPERTIES.items()):
            src = ("set" if name in self._overrides else
                   "env" if os.environ.get(spec.key) else "default")
            lines.append(f"  {name} = {self.get(name)!r} [{src}; "
                         f"${spec.key}]")
            lines.append(f"      {spec.description}")
        return "\n".join(lines)

    def to_dict(self) -> Dict[str, Any]:
        return {name: self.get(name) for name in PROPERTIES}


def environment() -> Environment:
    """Module-level accessor (reference: Nd4j.getEnvironment())."""
    return Environment.get_instance()

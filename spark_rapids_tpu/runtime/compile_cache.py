"""Persistent cross-process compilation layer.

The structural jit cache (runtime/jit_cache.py) evaporates with the
process, so a fresh session pays full XLA compilation for every fused
program variant — the round-5 review measured 482 s of cold start
against a 7.7 s CPU cold read, almost all of it compilation of the
multiplied fused-program variants. The reference pays no such tax
(cuDF kernels are precompiled); Theseus (arxiv 2508.05029) and the
Presto-on-GPU work treat time-to-first-query as a first-class engine
metric. This module is the XLA-native answer, and it is ONE cache:

DISK-BACKED PROGRAM CACHE — JAX's persistent compilation cache, so any
process re-tracing a structurally identical program loads the
serialized XLA executable instead of recompiling (tracing is host
seconds; compilation was the minutes). Entry keys are XLA's own (HLO +
compile options + jaxlib build + target device), so cross-version and
cross-backend collisions are impossible by construction, CPU
rehearsals share a directory with chip runs, and a changed lowering is
a different entry whatever the engine's structural key says: nothing
here can serve a stale program. The engine keeps no index, no artifact
and no thread of its own beside it.

WHERE IT LIVES is decided from outside, because the path is part of
what makes a cache hit (`resolve_dirs`):

- `JAX_COMPILATION_CACHE_DIR` set: JAX's cache lives exactly there —
  JAX reads the variable itself and this module sets no other path —
  and the engine's root (`cache_dir`) is its `srtpu/` sub-directory.
- else `spark.rapids.tpu.compileCache.dir`: JAX's cache in `<dir>/xla`.
- else a fixed, git-ignored directory inside the checkout
  (`FIXED_DIR`). Never the system temp directory, a pid or a time: a
  cache that moves never hits.

Observability rides along: a process-wide `CompileStats` ledger
(programs compiled / cache hits / compile seconds / jax's disk hits
and misses) that per-query metrics snapshot (api/dataframe.py,
session.last_execution), so the bench and CI can watch cold start
forever.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, Optional, Tuple


class CompileStats:
    """Process-wide compilation ledger; snapshot deltas become the
    per-query compile metrics."""

    def __init__(self):
        self._lock = threading.Lock()
        self.programs_compiled = 0     # fresh jit builds this process
        self.cache_hits = 0            # in-memory structural reuse
        self.compile_seconds = 0.0     # trace+compile time of builds
        self.xla_cache_hits = 0        # jax's disk cache served the
        #                                executable
        self.xla_cache_misses = 0      # XLA compiled it and wrote it

    @staticmethod
    def _emit(kind: str, **fields) -> None:
        from spark_rapids_tpu.obs import events as obs_events

        obs_events.emit("compile", kind=kind, **fields)

    def on_compile(self, seconds: float) -> None:
        with self._lock:
            self.programs_compiled += 1
            self.compile_seconds += float(seconds)
        self._emit("miss", seconds=round(float(seconds), 4))

    def on_hit(self) -> None:
        with self._lock:
            self.cache_hits += 1
        self._emit("hit")

    def on_jax_event(self, event: str, **_kw) -> None:
        """jax.monitoring listener: jax's cache says for itself whether
        a build in this process was an XLA compile or a disk load — the
        difference between `programsCompiled` and minutes."""
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.xla_cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            with self._lock:
                self.xla_cache_misses += 1

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "programsCompiled": self.programs_compiled,
                "cacheHits": self.cache_hits,
                "compileSeconds": round(self.compile_seconds, 3),
                "xlaCacheHits": self.xla_cache_hits,
                "xlaCacheMisses": self.xla_cache_misses,
            }

    @staticmethod
    def delta(before: Dict[str, Any], after: Dict[str, Any]
              ) -> Dict[str, Any]:
        return {k: (round(after[k] - before[k], 3)
                    if isinstance(after[k], float)
                    else after[k] - before[k])
                for k in after}


stats = CompileStats()

_lock = threading.Lock()
_configured_dir: Optional[str] = None   # None = disabled
_jax_listener_installed = False


#: Where the cache lives when nothing outside says otherwise: inside
#: the checkout (listed in .gitignore), the same path in every run.
FIXED_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".srtpu_compile_cache")

_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"


def resolve_dirs(conf=None) -> Tuple[str, Optional[str]]:
    """-> (the engine's root, directory to point JAX's cache at or
    None when JAX already has it from the environment). Precedence:
    the environment variable, the conf entry, the fixed path."""
    from spark_rapids_tpu.config import rapids_conf as rc

    env = os.environ.get(_ENV_DIR)
    if env:
        return os.path.join(os.path.abspath(env), "srtpu"), None
    root = os.path.abspath(
        (conf.get(rc.COMPILE_CACHE_DIR) if conf is not None else "")
        or FIXED_DIR)
    return root, os.path.join(root, "xla")


def enabled() -> bool:
    return _configured_dir is not None


def cache_dir() -> Optional[str]:
    """The engine's root beside jax's cache (obs/telemetry.py keeps the
    measured link peaks there); None while the cache is disabled."""
    return _configured_dir


def configure(conf=None) -> None:
    """Session-lifecycle hook (plugin.py TpuExecutorPlugin.init): turn
    jax's persistent cache on or off per conf. Idempotent for a
    repeated dir."""
    global _configured_dir
    from spark_rapids_tpu.config import rapids_conf as rc

    if conf is not None and not conf.get(rc.COMPILE_CACHE_ENABLED):
        with _lock:
            if _configured_dir is not None and \
                    not os.environ.get(_ENV_DIR):
                import jax

                jax.config.update("jax_compilation_cache_dir", None)
            _configured_dir = None
        return
    root, xla_dir = resolve_dirs(conf)
    with _lock:
        if _configured_dir != root:
            os.makedirs(root, exist_ok=True)
            _enable_jax_persistent_cache(xla_dir)
            _configured_dir = root


def _enable_jax_persistent_cache(xla_dir: Optional[str]) -> None:
    """Every XLA compile (eager operators included) round-trips through
    jax's disk cache. `xla_dir` None = JAX took its directory from
    JAX_COMPILATION_CACHE_DIR and no other path is set here. min
    thresholds drop to zero — cold start is the SUM of many sub-second
    compiles, so the defaults' 1 s floor would leave most of the tax in
    place."""
    global _jax_listener_installed
    import jax

    if xla_dir is not None:
        os.makedirs(xla_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", xla_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not _jax_listener_installed:  # under _lock (configure)
        jax.monitoring.register_event_listener(stats.on_jax_event)
        _jax_listener_installed = True


# nothing runs in the background any more, so there is nothing to wait
# for: kept only because benchmark/run.py calls it before its window
# and this PR may not edit that file (ROADMAP B10 removes both)
def warmup_join(timeout: Optional[float] = None) -> None:
    return None


# jax writes a cache entry inside the compile that made it, so nothing
# is pending: kept only for benchmark/run.py's call (ROADMAP B10)
def flush(timeout: float = 30.0) -> None:
    return None


def reset_for_tests() -> None:
    """Full deconfigure (tests only): subsequent sessions reconfigure,
    and jax opens its cache again at the directory they name (it reads
    the path once, when it first compiles)."""
    global _configured_dir
    from jax.experimental.compilation_cache import compilation_cache

    with _lock:
        _configured_dir = None
    compilation_cache.reset_cache()

"""Persistent cross-process compilation layer.

The structural jit cache (runtime/jit_cache.py) evaporates with the
process, so a fresh session pays full XLA compilation for every fused
program variant — the round-5 review measured 482 s of cold start
against a 7.7 s CPU cold read, almost all of it compilation of the
multiplied fused-program variants. The reference pays no such tax
(cuDF kernels are precompiled); Theseus (arxiv 2508.05029) and the
Presto-on-GPU work treat time-to-first-query as a first-class engine
metric. This module is the XLA-native answer, three layers deep:

1. DISK-BACKED PROGRAM CACHE — JAX's persistent compilation cache, so
   any process re-tracing a structurally identical program loads the
   serialized XLA executable instead of recompiling (tracing is host
   seconds; compilation was the minutes). Entry keys are XLA's own
   (HLO + compile options + jaxlib build + target device), so
   cross-version and cross-backend collisions are impossible by
   construction and CPU rehearsals share a directory with chip runs.

2. KEY -> ARTIFACT INDEX — our own index over the structural keys
   (Expression.key() trees + schema + _env_token(), which names the
   backend): per-program hit counts, compile seconds, and (for fused
   whole-stage programs) a serialized `jax.export` artifact. The index
   is stamped with the jax/jaxlib/plugin version tuple and WIPED on any
   mismatch (stale-artifact invalidation); every write is
   write-temp-then-rename so concurrent sessions never observe torn
   entries, and artifacts carry the full key repr so a digest
   collision is detected at load instead of serving a wrong program.

3. ASYNC WARMUP — a conf-gated background thread AOT-compiles the
   top-K most-used artifacts THIS backend recorded in prior runs while
   the first scan's decode/upload I/O is in flight; `cached_jit` then
   serves the ready executable, skipping even re-tracing for the hot
   programs.

WHERE IT LIVES is decided from outside, because the path is part of
what makes a cache hit (`resolve_dirs`):

- `JAX_COMPILATION_CACHE_DIR` set: JAX's cache lives exactly there —
  JAX reads the variable itself and this module sets no other path —
  and the index + artifacts go in its `srtpu/` sub-directory.
- else `spark.rapids.tpu.compileCache.dir`: JAX's cache in `<dir>/xla`.
- else a fixed, git-ignored directory inside the checkout
  (`FIXED_DIR`). Never the system temp directory, a pid or a time: a
  cache that moves never hits.

Observability rides along: a process-wide `CompileStats` ledger
(programs compiled / cache hits / warm hits / compile seconds) that
per-query metrics snapshot (api/dataframe.py, session.last_execution),
so the bench and CI can watch cold start forever.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import queue
import shutil
import tempfile
import threading
from typing import Any, Callable, Dict, Optional, Tuple

# tags of cached_jit keys whose programs are worth exporting to disk
# artifacts for cross-process warmup: the fused whole-stage programs
# (the cold-start dominators). Eager per-operator programs recompile in
# milliseconds-to-seconds via layer 1 and are not worth the artifact.
_ARTIFACT_TAGS = ("fused",)


class CompileStats:
    """Process-wide compilation ledger; snapshot deltas become the
    per-query compile metrics."""

    def __init__(self):
        self._lock = threading.Lock()
        self.programs_compiled = 0     # fresh jit builds this process
        self.cache_hits = 0            # in-memory structural reuse
        self.warm_hits = 0             # artifact-served programs
        self.compile_seconds = 0.0     # trace+compile time of builds
        self.artifacts_quarantined = 0  # corrupt entries set aside
        self.warm_rebuilds = 0         # warm executables that failed
        #                                to run and were rebuilt live
        self.export_failures = 0       # fused programs jax.export
        #                                could not turn into artifacts
        self.xla_cache_hits = 0        # jax's disk cache served the
        #                                executable (layer 1)
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

    def on_warm_hit(self) -> None:
        with self._lock:
            self.warm_hits += 1
        self._emit("warm")

    def on_quarantine(self) -> None:
        with self._lock:
            self.artifacts_quarantined += 1
        self._emit("quarantine")

    def on_warm_rebuild(self, error: str) -> None:
        with self._lock:
            self.warm_rebuilds += 1
        self._emit("warmRebuild", error=error)

    def on_export_failure(self, error: str) -> None:
        with self._lock:
            self.export_failures += 1
        self._emit("exportFailed", error=error)

    def on_jax_event(self, event: str, **_kw) -> None:
        """jax.monitoring listener: layer 1 says for itself whether a
        build in this process was an XLA compile or a disk load — the
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
                "warmHits": self.warm_hits,
                "compileSeconds": round(self.compile_seconds, 3),
                "artifactsQuarantined": self.artifacts_quarantined,
                "warmRebuilds": self.warm_rebuilds,
                "artifactExportFailures": self.export_failures,
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
_artifact_min_s = 0.5   # export threshold; set from conf at configure
_saver: Optional["_AsyncSaver"] = None
_warm: Dict[str, Callable] = {}         # key repr -> ready executable
_warm_lock = threading.Lock()
_warmup_thread: Optional[threading.Thread] = None
_warmed_dir: Optional[str] = None   # warmup ran for this dir already
_export_serialization_ready = False
_jax_listener_installed = False


def version_token() -> Dict[str, str]:
    """Everything that invalidates serialized artifacts: jax traces
    differently across versions, jaxlib executables are ABI-bound, and
    the engine's lowerings change per release. The backend is NOT here:
    every index key and XLA cache key already names it, so a CPU
    rehearsal and a chip run keep their entries side by side."""
    import jax
    import jaxlib

    import spark_rapids_tpu

    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "plugin": getattr(spark_rapids_tpu, "__version__", "0"),
    }


def key_digest(full_key: Tuple) -> str:
    """Stable cross-process digest of a structural key. Structural keys
    are built from strs/ints/bools/bytes and dtype reprs (the
    Expression.key() audit), so repr() is process-stable."""
    return hashlib.sha256(repr(full_key).encode()).hexdigest()[:32]


#: Where the cache lives when nothing outside says otherwise: inside
#: the checkout (listed in .gitignore), the same path in every run.
FIXED_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".srtpu_compile_cache")

_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"


def resolve_dirs(conf=None) -> Tuple[str, Optional[str]]:
    """-> (root of index + artifacts, directory to point JAX's cache at
    or None when JAX already has it from the environment). Precedence:
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
    return _configured_dir


def _index_dir() -> str:
    return os.path.join(_configured_dir, "index")


def _artifact_dir() -> str:
    return os.path.join(_configured_dir, "artifacts")


def _atomic_write(path: str, data: bytes) -> None:
    """Concurrent-writer discipline: temp file in the same directory +
    rename, so readers never see a torn entry and the last writer
    wins."""
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                               prefix=".tmp-")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def _check_version_stamp(root: str) -> None:
    """Wipe index + artifacts on any version-tuple mismatch; stamp the
    current tuple. JAX's own entries stay: their keys carry the jaxlib
    build, and the directory may be the user's. A second process
    racing the wipe at worst re-wipes — entries are re-creatable by
    definition."""
    stamp = os.path.join(root, "VERSION.json")
    tok = version_token()
    try:
        with open(stamp) as f:
            if json.load(f) == tok:
                return
    except (OSError, ValueError):
        pass
    for sub in ("index", "artifacts"):
        shutil.rmtree(os.path.join(root, sub), ignore_errors=True)
    _atomic_write(stamp, json.dumps(tok).encode())


def configure(conf=None) -> None:
    """Session-lifecycle hook (plugin.py TpuExecutorPlugin.init): enable
    the persistent layers per conf. Idempotent for a repeated dir."""
    global _configured_dir, _saver, _artifact_min_s
    from spark_rapids_tpu.config import rapids_conf as rc

    if conf is not None:
        _artifact_min_s = conf.get(rc.COMPILE_CACHE_ARTIFACT_MIN_S)
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
        already = _configured_dir == root
        if not already:
            os.makedirs(root, exist_ok=True)
            _check_version_stamp(root)
            for sub in ("index", "artifacts"):
                os.makedirs(os.path.join(root, sub), exist_ok=True)
            _enable_jax_persistent_cache(xla_dir)
            _configured_dir = root
        if _saver is None:
            _saver = _AsyncSaver()
    if conf is not None and conf.get(rc.COMPILE_CACHE_WARMUP):
        start_warmup(conf.get(rc.COMPILE_CACHE_WARMUP_TOP_K))


def _enable_jax_persistent_cache(xla_dir: Optional[str]) -> None:
    """Layer 1: every XLA compile (eager operators included) round-trips
    through jax's disk cache. `xla_dir` None = JAX took its directory
    from JAX_COMPILATION_CACHE_DIR and no other path is set here. min
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


# ------------------------------------------------------------- index

def _index_path(digest: str) -> str:
    return os.path.join(_index_dir(), digest + ".json")


def read_index() -> Dict[str, Dict[str, Any]]:
    """digest -> entry; skips torn/foreign files defensively."""
    out: Dict[str, Dict[str, Any]] = {}
    if not enabled():
        return out
    try:
        names = os.listdir(_index_dir())
    except OSError:
        return out
    for name in names:
        if not name.endswith(".json"):
            continue
        try:
            with open(os.path.join(_index_dir(), name)) as f:
                out[name[:-5]] = json.load(f)
        except (OSError, ValueError):
            continue
    return out


def _record_index(digest: str, key_repr: str, tag: str,
                  seconds: float, has_artifact: bool,
                  name: Optional[str] = None) -> None:
    """`name`: the traced function's name, which the XLA module
    carries (`jit_<name>`); warm-up gives a loaded artifact the same
    one, so a program is called the same in the device trace whoever
    built it."""
    import jax

    path = _index_path(digest)
    # the backend the program was traced for: warmup must not feed one
    # backend's exported artifact to another (it would fail to compile
    # there and be quarantined, in silence, for both)
    entry = {"key": key_repr, "tag": tag, "count": 0,
             "compile_s": 0.0, "artifact": has_artifact,
             "backend": jax.default_backend()}
    try:
        with open(path) as f:
            prev = json.load(f)
        if prev.get("key") == key_repr:
            entry = prev
            entry["artifact"] = entry.get("artifact", False) or \
                has_artifact
    except (OSError, ValueError):
        pass
    if name:
        entry["name"] = name
    entry["count"] = int(entry.get("count", 0)) + 1
    entry["compile_s"] = round(
        float(entry.get("compile_s", 0.0)) + seconds, 4)
    _atomic_write(path, json.dumps(entry).encode())


# --------------------------------------------------------- artifacts

def _register_export_serialization() -> None:
    """jax.export must be taught the engine's pytree containers once per
    process; aux data (schemas, dtypes, vranges) pickles."""
    global _export_serialization_ready
    if _export_serialization_ready:
        return
    import jax.export as jex

    from spark_rapids_tpu.columnar.batch import ColumnBatch, DeviceColumn
    from spark_rapids_tpu.ops.joinops import BuildIndex, BuildTable

    for node in (DeviceColumn, ColumnBatch):
        try:
            jex.register_pytree_node_serialization(
                node,
                serialized_name=f"srtpu.{node.__name__}",
                serialize_auxdata=pickle.dumps,
                deserialize_auxdata=pickle.loads)
        except ValueError:
            pass  # already registered (session re-init)
    for node in (BuildTable, BuildIndex):
        try:
            jex.register_namedtuple_serialization(
                node, serialized_name=f"srtpu.{node.__name__}")
        except ValueError:
            pass
    _export_serialization_ready = True


class _AsyncSaver(threading.Thread):
    """Write-behind index/artifact persistence: exporting a fused
    program re-traces it (host seconds), which must not sit on the
    query's critical path. Bounded queue; overflow drops the artifact,
    never blocks the query."""

    def __init__(self):
        super().__init__(name="srtpu-compile-cache-saver", daemon=True)
        self.q: "queue.Queue" = queue.Queue(maxsize=256)
        self.start()

    def run(self):
        while True:
            item = self.q.get()
            if item is None:
                self.q.task_done()
                return
            try:
                self._save(*item)
            except Exception:
                pass  # artifacts are best-effort by contract
            finally:
                self.q.task_done()

    def _save(self, full_key, tag, seconds, jitted, avals):
        digest = key_digest(full_key)
        key_repr = repr(full_key)
        has_artifact = False
        if (jitted is not None and avals is not None
                and tag in _ARTIFACT_TAGS):
            has_artifact = self._export(digest, key_repr, jitted, avals)
        _record_index(digest, key_repr, tag, seconds, has_artifact,
                      getattr(jitted, "__name__", None))

    def _export(self, digest, key_repr, jitted, avals) -> bool:
        try:
            import jax.export as jex

            _register_export_serialization()
            exp = jex.export(jitted)(*avals)
            blob = exp.serialize()
        except Exception as e:
            # program outside export's subset: index-only — counted,
            # because a layer that cannot record cannot warm either
            stats.on_export_failure(f"{type(e).__name__}: {e}"[:200])
            return False
        _atomic_write(os.path.join(_artifact_dir(), digest + ".key"),
                      key_repr.encode())
        _atomic_write(os.path.join(_artifact_dir(), digest + ".bin"),
                      blob)
        return True


def record_use(full_key: Tuple, tag: str) -> None:
    """Bump a program's index count WITHOUT a compile (warm-served or
    cross-query reuse): top-K warmup ranks by count, so programs every
    process touches must outrank one-off entries from past runs."""
    if not enabled() or _saver is None:
        return
    try:
        _saver.q.put_nowait((full_key, tag, 0.0, None, None))
    except queue.Full:
        pass


def record_build(full_key: Tuple, tag: str, seconds: float,
                 jitted=None, args: Optional[tuple] = None) -> None:
    """Called by cached_jit after a fresh build's first dispatch:
    account the compile and enqueue persistence. Input AVALS are
    captured here (cheap, host-side) instead of the arrays — holding
    example batches until the saver runs would pin gigabytes of HBM."""
    stats.on_compile(seconds)
    if not enabled() or _saver is None:
        return
    avals = None
    if (args is not None and tag in _ARTIFACT_TAGS
            and seconds >= _artifact_min_s):
        try:
            import jax

            avals = jax.tree_util.tree_map(
                lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
                args)
        except Exception:
            avals = None
    try:
        _saver.q.put_nowait((full_key, tag, seconds, jitted, avals))
    except queue.Full:
        pass


def flush(timeout: float = 30.0) -> None:
    """Drain pending index/artifact writes (tests, session stop)."""
    if _saver is not None:
        try:
            _saver.q.join()
        except Exception:
            pass


# ------------------------------------------------------------ warmup

def take_warm(full_key: Tuple) -> Optional[Callable]:
    """Ready executable for a structural key, if warmup loaded one.
    Matched on the FULL key repr (not the digest), so a digest
    collision can never serve the wrong program."""
    if not _warm:
        return None
    with _warm_lock:
        return _warm.pop(repr(full_key), None)


def warm_count() -> int:
    with _warm_lock:
        return len(_warm)


def invalidate_warm() -> int:
    """Device-loss recovery hook (runtime/device_monitor.py): warm AOT
    executables were loaded against the PJRT client the recovery just
    tore down — drop them all. The disk artifacts they came from stay
    valid (serialized HLO, epoch-free keys) and re-serve lazily: a
    later session init re-runs warmup against the fresh backend, and a
    cache miss simply recompiles. Returns how many were dropped."""
    global _warmed_dir
    with _warm_lock:
        n = len(_warm)
        _warm.clear()
    with _lock:
        # let the next configure() warm up again for the same dir
        _warmed_dir = None
    return n


def start_warmup(top_k: int = 32) -> None:
    """Layer 3: AOT-compile the top-K most-used prior-run artifacts in
    the background (overlapping the first scan's decode/upload I/O).
    Each compile also primes jax's persistent-cache memory layer, so
    even a program the warm table misses gets its disk entry hot."""
    global _warmup_thread, _warmed_dir
    if not enabled():
        return
    with _lock:
        # once per process per cache dir: session churn (tests, REPL
        # re-creation) must not re-scan the index every init
        if _warmed_dir == _configured_dir:
            return
        if _warmup_thread is not None and _warmup_thread.is_alive():
            return
        _warmed_dir = _configured_dir
        _warmup_thread = threading.Thread(
            target=_warmup_run, args=(int(top_k),),
            name="srtpu-compile-cache-warmup", daemon=True)
        _warmup_thread.start()


def warmup_join(timeout: Optional[float] = None) -> None:
    t = _warmup_thread
    if t is not None:
        t.join(timeout)


def _warmup_run(top_k: int) -> None:
    import jax

    backend = jax.default_backend()
    # an entry without a name predates stable program names: it is
    # built live once more and recorded with one
    entries = [(d, e) for d, e in read_index().items()
               if e.get("artifact") and e.get("backend") == backend
               and e.get("name")]
    entries.sort(key=lambda de: (-int(de[1].get("count", 0)), de[0]))
    for digest, entry in entries[:top_k]:
        try:
            fn = _load_artifact(digest, entry["key"], entry["name"])
        except Exception:
            fn = None
        if fn is not None:
            with _warm_lock:
                _warm[entry["key"]] = fn


def quarantine_artifact(digest: str) -> None:
    """Set a corrupt artifact's files aside (rename to .quarantine) so
    the next run neither re-reads the poison nor loses the evidence;
    count it so metrics surface decay of the cache medium."""
    adir = _artifact_dir()
    for ext in (".bin", ".key"):
        src = os.path.join(adir, digest + ext)
        try:
            os.replace(src, src + ".quarantine")
        except OSError:
            pass
    stats.on_quarantine()


def _load_artifact(digest: str, key_repr: str,
                   name: str = "call") -> Optional[Callable]:
    """Deserialize + AOT-compile one artifact as XLA module
    `jit_<name>`. The .key sidecar must equal the index's key repr — a
    mismatch means a digest collision or a torn write, and the
    artifact is ignored.

    Failure contract (PR 2): a corrupt/truncated artifact — or an
    injected compile.cache_load fault — is a CACHE MISS, never a query
    failure: the file is quarantined, a metric counts it, and the
    program recompiles from source as if the entry never existed."""
    import jax

    from spark_rapids_tpu.runtime import faults

    adir = _artifact_dir()
    try:
        faults.maybe_inject("compile.cache_load", detail=digest)
        with open(os.path.join(adir, digest + ".key"), "rb") as f:
            if f.read().decode() != key_repr:
                return None
        with open(os.path.join(adir, digest + ".bin"), "rb") as f:
            blob = f.read()
        import jax.export as jex

        _register_export_serialization()
        exp = jex.deserialize(blob)
        args, kwargs = jax.tree_util.tree_unflatten(
            exp.in_tree, exp.in_avals)

        def call(*a, **k):
            return exp.call(*a, **k)

        call.__name__ = call.__qualname__ = name
        return jax.jit(call).lower(*args, **kwargs).compile()
    except FileNotFoundError:
        return None  # plain miss: nothing to quarantine
    except Exception:
        quarantine_artifact(digest)
        return None


# ------------------------------------------------------------- admin

def clear(remove_files: bool = False) -> None:
    """Test hook: drop warm table (+ optionally the on-disk entries)."""
    global _warmup_thread, _warmed_dir
    with _warm_lock:
        _warm.clear()
    _warmup_thread = None
    _warmed_dir = None
    if remove_files and enabled():
        for sub in ("index", "artifacts"):
            d = os.path.join(_configured_dir, sub)
            shutil.rmtree(d, ignore_errors=True)
            os.makedirs(d, exist_ok=True)


def reset_for_tests() -> None:
    """Full deconfigure (tests only): subsequent sessions reconfigure."""
    global _configured_dir, _saver, _warmup_thread, _warmed_dir
    flush()
    with _lock:
        _configured_dir = None
        _saver = None
    with _warm_lock:
        _warm.clear()
    _warmup_thread = None
    _warmed_dir = None

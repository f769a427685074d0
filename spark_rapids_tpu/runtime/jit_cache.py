"""Global compiled-program cache keyed on program STRUCTURE.

`jax.jit` caches compiled executables per function *object*. Operators
used to call `jax.jit(self._run)` in __init__, so every new query plan
(fresh operator instances) recompiled structurally identical programs —
tens of seconds per query on TPU. The reference has no analog problem
(cuDF kernels are precompiled); the XLA-native answer is to key the
jitted callable on the structural description of the program
(Expression.key() trees + output schema) so any query with the same
shape of work reuses the compiled artifact, exactly like a second batch
through the same operator does.

Entries hold the first instance's bound method; behavior must be fully
determined by the key (expression keys include dtypes/ordinals/params,
schema keys include names) — the audit lives in the expr key() overrides.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Tuple

import jax

_cache: Dict[Tuple, Callable] = {}
_lock = threading.Lock()


_segmented_mod = None


def _env_token() -> Tuple:
    """Trace-environment facts that change what a structurally identical
    program computes: the backend (kernels branch on it, e.g. the MXU
    segmented reductions) and the test-only forced-matmul flag.
    Deliberately EPOCH-FREE: this token rides into the persistent
    compile-cache keys, and disk artifacts survive a device-loss
    recovery (they reload into the rebuilt client) as well as process
    restarts that reset the epoch to 1."""
    global _segmented_mod
    if _segmented_mod is None:  # lazy: segmented imports columnar.batch
        from spark_rapids_tpu.ops import segmented

        _segmented_mod = segmented
    return (jax.default_backend(), _segmented_mod._MM_FORCE.get())


_device_monitor_mod = None


def _mem_key(full: Tuple) -> Tuple:
    """In-memory cache key: the persistent key PLUS the device epoch
    (runtime/device_monitor.py). Executables jitted against a backend
    that device-loss recovery tore down must never be re-dispatched —
    the epoch bump makes every pre-recovery entry a miss, and programs
    re-intern lazily against the fresh client (via the epoch-free disk
    artifacts when one exists)."""
    global _device_monitor_mod
    if _device_monitor_mod is None:  # lazy: avoids an import cycle
        from spark_rapids_tpu.runtime import device_monitor

        _device_monitor_mod = device_monitor
    return full + (("deviceEpoch", _device_monitor_mod._EPOCH),)


def cached_jit(key: Tuple, build: Callable[[], Callable],
               **jit_kwargs) -> Callable:
    """Return a callable dispatching to the jitted program for `key`,
    building it on first use. The trace-environment part of the key is
    resolved at CALL time, not construction time — jax.jit traces
    lazily on first call, so a construction-time snapshot could label a
    trace with an environment it was not traced under.

    Entries route through the persistent compilation layer
    (runtime/compile_cache.py): a fresh build's first dispatch is timed
    and recorded (and, for fused whole-stage programs, exported to a
    disk artifact), and a key the background warmup already AOT-compiled
    is served without building — the cross-process analog of this
    module's in-process structural reuse."""

    def dispatch(*args, **kwargs):
        mem = _mem_key(key + _env_token())
        # lock-free fast path: CPython dict reads are atomic, and every
        # per-batch dispatch engine-wide funnels through here
        fn = _cache.get(mem)
        if fn is None:
            with _lock:
                fn = _cache.get(mem)
                if fn is None:
                    fn = _make_entry(mem[:-1], key, build, jit_kwargs)
                    _cache[mem] = fn
        return fn(*args, **kwargs)

    return dispatch


def _make_entry(full: Tuple, key: Tuple, build: Callable[[], Callable],
                jit_kwargs) -> Callable:
    """One cache entry: either a warmup-served AOT executable (with a
    build-on-mismatch fallback) or a jax.jit whose first dispatch is
    timed for the compile ledger. Must be called under _lock."""
    from spark_rapids_tpu.runtime import compile_cache as cc

    tag = key[0] if key and isinstance(key[0], str) else "?"
    warm = cc.take_warm(full) if not jit_kwargs else None
    state = {"jitted": None, "timed": warm is not None}
    entry_lock = threading.Lock()

    def entry(*args, **kwargs):
        if warm is not None and state["jitted"] is None:
            try:
                return warm(*args, **kwargs)
            except Exception as e:
                # aval/env drift between the recording and this
                # process: rebuild live, never fail the query — but
                # count it (and time the rebuild like any compile), so
                # a warm layer that never serves shows
                cc.stats.on_warm_rebuild(f"{type(e).__name__}: {e}"[:200])
                state["timed"] = False
        fn = state["jitted"]
        if fn is not None and state["timed"]:
            return fn(*args, **kwargs)
        with entry_lock:
            if state["jitted"] is None:
                state["jitted"] = jax.jit(build(), **jit_kwargs)
            if not state["timed"]:
                state["timed"] = True
                from spark_rapids_tpu.obs import events as obs_events

                t0 = time.perf_counter()
                with obs_events.span("compile", kind=tag) as sp:
                    out = state["jitted"](*args, **kwargs)
                    # async dispatch returns once tracing+compilation
                    # are done (execution overlaps) — the cold-start
                    # quantity
                    seconds = time.perf_counter() - t0
                    sp.set(seconds=round(seconds, 6))
                cc.record_build(
                    full, tag, seconds, state["jitted"],
                    args if not (kwargs or jit_kwargs) else None)
                return out
        return state["jitted"](*args, **kwargs)

    if warm is not None:
        cc.stats.on_warm_hit()
        cc.record_use(full, tag)
    return entry


def detached(op):
    """Shallow copy of an operator with children (and conf) stripped, so
    a cached bound method does not pin the whole physical plan — and
    through it source tables — for the process lifetime. Phase functions
    (_run/_partial/...) only read the operator's own expression fields."""
    import copy

    c = copy.copy(op)
    c.children = []
    c.conf = None
    return c


def probe(key: Tuple) -> bool:
    """Whether a program for `key` (under the CURRENT trace
    environment and device epoch) is already resident — per-query
    compiled-vs-hit accounting without forcing a build."""
    return _mem_key(key + _env_token()) in _cache


def cache_size() -> int:
    with _lock:
        return len(_cache)


def clear():
    with _lock:
        _cache.clear()


def schema_key(schema) -> Tuple:
    return tuple((f.name, repr(f.dataType), f.nullable)
                 for f in schema.fields)


def aliases_key(aliases) -> Tuple:
    return tuple((a.name, a.key()) for a in aliases)


def orders_key(orders) -> Tuple:
    return tuple((o.expr.key(), o.ascending, o.nulls_first)
                 for o in orders)

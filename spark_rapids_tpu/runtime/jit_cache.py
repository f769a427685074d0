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


def _segmented():
    global _segmented_mod
    if _segmented_mod is None:  # lazy: segmented imports columnar.batch
        from spark_rapids_tpu.ops import segmented

        _segmented_mod = segmented
    return _segmented_mod


def _env_token() -> Tuple:
    """Trace-environment facts that change what a structurally identical
    program computes: the backend (kernels branch on it, e.g. the MXU
    segmented reductions) and the test-only forced-matmul flag. Part of
    every in-process key; the persistent cache (jax's) is keyed on the
    traced HLO and needs no token of ours."""
    return (jax.default_backend(), _segmented()._MM_FORCE.get())


_device_monitor_mod = None


def _mem_key(full: Tuple) -> Tuple:
    """In-memory cache key: structure + trace environment PLUS the
    device epoch (runtime/device_monitor.py). Executables jitted against
    a backend that device-loss recovery tore down must never be
    re-dispatched — the epoch bump makes every pre-recovery entry a
    miss, and programs re-intern lazily against the fresh client (their
    rebuild loads from jax's disk cache, whose keys know no epoch)."""
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

    This is the one in-process program cache; across processes a
    fresh build's compile is served by jax's persistent cache
    (runtime/compile_cache.py). A build's first dispatch is timed under
    the `compile` span and counted in the compile ledger."""

    def dispatch(*args, **kwargs):
        mem = _mem_key(key + _env_token())
        # lock-free fast path: CPython dict reads are atomic, and every
        # per-batch dispatch engine-wide funnels through here
        fn = _cache.get(mem)
        if fn is None:
            with _lock:
                fn = _cache.get(mem)
                if fn is None:
                    fn = _make_entry(key, build, jit_kwargs)
                    _cache[mem] = fn
        return fn(*args, **kwargs)

    return dispatch


def _make_entry(key: Tuple, build: Callable[[], Callable],
                jit_kwargs) -> Callable:
    """One cache entry: a jax.jit whose first dispatch is timed for the
    compile ledger. Must be called under _lock."""
    from spark_rapids_tpu.runtime import compile_cache as cc

    tag = key[0] if key and isinstance(key[0], str) else "?"
    jitted = None
    entry_lock = threading.Lock()

    def entry(*args, **kwargs):
        nonlocal jitted
        if jitted is not None:
            return jitted(*args, **kwargs)
        with entry_lock:
            if jitted is None:
                jitted = jax.jit(build(), **jit_kwargs)
                from spark_rapids_tpu.obs import events as obs_events
                from spark_rapids_tpu.ops import common

                t0 = time.perf_counter()
                # what is decided while the program is traced and
                # reported with every later dispatch stays with it
                with obs_events.span("compile", kind=tag) as sp, \
                        _segmented().noting_sum_lowerings() as noted, \
                        common.noting_sorts() as sorts, \
                        common.noting_expands() as expands:
                    out = jitted(*args, **kwargs)
                    entry.sum_lowerings = noted
                    entry.sort_lowerings = sorts
                    entry.expand_lowerings = expands
                    # async dispatch returns once tracing+compilation
                    # are done (execution overlaps) — the cold-start
                    # quantity
                    seconds = time.perf_counter() - t0
                    sp.set(seconds=round(seconds, 6))
                cc.stats.on_compile(seconds)
                return out
        return jitted(*args, **kwargs)

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


def sum_lowerings(key: Tuple) -> dict:
    """How the resident program for `key` lowered its partial
    aggregate's sums (ops/segmented.py `noting_sum_lowerings`): taken
    when it was traced, read by every dispatch that hits the cache."""
    fn = _cache.get(_mem_key(key + _env_token()))
    return getattr(fn, "sum_lowerings", {})


def sort_lowerings(key: Tuple) -> list:
    """How the resident program for `key` lowered its sorts and
    group-bys (ops/common.py `noting_sorts`), as `sum_lowerings`."""
    fn = _cache.get(_mem_key(key + _env_token()))
    return getattr(fn, "sort_lowerings", [])


def expand_lowerings(key: Tuple) -> list:
    """The Expands the resident program for `key` traced, with their
    grouping sets and slots (ops/common.py `noting_expands`), as
    `sum_lowerings`."""
    fn = _cache.get(_mem_key(key + _env_token()))
    return getattr(fn, "expand_lowerings", [])


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

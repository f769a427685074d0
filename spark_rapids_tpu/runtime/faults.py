"""Deterministic fault-injection registry — the chaos harness.

The reference plugin proves its recoverable-failure machinery with
forced-fault tests (the `*RetrySuite` strategy, SURVEY.md section 4
tier 2: RmmSpark injects OOMs at allocation points). This module
generalizes that discipline to EVERY failure domain of the engine:
injection SITES are declared as dotted names at the exact code
locations where the real world fails —

    io.read             file open/read in io/readers.py + io/avro.py
    shuffle.fetch       shuffle block file reads (shuffle/manager.py)
    shuffle.deserialize wire-format decode (shuffle/serde.py)
    spill.disk          disk-tier spill writes/reads (runtime/memory.py)
    device.dispatch     fused/eager program dispatch (exec/fused.py,
                        api/dataframe.py) — the site that exercises the
                        degradation ladder end to end
    worker.crash        task-attempt launch in the stage scheduler
                        (runtime/scheduler.py) — the attempt dies as if
                        its worker was kill -9'd; the scheduler evicts
                        the worker and re-runs the partition
    task.straggler      task-attempt launch in the stage scheduler —
                        the attempt stalls instead of dying, exercising
                        speculative execution's duplicate-attempt +
                        commit-once path
    shuffle.lost_output shuffle block reads of attempt-tagged map
                        output (shuffle/manager.py) — the block is gone
                        AFTER the block-level retry budget, exercising
                        lineage recomputation of the owning map task
    query.cancel_race   query completion in the admission controller
                        (runtime/admission.py) — a cancel lands exactly
                        as the query finishes; the result must still
                        return, permits/slots release exactly once, and
                        the late cancel must not bleed into the next
                        query
    admission.slow_drain admission slot release — the handoff to the
                        next queued query is delayed, exercising
                        queue-wait accounting and queue-timeout margins
    semaphore.partial_hold
                        device-permit grant (runtime/semaphore.py) —
                        the granted task keeps holding while stalled
                        (interruptibly) for a beat, deterministically
                        widening the hold-and-wait window so the
                        legacy-acquisition deadlock gates form their
                        cycle on every run instead of relying on
                        scheduler timing
    device.fatal        fused/eager program dispatch and unspill H2D
                        (runtime/device_monitor.py guard sites) — a
                        FATAL runtime error, as if the PJRT client
                        died: the engine fences, cancels in-flight
                        queries with retryable DeviceLostError, warm-
                        recovers (epoch bump + backend rebuild + tier
                        restore) and resubmits once through admission
    device.lost_buffer  spill-catalog batch registration
                        (runtime/memory.py add_batch) — poisons ONE
                        device buffer's epoch so its next use hits the
                        stale-handle gate: the deterministic proof
                        that pre-epoch handles raise instead of
                        reading recycled device memory
    dcn.collective      multi-host SPMD dispatch
                        (parallel/plan_compiler.py) — a transient
                        cross-host (DCN) collective failure; bounded
                        retries per spark.rapids.tpu.multihost.
                        collectiveRetries before escalating to
                        host-loss handling
    host.fatal          multi-host SPMD dispatch — an entire HOST
                        (one process's worth of chips) dies
                        mid-collective: the mesh engine fences every
                        chip of that host in one step (fence_host),
                        rebuilds the mesh over the surviving hosts,
                        and recovers the lost shards from lineage
    stream.prefetch     staging-queue read in the streaming executor
                        (stream/executor.py) — a prefetched unit is
                        lost between decode and upload; the executor
                        re-enqueues that ScanUnit (bounded retries)
                        and the stream continues, proving partition-
                        granular retry without restarting the query
    stream.window_evict window-slot consume in the streaming executor
                        — the slot is forcibly spilled to host before
                        compute touches it, exercising the SpillCatalog
                        round trip (unspill-on-use) under window
                        pressure
    io.write            staged file write in the commit protocol
                        (io/commit.py stage_file) — the physical write
                        into a task attempt's staging dir fails; the
                        backoff loop re-writes the tmp file and the
                        atomic rename only ever publishes a complete
                        file into staging
    commit.task         task-commit promotion (io/commit.py) — the
                        rename of an attempt dir to its committed name
                        fails transiently; retried under backoff, and
                        first-commit-wins means a racing speculative
                        attempt can never double-publish
    commit.job          job-commit publish (io/commit.py commit_job) —
                        injected BEFORE any file becomes reader-visible;
                        an exhausted retry budget aborts the job with
                        staging unwound and pre-existing output (the
                        deferred overwrite swap) byte-identical
    commit.conflict     lakehouse version-file claim (lakehouse/delta.py
                        _commit, lakehouse/iceberg.py commit_metadata) —
                        a synthetic concurrent-commit conflict; the
                        optimistic-transaction loser re-reads the
                        snapshot, re-runs conflict semantics and retries
                        under backoff, billed to the query retry budget

and every site's CONSUMER survives the injected fault: backoff retries
(runtime/backoff.py), lineage recomputation, or engine demotion.
CI re-runs a query subset with seeded injection at each site and
asserts results are identical to the clean run (ci/chaos_check.sh).

Determinism: each site owns its own `random.Random` stream seeded from
(chaos.seed, site name), so the injection sequence at one site never
depends on how calls interleave across sites — the same seed replays
the same faults for a fixed per-site call sequence.

Per-site policy grammar (conf `spark.rapids.tpu.chaos.sites`):

    site:p=0.05     inject each call with probability 0.05
    site:every=7    inject every 7th call (deterministic, no RNG)
    site:once       inject exactly the first call
    site            inject at chaos.defaultProbability

Multiple sites join with ';'. An empty spec with chaos.enabled=true
arms every KNOWN site at the default probability.
"""

from __future__ import annotations

import random
import threading
from typing import Dict, Optional, Tuple

KNOWN_SITES = (
    "io.read",
    "shuffle.fetch",
    "shuffle.deserialize",
    "spill.disk",
    "device.dispatch",
    "worker.crash",
    "task.straggler",
    "shuffle.lost_output",
    "query.cancel_race",
    "admission.slow_drain",
    "semaphore.partial_hold",
    "device.fatal",
    "device.lost_buffer",
    "ici.collective",
    "chip.fatal",
    "dcn.collective",
    "host.fatal",
    "stream.prefetch",
    "stream.window_evict",
    "io.write",
    "commit.task",
    "commit.job",
    "commit.conflict",
)


class InjectedFault(RuntimeError):
    """A chaos-harness fault. Deliberately NOT a TpuOOMError: the OOM
    retry loops must not swallow it — each site's own recovery path
    (backoff, quarantine, degradation ladder) has to prove itself."""

    def __init__(self, site: str, detail: str = ""):
        self.site = site
        self.detail = detail
        msg = f"injected fault at {site}"
        if detail:
            msg += f" ({detail})"
        super().__init__(msg)


class SitePolicy:
    """One site's injection policy: probability | every-Nth | one-shot."""

    __slots__ = ("kind", "value")

    def __init__(self, kind: str, value: float = 0.0):
        if kind not in ("p", "every", "once"):
            raise ValueError(f"unknown chaos policy kind {kind!r}")
        self.kind = kind
        self.value = value

    def decide(self, rng: random.Random, call_index: int) -> bool:
        if self.kind == "once":
            return call_index == 1
        if self.kind == "every":
            n = max(1, int(self.value))
            return call_index % n == 0
        return rng.random() < float(self.value)

    def __repr__(self):
        if self.kind == "once":
            return "once"
        return f"{self.kind}={self.value}"


def parse_sites(spec: str, default_p: float) -> Dict[str, SitePolicy]:
    """'io.read:p=0.1;shuffle.fetch:every=3;spill.disk:once'
    -> {site: SitePolicy}. A bare site name takes the default
    probability. Unknown site names are allowed (future PRs declare new
    sites without touching the parser)."""
    out: Dict[str, SitePolicy] = {}
    for part in (spec or "").split(";"):
        part = part.strip()
        if not part:
            continue
        site, _, pol = part.partition(":")
        site = site.strip()
        pol = pol.strip()
        if not site:
            raise ValueError(f"empty site name in chaos spec {spec!r}")
        if not pol:
            out[site] = SitePolicy("p", default_p)
        elif pol == "once":
            out[site] = SitePolicy("once")
        elif pol.startswith("p="):
            p = float(pol[2:])
            if not 0.0 <= p <= 1.0:
                raise ValueError(f"chaos probability out of [0,1]: {pol}")
            out[site] = SitePolicy("p", p)
        elif pol.startswith("every="):
            out[site] = SitePolicy("every", int(pol[6:]))
        else:
            raise ValueError(f"unknown chaos policy {pol!r} for {site}")
    return out


class FaultRegistry:
    """Thread-safe registry of armed sites with per-site deterministic
    RNG streams and checked/injected counters."""

    def __init__(self, seed: int = 0,
                 policies: Optional[Dict[str, SitePolicy]] = None):
        self.seed = seed
        self._policies = dict(policies or {})
        self._rngs = {site: random.Random(f"{seed}:{site}")
                      for site in self._policies}
        self._calls: Dict[str, int] = {s: 0 for s in self._policies}
        self._injected: Dict[str, int] = {}
        self._lock = threading.Lock()

    @property
    def armed(self) -> bool:
        return bool(self._policies)

    def sites(self) -> Tuple[str, ...]:
        return tuple(sorted(self._policies))

    def should_inject(self, site: str) -> bool:
        pol = self._policies.get(site)
        if pol is None:
            return False
        with self._lock:
            self._calls[site] += 1
            hit = pol.decide(self._rngs[site], self._calls[site])
            if hit:
                self._injected[site] = self._injected.get(site, 0) + 1
        if hit:
            from spark_rapids_tpu.obs import events as obs_events

            obs_events.emit("chaos", site=site)
        return hit

    def maybe_inject(self, site: str, detail: str = "") -> None:
        if self.should_inject(site):
            raise InjectedFault(site, detail)

    def counters(self) -> Dict[str, Dict[str, int]]:
        with self._lock:
            return {site: {"checked": self._calls.get(site, 0),
                           "injected": self._injected.get(site, 0)}
                    for site in self._policies}


_DISABLED = FaultRegistry()
_registry: FaultRegistry = _DISABLED
_lock = threading.Lock()


def get() -> FaultRegistry:
    return _registry


def install(registry: FaultRegistry) -> FaultRegistry:
    """Swap the process registry (tests, session configure)."""
    global _registry
    with _lock:
        _registry = registry
    return registry


def configure(conf=None) -> FaultRegistry:
    """Session-lifecycle hook (plugin.py TpuExecutorPlugin.init): arm
    the registry per `spark.rapids.tpu.chaos.*` or disarm it."""
    from spark_rapids_tpu.config import rapids_conf as rc

    if conf is None or not conf.get(rc.CHAOS_ENABLED):
        return install(_DISABLED)
    default_p = conf.get(rc.CHAOS_DEFAULT_P)
    policies = parse_sites(conf.get(rc.CHAOS_SITES), default_p)
    if not policies:
        policies = {s: SitePolicy("p", default_p) for s in KNOWN_SITES}
    return install(FaultRegistry(conf.get(rc.CHAOS_SEED), policies))


def maybe_inject(site: str, detail: str = "") -> None:
    """Hot-path entry: a dict lookup + early return when disarmed."""
    reg = _registry
    if reg._policies:
        reg.maybe_inject(site, detail)


def should_inject(site: str) -> bool:
    reg = _registry
    return bool(reg._policies) and reg.should_inject(site)


def counters() -> Dict[str, Dict[str, int]]:
    return _registry.counters()

"""Model server core: versioned loading, hot-swap, micro-batching.

TPU-native heir of C++ ``tensorflow_model_server``
(kubeflow/tf-serving/tf-serving.libsonnet:118-132): watches a model base
path for numbered versions, serves the latest, hot-swaps when new versions
land, and unloads superseded ones — the semantics the reference got for
free from TF-Serving (SURVEY.md §7 "Hard parts: serving on TPU").

Batching: TPU inference wants large, fixed-shape batches for the MXU; the
MicroBatcher coalesces concurrent single requests into one device call,
padding to the nearest allowed batch size so XLA reuses a handful of
compiled programs instead of one per request shape.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import random
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from kubeflow_tpu.runtime import tracing
from kubeflow_tpu.serving.adapters import (
    AdapterNotFound,
    split_model_adapter,
)
from kubeflow_tpu.serving.errors import (  # noqa: F401 — re-exported
    BatcherClosed,
    DeadlineExceeded,
    Overloaded,
)
from kubeflow_tpu.serving.export import list_versions, load_version
from kubeflow_tpu.testing import faults

log = logging.getLogger(__name__)


def locked_snapshot(lock, data: Dict[str, Any],
                    extra: Optional[Callable[[], Dict[str, Any]]] = None):
    """Copy mutable stats counters under their owning lock.

    Returns (dict(data), extra() or {}) taken atomically.  Every stats()
    surface (MicroBatcher, BucketedLMBatcher, DecodeEngine) reads its
    counters through this ONE helper, and writers merge under the same
    lock — a /metrics scrape mid-dispatch must never see a torn
    half-updated cycle profile or an occupancy that sums to more
    requests than exist."""
    with lock:
        return dict(data), (extra() if extra is not None else {})


# One name/help for the request counter shared by the REST and gRPC
# faces — divergent literals would silently create a second series.
REQUESTS_TOTAL = "kft_serving_requests_total"
REQUESTS_HELP = "serving requests by model/route/outcome (REST + gRPC)"
LATENCY_SECONDS = "kft_serving_request_seconds"
LATENCY_HELP = "serving request latency by route (REST + gRPC)"
# Fault-layer series shared by every batching plane (MicroBatcher,
# BucketedLMBatcher, DecodeEngine) — one series per batcher label, so
# overload sheds and deadline expiries are comparable across planes.
SHED_TOTAL = "kft_serving_shed_total"
SHED_HELP = "admissions refused at the queue/in-flight caps, by batcher"
EXPIRED_TOTAL = "kft_serving_deadline_expired_total"
EXPIRED_HELP = "requests failed by their deadline, by batcher"
RELOAD_FAILURES_TOTAL = "kft_serving_reload_failures_total"
RELOAD_FAILURES_HELP = "model (re)load attempts that raised, by model"
BREAKER_OPEN = "kft_serving_reload_breaker_open"
BREAKER_OPEN_HELP = "1 while a model's reload circuit breaker is open"
# Scrape-refreshed load gauges (refresh_gauges): until these existed,
# in-flight was only visible through the :stats JSON route — the fleet
# autoscaler and dashboards scrape ONE endpoint (/metrics) for load.
INFLIGHT_GAUGE = "kft_serving_inflight"
INFLIGHT_HELP = ("requests in flight (transport + predict); unlabeled "
                 "= process total, model= per-model predict calls")
QUEUE_GAUGE = "kft_serving_queue_depth"
QUEUE_HELP = "pending entries in a model's batching plane, by model"
READY_GAUGE = "kft_serving_ready"
READY_HELP = "1 when /readyz would say ready (models loaded, not draining)"
CACHED_RATIO_GAUGE = "kft_serving_cached_token_ratio"
CACHED_RATIO_HELP = ("fraction of prompt tokens served from the engine "
                     "prefix cache; unlabeled = process aggregate, "
                     "model= per-model")
# Hierarchical KV (§5.10): host-tier occupancy as a fraction of the
# spill capacity — the fleet scrape and `fleet status` SPILL% column
# read this per replica.
SPILL_RATIO_GAUGE = "kft_serving_kv_spill_ratio"
SPILL_RATIO_HELP = ("host spill-tier occupancy / host_spill_blocks "
                    "(0 when the tier is disabled), by model; "
                    "unlabeled = process aggregate")
# Idempotency dedup: requests answered from the per-key result cache
# (completed duplicates) or attached to an in-flight execution — the
# survivable-inference counter a chaos run asserts on.
DEDUP_HITS_TOTAL = "kft_serving_dedup_hits_total"
DEDUP_HITS_HELP = ("requests answered from the idempotency dedup "
                   "cache (completed result or in-flight attach), "
                   "by model")


@dataclasses.dataclass
class LoadedModel:
    name: str
    version: int
    predict: Callable[[Dict[str, Any]], Dict[str, Any]]
    meta: Dict[str, Any]


class _ReloadBreaker:
    """Exponential-backoff circuit breaker for one model's (re)loads.

    A corrupt checkpoint directory must not hot-loop the version
    watcher: after a load failure the breaker OPENS for a jittered,
    exponentially-growing backoff during which reload() skips the disk
    entirely (the last-good version keeps serving).  When the backoff
    expires the breaker goes HALF-OPEN: exactly one trial load runs;
    success closes it, failure re-opens with a doubled backoff.  A NEW
    latest version (different from the one that failed) resets the
    breaker immediately — the breaker guards the corrupt artifact, not
    the model name.

    The backoff clock is faults.monotonic() (the skewable policy
    clock), so chaos tests drive the open -> half-open -> closed walk
    without wall-clock sleeps."""

    def __init__(self, base_s: float = 0.5, cap_s: float = 60.0,
                 rng: Optional[random.Random] = None):
        self._base_s = base_s
        self._cap_s = cap_s
        # OS-seeded by default: each replica must walk a DIFFERENT
        # jitter sequence or concurrent replicas watching one shared
        # model path retry in lockstep.  Tests needing a fixed walk
        # pass their own rng.
        self._rng = rng or random.Random()
        self._lock = threading.Lock()
        self.failures = 0
        self.open_until = 0.0
        self.failing_version: Optional[int] = None
        self._half_open = False

    def allow(self, version: int) -> bool:
        """May a load of ``version`` run now?  Claims the single
        half-open trial slot when the backoff has expired."""
        with self._lock:
            if self.failures == 0:
                return True
            if version != self.failing_version:
                self._reset_locked()
                return True
            if self._half_open:
                return False  # a trial is already in flight
            if faults.monotonic() < self.open_until:
                return False
            self._half_open = True
            return True

    def record_failure(self, version: int) -> None:
        with self._lock:
            self.failures += 1
            self.failing_version = version
            self._half_open = False
            backoff = min(self._cap_s,
                          self._base_s * (2 ** (self.failures - 1)))
            # Full jitter up to +25%: concurrent replicas watching one
            # shared model path must not retry in lockstep.
            backoff *= 1.0 + 0.25 * self._rng.random()
            self.open_until = faults.monotonic() + backoff

    def record_success(self) -> None:
        with self._lock:
            self._reset_locked()

    def _reset_locked(self) -> None:
        self.failures = 0
        self.open_until = 0.0
        self.failing_version = None
        self._half_open = False

    @property
    def open(self) -> bool:
        with self._lock:
            return self.failures > 0


class _DedupCache:
    """Bounded, TTL'd idempotency-key -> result cache.

    One entry per key: the FIRST request to present a key becomes the
    primary and executes; concurrent duplicates attach to its entry
    and wait on its event; later duplicates of a COMPLETED key are
    answered from the cached result — so a connection that dies after
    the replica finished no longer forces a client-visible failure or
    a double execution when the request is retried with the same key.

    Failures are never cached: ``fail`` resolves attached waiters with
    the error and drops the entry, so a later retry re-executes (a
    transient Overloaded must not be replayed from cache for the TTL).
    Completed entries expire after ``ttl_s`` on the skewable policy
    clock and are LRU-bounded at ``capacity``; in-flight entries are
    pinned (waiters hold references) and never evicted."""

    def __init__(self, capacity: int = 1024, ttl_s: float = 120.0):
        self.capacity = max(1, int(capacity))
        self.ttl_s = float(ttl_s)
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[str, dict]" = \
            collections.OrderedDict()

    def begin(self, key: str) -> Tuple[str, dict]:
        """Claim or join ``key``: ("new", entry) makes the caller the
        primary (it MUST finish/fail the entry), ("inflight", entry)
        attaches to a live execution, ("done", entry) hands back the
        cached result."""
        with self._lock:
            self._sweep_locked()
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                verdict = "done" if entry["event"].is_set() \
                    else "inflight"
                return verdict, entry
            entry = {"event": threading.Event(), "result": None,
                     "err": None, "done_at": None}
            self._entries[key] = entry
            while len(self._entries) > self.capacity:
                victim = next(
                    (k for k, e in self._entries.items()
                     if e["event"].is_set()), None)
                if victim is None:
                    break  # everything in flight: pinned
                del self._entries[victim]
            return "new", entry

    def finish(self, key: str, entry: dict, result: Any) -> None:
        with self._lock:
            entry["result"] = result
            entry["done_at"] = faults.monotonic()
        entry["event"].set()

    def fail(self, key: str, entry: dict, exc: BaseException) -> None:
        with self._lock:
            entry["err"] = exc
            if self._entries.get(key) is entry:
                del self._entries[key]
        entry["event"].set()

    def _sweep_locked(self) -> None:
        if self.ttl_s <= 0:
            return
        now = faults.monotonic()
        stale = [k for k, e in self._entries.items()
                 if e["done_at"] is not None
                 and now - e["done_at"] > self.ttl_s]
        for k in stale:
            del self._entries[k]

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


class ModelServer:
    """Serves N named models, each from a versioned base path."""

    def __init__(self, poll_interval_s: float = 2.0,
                 reload_backoff_s: float = 0.5,
                 reload_backoff_cap_s: float = 60.0,
                 max_inflight: int = 0,
                 overload_retry_after_s: float = 1.0,
                 dedup_capacity: int = 1024,
                 dedup_ttl_s: float = 120.0,
                 role: str = "unified"):
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(
                f"role must be unified/prefill/decode, got {role!r}")
        # Disaggregated-serving tier (--role): advertised on /readyz so
        # the fleet registry learns the two-tier topology — "prefill"
        # replicas serve :prefill into KV handoff payloads, "decode"
        # replicas import them, "unified" (default) replicas keep
        # today's single-tier path.  The role is an ADVERTISEMENT, not
        # a gate: every replica still answers every route, so a
        # degraded fleet can always fall back to the untiered path.
        self.role = role
        self._models: Dict[str, Dict[int, LoadedModel]] = {}
        self._base_paths: Dict[str, str] = {}
        self._lock = threading.RLock()
        self._poll_interval_s = poll_interval_s
        self._watcher: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # Per-model request batching (enable_batching): factory builds a
        # batcher around each newly-loaded version's predict, so
        # hot-swap keeps batching without a restart.
        self._batcher_factories: Dict[str, Callable] = {}
        self._batchers: Dict[str, Any] = {}
        # Reload circuit breakers, one per model (see _ReloadBreaker).
        self._reload_backoff_s = reload_backoff_s
        self._reload_backoff_cap_s = reload_backoff_cap_s
        self._breakers: Dict[str, _ReloadBreaker] = {}
        # Readiness: /readyz flips not-ready on begin_drain() (SIGTERM)
        # while /healthz stays live — the rolling-update contract.
        self._draining = threading.Event()
        # Requests inside predict() right now, across REST + gRPC +
        # direct callers — the graceful-drain quiescence signal.
        self._inflight = 0
        # Per-model in-flight cap covering EVERY path — including the
        # direct one (multi-row requests, prompts a batcher's accepts()
        # declines), which has no batcher queue to bound it: each such
        # request otherwise runs a whole device program on its own
        # transport thread, unbounded.  0 = unbounded.
        self._max_inflight = max(0, int(max_inflight))
        self._overload_retry_after_s = overload_retry_after_s
        self._inflight_by_model: Dict[str, int] = {}
        # Idempotency-key result dedup (see _DedupCache): both wire
        # faces pass the x-kft-idempotency-key header/metadata through
        # to predict(); the fleet router mints one per proxied POST.
        self._dedup = _DedupCache(dedup_capacity, dedup_ttl_s)

    # -- loading ----------------------------------------------------------

    def add_model(self, name: str, base_path: str) -> None:
        with self._lock:
            self._base_paths[name] = base_path
            self._models.setdefault(name, {})
        self.reload(name)

    def reload(self, name: str) -> bool:
        """Scan the base path; load new latest version, drop stale ones.
        Returns True if the served version changed.

        Load failures (corrupt checkpoint directory, bad loader) raise
        to the caller AND trip the model's circuit breaker: until its
        jittered exponential backoff expires, further reload() calls of
        the same version return False without touching the loader, so
        the version watcher cannot hot-loop on a bad artifact while the
        last-good version keeps serving."""
        base = self._base_paths[name]
        versions = list_versions(base)
        if not versions:
            log.warning("no versions for model %r under %s", name, base)
            return False
        latest = versions[-1]
        with self._lock:
            have = self._models[name]
            if latest in have:
                return False
            breaker = self._breakers.get(name)
            if breaker is None:
                breaker = self._breakers[name] = _ReloadBreaker(
                    self._reload_backoff_s, self._reload_backoff_cap_s)
        if not breaker.allow(latest):
            return False
        from kubeflow_tpu.runtime.prom import REGISTRY

        try:
            faults.fire("loader.load")
            predict, meta = load_version(base, latest)
        except Exception:
            breaker.record_failure(latest)
            REGISTRY.counter(
                RELOAD_FAILURES_TOTAL, RELOAD_FAILURES_HELP).inc(
                    model=name)
            REGISTRY.gauge(BREAKER_OPEN, BREAKER_OPEN_HELP).set(
                1, model=name)
            log.warning(
                "load of %r v%d failed; breaker open until +%.1fs "
                "(failure #%d), last-good version keeps serving",
                name, latest,
                max(0.0, breaker.open_until - faults.monotonic()),
                breaker.failures)
            raise
        breaker.record_success()
        REGISTRY.gauge(BREAKER_OPEN, BREAKER_OPEN_HELP).set(0, model=name)
        with self._lock:
            model = LoadedModel(
                name=name, version=latest, predict=predict, meta=meta
            )
            self._models[name][latest] = model
            # Keep only the latest (TF-Serving default version policy).
            for v in [v for v in self._models[name] if v != latest]:
                del self._models[name][v]
            old_batcher = self._batchers.pop(name, None)
            factory = self._batcher_factories.get(name)
        self._swap_batcher(name, factory, model, old_batcher)
        log.info("model %r now serving version %d", name, latest)
        return True

    def _swap_batcher(self, name, factory, model, old_batcher) -> None:
        """Close-old / build / install / close-displaced, the ONE
        batcher swap sequence (reload and enable_batching share it).

        Runs outside the server lock: close blocks on in-flight
        requests, which themselves may be waiting on get()/predict().
        The old batcher closes BEFORE the successor is built — a
        DecodeEngine owns a device-resident KV cache, and build-then-
        close would hold two at once (OOM on models sized to fit one);
        requests landing in the gap take the direct predict path.  A
        factory may decline a model (return None) — e.g. the serving
        entrypoint's factory engines LM models but leaves others on
        the direct path when micro-batching is off — which DISABLES
        batching rather than leaving the old batcher serving."""
        if old_batcher is not None:
            old_batcher.close()
        if factory is None or model is None:
            return
        batcher = factory(model)
        if batcher is not None:
            with self._lock:
                displaced = self._batchers.get(name)
                self._batchers[name] = batcher
            if displaced is not None and displaced is not batcher:
                displaced.close()  # lost a swap race; don't leak it

    def start_watcher(self) -> None:
        """Background version polling — the hot-swap path."""
        if self._watcher is not None:
            return
        self._stop.clear()

        def run():
            while not self._stop.wait(self._poll_interval_s):
                for name in list(self._base_paths):
                    try:
                        self.reload(name)
                    except Exception:
                        log.exception("reload of %r failed", name)

        self._watcher = threading.Thread(target=run, daemon=True,
                                         name="version-watcher")
        self._watcher.start()

    def enable_batching(
        self, name: str,
        factory: Callable[[LoadedModel], Any],
    ) -> None:
        """Coalesce concurrent predict() calls for ``name`` through a
        batcher built by ``factory(loaded_model)`` (anything with
        submit/close — MicroBatcher or BucketedLMBatcher).  The batcher
        is rebuilt around every newly-loaded version, so hot-swap keeps
        batching; explicit-version requests bypass it (debugging a
        pinned version should not share the live batch path).
        """
        with self._lock:
            self._batcher_factories[name] = factory
            model = None
            versions = self._models.get(name)
            if versions:
                model = versions[max(versions)]
            old_batcher = self._batchers.pop(name, None)
        self._swap_batcher(name, factory, model, old_batcher)

    def stop(self) -> None:
        self._stop.set()
        if self._watcher is not None:
            self._watcher.join(timeout=5)
            self._watcher = None
        with self._lock:
            batchers = list(self._batchers.values())
            self._batchers.clear()
        for b in batchers:
            b.close()

    # -- queries ----------------------------------------------------------

    def get(self, name: str, version: Optional[int] = None) -> LoadedModel:
        with self._lock:
            if name not in self._models or not self._models[name]:
                raise KeyError(f"model {name!r} not loaded")
            versions = self._models[name]
            if version is None:
                return versions[max(versions)]
            if version not in versions:
                raise KeyError(
                    f"model {name!r} has no version {version}; "
                    f"serving {sorted(versions)}"
                )
            return versions[version]

    def models(self) -> Dict[str, List[int]]:
        with self._lock:
            return {n: sorted(v) for n, v in self._models.items()}

    def has_model(self, name: str) -> bool:
        base, _ = split_model_adapter(name)
        with self._lock:
            return base in self._models

    def adapter_info(self) -> Dict[str, List[Dict[str, Any]]]:
        """Resident adapters per engine-served model — name, digest,
        slot index, pins — for the /readyz advertisement the router's
        digest-affinity pick reads (§5.11).  Models without an adapter
        registry are omitted."""
        with self._lock:
            batchers = dict(self._batchers)
        out: Dict[str, List[Dict[str, Any]]] = {}
        for name, batcher in batchers.items():
            info_fn = getattr(batcher, "adapter_info", None)
            if info_fn is None:
                continue
            info = info_fn()
            if info:
                out[name] = info
        return out

    def _resolve_adapter(
        self, name: str, inputs: Dict[str, Any],
    ) -> Tuple[str, Dict[str, Any]]:
        """Split a ``model@adapter`` request name (§5.11): the BASE
        name drives every lookup/metric/batcher route — one model, one
        engine, one program — while the adapter rides
        ``inputs["adapter"]`` for the engine to resolve against its
        registry at admission.  Plain names pass through untouched."""
        base, adapter = split_model_adapter(name)
        if adapter:
            inputs = dict(inputs)
            inputs["adapter"] = adapter
        return base, inputs

    # -- readiness / drain ------------------------------------------------

    def begin_drain(self) -> None:
        """Flip /readyz not-ready (SIGTERM).  Requests already accepted
        — and late arrivals from load balancers that have not yet seen
        the readiness flip — keep being served; only the readiness
        signal changes, so rolling updates drain without dropping."""
        if not self._draining.is_set():
            log.info("drain: readiness flipped to not-ready")
        self._draining.set()

    def draining(self) -> bool:
        return self._draining.is_set()

    def is_ready(self) -> bool:
        """Readiness = at least one model loaded and not draining —
        distinct from /healthz liveness, which stays true throughout a
        drain (a draining pod is alive, just not accepting NEW work)."""
        if self._draining.is_set():
            return False
        with self._lock:
            return any(self._models.values())

    def inflight(self) -> int:
        """Requests currently inside predict() plus accepted transport
        requests still being parsed (enter_request) — the graceful-
        drain quiescence signal."""
        with self._lock:
            return self._inflight

    def enter_request(self) -> None:
        """Transport-level in-flight bracket: the REST handler wraps
        its WHOLE dispatch (body read and parse included) so drain
        cannot conclude quiescence while an accepted connection is
        still deserializing the request it would then lose.  Nests
        with predict()'s own bracket — inflight() is a zero/nonzero
        quiescence signal, not a request count."""
        with self._lock:
            self._inflight += 1

    def exit_request(self) -> None:
        with self._lock:
            self._inflight -= 1

    def refresh_gauges(self) -> None:
        """Push the live load signals into the prom registry — called at
        scrape time by the /metrics route (a gauge the autoscaler reads
        must be current at the instant of the scrape, and in-flight has
        no natural write site that is not the predict hot path)."""
        from kubeflow_tpu.runtime.prom import REGISTRY

        with self._lock:
            total = self._inflight
            per_model = {n: self._inflight_by_model.get(n, 0)
                         for n in self._models}
        inflight = REGISTRY.gauge(INFLIGHT_GAUGE, INFLIGHT_HELP)
        inflight.set(total)
        for name, count in per_model.items():
            inflight.set(count, model=name)
        queue = REGISTRY.gauge(QUEUE_GAUGE, QUEUE_HELP)
        ratio = REGISTRY.gauge(CACHED_RATIO_GAUGE, CACHED_RATIO_HELP)
        spill = REGISTRY.gauge(SPILL_RATIO_GAUGE, SPILL_RATIO_HELP)
        cached_total = prompt_total = 0
        spill_used = spill_cap = 0
        any_engine = any_spill = False
        for name in per_model:
            stats = self.batcher_stats(name) or {}
            queue.set(stats.get("queue_depth", 0) or 0, model=name)
            if "cached_token_ratio" in stats:
                # Prefix-cache effectiveness (DecodeEngine models): the
                # fleet registry scrapes this per replica so operators
                # see cache hit rates across the whole fleet.
                any_engine = True
                ratio.set(stats["cached_token_ratio"], model=name)
                cached_total += stats.get("cached_prompt_tokens", 0)
                prompt_total += stats.get("prompt_tokens", 0)
            cap = stats.get("host_spill_blocks", 0) or 0
            if cap:
                # Host spill-tier occupancy (§5.10): same reset-with-
                # the-engine discipline as the cached ratio above.
                any_spill = True
                used = stats.get("host_tier_used", 0) or 0
                spill.set(round(used / cap, 4), model=name)
                spill_used += used
                spill_cap += cap
        if any_spill:
            spill.set(round(spill_used / spill_cap, 4))
        if any_engine:
            # The unlabeled aggregate must RESET with its engines: a
            # hot-reload rebuilds the engine with an empty cache, and
            # the fleet scrape reads this (first-sorted) series — a
            # stale pre-reload ratio would report a warm cache the
            # replica no longer has.
            ratio.set(round(cached_total / prompt_total, 4)
                      if prompt_total else 0.0)
        REGISTRY.gauge(READY_GAUGE, READY_HELP).set(
            1 if self.is_ready() else 0)

    def batcher_stats(self, name: str) -> Optional[Dict[str, Any]]:
        """Live stats of the model's batcher/engine (None when the model
        serves on the direct path) — the :stats REST route and the gRPC
        metadata face both read through here."""
        with self._lock:
            batcher = self._batchers.get(name)
        stats = getattr(batcher, "stats", None)
        return stats() if callable(stats) else None

    @staticmethod
    def _single_row(inputs: Dict[str, Any]) -> bool:
        """True when every input leaf carries exactly one example — the
        only shape a batcher entry can represent (each entry gets one
        result row back; multi-row requests go straight to predict)."""
        for v in inputs.values():
            if isinstance(v, str):
                continue  # routing metadata (e.g. "adapter"), not a leaf
            shape = getattr(v, "shape", None)
            if shape is None:
                v = np.asarray(v)
                shape = v.shape
            if len(shape) == 0 or shape[0] != 1:
                return False
        return True

    def predict(
        self, name: str, inputs: Dict[str, Any],
        version: Optional[int] = None,
        deadline: Optional[float] = None,
        idem_key: Optional[str] = None,
    ) -> Dict[str, Any]:
        """``deadline`` is an absolute faults.monotonic() instant: the
        batching planes enforce it in their queues and (the engine) mid-
        generation; the direct path checks it at entry only — a jitted
        whole-generation program cannot be interrupted, which is exactly
        why the engine owns the LM hot path.

        ``idem_key`` (the x-kft-idempotency-key header/metadata value)
        dedups retried requests: the first presentation executes, an
        in-flight duplicate attaches to that execution, and a completed
        duplicate is answered from the TTL'd result cache — so a retry
        after a dropped connection is answered, never re-run."""
        name, inputs = self._resolve_adapter(name, inputs)
        if idem_key:
            return self._predict_deduped(name, inputs, version,
                                         deadline, idem_key)
        return self._predict_admitted(name, inputs, version, deadline)

    def _predict_deduped(self, name, inputs, version, deadline,
                         idem_key):
        from kubeflow_tpu.runtime.prom import REGISTRY

        verdict, entry = self._dedup.begin(idem_key)
        if verdict != "new":
            with self._lock:
                label = name if name in self._models else "_unknown_"
            REGISTRY.counter(DEDUP_HITS_TOTAL, DEDUP_HITS_HELP).inc(
                model=label)
            if verdict == "inflight":
                # Attach to the primary (no second execution, no
                # second in-flight slot), bounded by OUR deadline —
                # the primary enforces its own.
                timeout = None if deadline is None else max(
                    0.0, deadline - faults.monotonic())
                if not entry["event"].wait(timeout):
                    raise DeadlineExceeded(
                        f"deadline expired waiting on the in-flight "
                        f"twin of idempotency key {idem_key!r}")
            if entry["err"] is not None:
                raise entry["err"]
            return entry["result"]
        try:
            result = self._predict_admitted(name, inputs, version,
                                            deadline)
        except BaseException as exc:
            # Failures are not cached: waiters get the error, the key
            # frees, and a later retry re-executes.
            self._dedup.fail(idem_key, entry, exc)
            raise
        self._dedup.finish(idem_key, entry, result)
        return result

    def _predict_admitted(
        self, name: str, inputs: Dict[str, Any],
        version: Optional[int], deadline: Optional[float],
    ) -> Dict[str, Any]:
        # Admission child span (trace context set by the transport
        # layer): covers the in-flight-cap verdict; a shed admission
        # records status="shed" so the trace is always tail-retained.
        ctx = tracing.current_ctx()
        t_adm = time.perf_counter() if ctx is not None else 0.0
        try:
            with self._lock:
                if self._max_inflight and self._inflight_by_model.get(
                        name, 0) >= self._max_inflight:
                    from kubeflow_tpu.runtime.prom import REGISTRY

                    REGISTRY.counter(SHED_TOTAL, SHED_HELP).inc(
                        batcher=f"{name}-inflight")
                    raise Overloaded(
                        f"model {name!r} at its in-flight cap "
                        f"({self._max_inflight})",
                        retry_after_s=self._overload_retry_after_s)
                self._inflight += 1
                self._inflight_by_model[name] = \
                    self._inflight_by_model.get(name, 0) + 1
        except Overloaded:
            self._record_admission(ctx, name, t_adm, status="shed")
            raise
        self._record_admission(ctx, name, t_adm)
        try:
            return self._predict(name, inputs, version, deadline)
        finally:
            with self._lock:
                self._inflight -= 1
                self._inflight_by_model[name] -= 1

    def _record_admission(self, ctx, name: str, t_adm: float,
                          status: str = "ok") -> None:
        """The one server.admission stamping site (span names are
        unique per module — span-discipline): shed and admitted
        verdicts both land here."""
        if ctx is not None:
            tracing.record_span(
                "server.admission", ctx, t_adm, time.perf_counter(),
                status=status, attrs={"model": name})

    def _predict(
        self, name: str, inputs: Dict[str, Any],
        version: Optional[int], deadline: Optional[float],
    ) -> Dict[str, Any]:
        if deadline is not None and faults.monotonic() >= deadline:
            raise DeadlineExceeded(
                f"deadline expired before dispatch of {name!r}")
        if version is None:
            # Convert list-typed payloads (raw REST JSON) to arrays ONCE
            # before the batched path touches them — _single_row,
            # _shape_sig, and the dispatch concatenate all consume the
            # same arrays instead of re-materializing the payload.
            converted = {
                k: v if isinstance(v, str) or hasattr(v, "shape")
                else np.asarray(v)
                for k, v in inputs.items()
            }
            # Bounded retry: a hot-swap or drain can close the batcher
            # between the lookup and submit — and close() now FAILS
            # queued entries with BatcherClosed instead of draining
            # them — so the second lap picks up the replacement built
            # by reload(), and a missing replacement falls through to
            # the direct path: an accepted request is never dropped by
            # a swap race.
            for _ in range(2):
                with self._lock:
                    batcher = self._batchers.get(name)
                if batcher is None or not self._single_row(converted):
                    break
                accepts = getattr(batcher, "accepts", None)
                if accepts is not None and not accepts(converted):
                    break  # e.g. prompt beyond the largest bucket
                try:
                    if deadline is None:
                        return batcher.submit(converted)
                    return batcher.submit(converted, deadline=deadline)
                except BatcherClosed:
                    continue
        model = self.get(name, version)
        if inputs.get("adapter"):
            # The direct path dispatches whole-generation programs with
            # the BASE weights only — silently answering an adapter
            # request with base output would be a wrong-tenant response,
            # strictly worse than failing (§5.11).
            raise AdapterNotFound(
                f"adapter {inputs['adapter']!r} requires the "
                f"continuous-batching engine; model {name!r} fell "
                f"through to the direct path")
        # Re-checked at the fallthrough: the request may have spent its
        # whole budget queued in a batcher that closed under it (drain,
        # swap race) — launching an uninterruptible whole-generation
        # program now would return a late 200 the caller abandoned.
        if deadline is not None and faults.monotonic() >= deadline:
            raise DeadlineExceeded(
                f"deadline expired before direct dispatch of {name!r}")
        return model.predict(inputs)

    def prefill_handoff(
        self, name: str, inputs: Dict[str, Any],
        deadline: Optional[float] = None,
    ) -> Dict[str, Any]:
        """Disaggregated serving, prefill tier: run the prompt's
        chunked prefill on this replica's DecodeEngine and return the
        result WITH its finished KV pages (``kv_handoff``) so a
        decode-tier replica can import them and stream the completion
        (the :prefill route).  Raises KeyError on unknown models and
        ValueError when the model has no engine.  Bracketed in the
        in-flight counts like any predict."""
        name, inputs = self._resolve_adapter(name, inputs)
        self.get(name)  # KeyError -> 404 on unknown names
        with self._lock:
            batcher = self._batchers.get(name)
        export_fn = getattr(batcher, "prefill_export", None)
        if export_fn is None:
            raise ValueError(
                f"model {name!r} has no decode engine "
                f"(:prefill requires the continuous-batching engine)")
        with self._lock:
            self._inflight += 1
            self._inflight_by_model[name] = \
                self._inflight_by_model.get(name, 0) + 1
        try:
            return export_fn(inputs, deadline=deadline)
        finally:
            with self._lock:
                self._inflight -= 1
                self._inflight_by_model[name] -= 1

    def fetch_kv(self, name: str,
                 inputs: Dict[str, Any]) -> Dict[str, Any]:
        """Hierarchical KV fetch (§5.10): look ``tokens`` up in the
        model's engine host spill tier and return the covered prefix's
        pages in engine export form, or a miss.  Raises KeyError on
        unknown models and ValueError when the model has no engine.
        A pure host-memory read — no in-flight bracket: a drain must
        not wait on a peer's failover fetch, and the fetch must keep
        answering WHILE this replica drains (the surviving session
        state is exactly what a peer needs then)."""
        name, _ = split_model_adapter(name)
        self.get(name)  # KeyError -> 404 on unknown names
        with self._lock:
            batcher = self._batchers.get(name)
        fetch_fn = getattr(batcher, "fetch_kv", None)
        if fetch_fn is None:
            raise ValueError(
                f"model {name!r} has no decode engine "
                f"(:fetch_kv requires the continuous-batching engine)")
        return fetch_fn(inputs)

    def generate_stream(
        self, name: str, inputs: Dict[str, Any],
        deadline: Optional[float] = None,
    ):
        """Streaming LM generation: (meta, iterator) from the model's
        DecodeEngine (the only batching plane with a streaming
        surface — see DecodeEngine.submit_stream).  Raises KeyError on
        unknown models and ValueError when the model has no engine:
        the static batchers dispatch whole-generation programs and
        cannot stream.  The iterator is bracketed in the in-flight
        counts (drain waits for live streams); callers must exhaust or
        close() it."""
        name, inputs = self._resolve_adapter(name, inputs)
        self.get(name)  # KeyError -> 404 on unknown names
        with self._lock:
            batcher = self._batchers.get(name)
        stream_fn = getattr(batcher, "submit_stream", None)
        if stream_fn is None:
            raise ValueError(
                f"model {name!r} has no streaming decode engine "
                f"(:generate requires the continuous-batching engine)")
        meta, stream = stream_fn(inputs, deadline=deadline)

        def bracketed():
            # Counted from first iteration (a generator closed before
            # its first next() never runs its finally, so an eager
            # increment could leak); the REST transport's own
            # enter_request bracket covers the gap.
            with self._lock:
                self._inflight += 1
                self._inflight_by_model[name] = \
                    self._inflight_by_model.get(name, 0) + 1
            try:
                for chunk in stream:
                    yield chunk
            finally:
                with self._lock:
                    self._inflight -= 1
                    self._inflight_by_model[name] -= 1

        return meta, bracketed()


class MicroBatcher:
    """Coalesce concurrent requests into padded, pipelined device batches.

    Callers block in ``submit`` until their rows come back.  Batches are
    padded up to the next size in ``allowed_batch_sizes`` so the jitted
    predict fn compiles once per size, not once per request count —
    the TF-Serving batching-parameters idea, TPU-shaped.

    Dispatch is pipelined: ``in_flight`` executor threads each collect a
    batch and run predict concurrently, so while batch N's device call is
    in its (possibly high-latency) round trip, batch N+1 is already being
    assembled and dispatched.  With one executor the effective pipeline
    depth is 1 and throughput collapses to batch_size/latency — the
    round-2 failure mode.  Per-batch device results are converted to host
    numpy ONCE per output key (a single device->host transfer), then rows
    are handed out as views; the earlier per-request ``np.asarray`` did
    one transfer per request and serialized the whole batch on latency.

    Instrumentation: every dispatched batch records its occupied size in
    ``stats()`` — the effective-batch-size distribution is the first
    thing to look at when batcher throughput is below expectation.
    """

    def __init__(
        self,
        predict: Callable[[Dict[str, Any]], Dict[str, Any]],
        *,
        max_batch_size: int = 8,
        batch_timeout_s: float = 0.005,
        allowed_batch_sizes: Optional[List[int]] = None,
        in_flight: int = 2,
        max_queue_depth: int = 0,
        overload_retry_after_s: float = 1.0,
        name: str = "default",
        group_key: Optional[Callable[[Dict[str, Any]], Any]] = None,
        collate: Optional[Callable[
            [List[Dict[str, Any]]],
            "tuple[Dict[str, Any], List[Any]]"]] = None,
        finish: Optional[Callable[
            [Dict[str, Any], Any], Dict[str, Any]]] = None,
    ):
        # Batch-assembly hooks (all-or-none, enforced): `group_key`
        # replaces the shape signature — entries with equal keys may
        # share a device batch even when their shapes differ — and
        # `collate` then builds the stacked arrays from the raw inputs
        # (returning per-row metadata that `finish` uses to restore each
        # row's natural shape).  Without hooks, grouping is by exact
        # shape signature and collation is axis-0 concatenation — rows
        # of different shapes can never legally concatenate, which is
        # why cross-shape batching must bring its own collate; a collate
        # without finish would silently drop the per-row metas, so a
        # partial hook set is a construction error, not a latent one.
        hooks = {"group_key": group_key, "collate": collate,
                 "finish": finish}
        given = [k for k, v in hooks.items() if v is not None]
        if given and len(given) != len(hooks):
            missing = sorted(set(hooks) - set(given))
            raise ValueError(
                f"MicroBatcher batch-assembly hooks are all-or-none: "
                f"got {sorted(given)} without {missing}")
        self._predict = predict
        self._group_key = group_key
        self._collate = collate
        self._finish = finish
        self.allowed = sorted(allowed_batch_sizes or [1, 2, 4, 8])
        # A batch larger than the padding table would go to the device
        # unpadded and trigger a fresh XLA compile — the exact thing this
        # class exists to prevent — so the effective cap is the table max.
        self.max_batch_size = min(max_batch_size, self.allowed[-1])
        self.batch_timeout_s = batch_timeout_s
        self._lock = threading.Lock()
        # Pending entries live in per-shape-signature queues: dispatch is
        # O(#groups) per cycle (not a rescan of every pending entry), and
        # each shape group ages against its OWN oldest-entry deadline —
        # under sustained mixed-shape load a minority shape no longer
        # waits an extra full batch_timeout_s per cycle while majority
        # batches reset the clock.
        self._groups: Dict[Any, List[dict]] = {}
        self._next_deadline: Optional[float] = None
        self._flusher = threading.Condition(self._lock)
        self._stopped = False
        self._batch_sizes: Dict[int, int] = {}
        self._requests = 0
        # Bounded admission: > max_queue_depth pending entries shed new
        # submissions with Overloaded (fail-fast 429) instead of
        # queueing unboundedly; 0 = unbounded (library default — the
        # serving entrypoint configures a bound).
        self.max_queue_depth = max(0, int(max_queue_depth))
        self.overload_retry_after_s = overload_retry_after_s
        self._pending_total = 0
        self._shed = 0
        self._expired = 0
        # Per-stage dispatch-cycle accounting (seconds, cumulative) —
        # the first thing VERDICT r4 asked for when capacity came in 5x
        # under the device rate: queue_wait is oldest-entry age at
        # dispatch, the rest split one _process call.  overlap tracks
        # how many runners are actually inside _process concurrently
        # (pipeline depth achieved, not configured).
        self._cycle = {k: 0.0 for k in (
            "queue_wait", "collate", "pad", "predict", "to_host",
            "deliver")}
        self._in_process = 0
        self._max_in_process = 0
        from kubeflow_tpu.runtime.prom import REGISTRY

        # Registered at construction so the series exists on /metrics
        # from the first scrape — an idle or stuck batcher must show a
        # zero-count histogram, not 'no data'.  Effective batch size is
        # the first thing to look at when throughput is below
        # expectation (the round-2 failure mode was mean batch ~1).
        # `name` labels the series per batcher (a process may run one
        # per served model, like the serving-metric model= labels).
        self._metric_name = name
        self._size_hist = REGISTRY.histogram(
            "kft_serving_batch_size",
            "occupied micro-batch size at dispatch, by batcher",
            buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0),
        ).declare(batcher=name)
        self._shed_ctr = REGISTRY.counter(SHED_TOTAL, SHED_HELP)
        self._expired_ctr = REGISTRY.counter(EXPIRED_TOTAL, EXPIRED_HELP)
        self._runners = [
            threading.Thread(target=self._run, daemon=True,
                             name=f"microbatcher-{i}")
            for i in range(max(1, in_flight))
        ]
        for r in self._runners:
            r.start()

    def submit(self, inputs: Dict[str, Any],
               deadline: Optional[float] = None) -> Dict[str, Any]:
        """One logical request of batch-dim 1 ([1, ...] rows).

        Enforced here (loudly, to the offending caller only): each
        entry gets exactly ONE result row back at delivery, so a
        multi-row submission would silently lose every row but the
        first.  Hooked batchers (group_key/collate) validate in their
        own submit (e.g. BucketedLMBatcher).

        ``deadline`` (absolute faults.monotonic() instant): expired-on-
        arrival raises DeadlineExceeded immediately; a queued entry
        whose deadline passes pre-dispatch is failed by the runner
        sweep instead of being dispatched."""
        # Trace context captured on the caller's thread (the transport
        # set it); the runner threads stamp queue-wait/dispatch spans
        # from these perf readings at dispatch time.  None when
        # tracing is off — every span site below is gated on it.
        trace_ctx = tracing.current_ctx()
        entry = {"inputs": inputs,
                 "t": faults.monotonic(), "deadline": deadline,
                 "trace": trace_ctx,
                 "t_perf": time.perf_counter()
                 if trace_ctx is not None else 0.0,
                 "event": threading.Event(), "out": None, "err": None}
        if deadline is not None and faults.monotonic() >= deadline:
            with self._lock:
                self._expired += 1
            self._expired_ctr.inc(batcher=self._metric_name)
            raise DeadlineExceeded(
                f"deadline expired before batcher "
                f"{self._metric_name!r} admission")
        # Signature computed once, outside the lock: np.asarray on
        # list-typed payloads (the REST JSON path) is O(payload).
        if self._group_key is not None:
            sig = self._group_key(inputs)
        else:
            sig = self._shape_sig(inputs)
            for (key, shape, _) in sig:
                if not shape or shape[0] != 1:
                    raise ValueError(
                        f"MicroBatcher.submit takes one row per call: "
                        f"input {key!r} has shape {shape}; submit rows "
                        f"separately")
        with self._lock:
            if self._stopped:
                # After close() the runner threads are gone; an entry
                # appended now would wait forever on its Event.
                raise BatcherClosed(f"batcher {self._metric_name!r} "
                                    "is closed")
            if self.max_queue_depth \
                    and self._pending_total >= self.max_queue_depth:
                # Fail fast: under overload a bounded 429 beats an
                # unbounded queue whose every entry times out.
                self._shed += 1
                self._shed_ctr.inc(batcher=self._metric_name)
                raise Overloaded(
                    f"batcher {self._metric_name!r} queue full "
                    f"({self._pending_total} pending)",
                    retry_after_s=self.overload_retry_after_s)
            self._groups.setdefault(sig, []).append(entry)
            self._pending_total += 1
            self._flusher.notify()
        entry["event"].wait()
        if entry["err"] is not None:
            raise entry["err"]
        return entry["out"]

    def stats(self) -> Dict[str, Any]:
        """Effective-batch-size distribution over dispatched batches,
        plus the mean per-batch cost of each dispatch-cycle stage and
        the achieved pipeline depth (max concurrent _process calls)."""
        cycle, extra = locked_snapshot(
            self._lock, self._cycle,
            lambda: {"hist": dict(sorted(self._batch_sizes.items())),
                     "requests": self._requests,
                     "max_overlap": self._max_in_process,
                     "queue_depth": self._pending_total,
                     "shed": self._shed, "expired": self._expired})
        hist, requests = extra["hist"], extra["requests"]
        max_overlap = extra["max_overlap"]
        batches = sum(hist.values())
        return {
            "requests": requests,
            "batches": batches,
            "batch_size_hist": hist,
            "mean_batch_size": round(requests / batches, 2) if batches
            else 0.0,
            "cycle_profile_ms": {
                k: round(v / batches * 1e3, 3) for k, v in cycle.items()
            } if batches else {},
            "max_pipeline_depth": max_overlap,
            "queue_depth": extra["queue_depth"],
            "shed": extra["shed"],
            "deadline_expired": extra["expired"],
        }

    def close(self) -> None:
        """Refuse new work AND fail queued-undispatched entries with
        BatcherClosed (batches already dispatched complete normally) —
        the same contract as DecodeEngine.close.  Failing instead of
        draining keeps every path consistent: ModelServer.predict
        catches BatcherClosed and retries the replacement batcher (hot
        swap) or falls through to the direct path (drain/stop), so an
        accepted request is never dropped — it just stops waiting on a
        dying queue."""
        with self._lock:
            self._stopped = True
            queued = [e for q in self._groups.values() for e in q]
            self._groups.clear()
            self._pending_total = 0
            self._flusher.notify_all()
        err = BatcherClosed(f"batcher {self._metric_name!r} is closed")
        for e in queued:
            e["err"] = err
            e["event"].set()
        for r in self._runners:
            r.join(timeout=5)

    @staticmethod
    def _shape_sig(inputs: Dict[str, Any]):
        sig = []
        for k, v in sorted(inputs.items()):
            a = np.asarray(v)  # once: O(payload) for list-typed values
            sig.append((k, a.shape, a.dtype.str))
        return tuple(sig)

    def _take_batch_locked(
            self, expired: List[dict]) -> Optional[List[dict]]:
        """Pop the next dispatchable shape group, or None with no group
        ready yet (caller waits until the earliest group deadline).

        Only rows of one shape signature share a device batch (they are
        concatenated on axis 0) — without the grouping, one odd-shaped
        request poisoned the whole batch with a concatenate error.  A
        group becomes dispatchable when it is full or its OLDEST entry
        has aged past batch_timeout_s (or at shutdown, immediately);
        among dispatchable groups the oldest head goes first — full
        groups get no priority over expired ones, or a saturating
        majority shape would starve minority shapes forever (their
        clients block in submit with no timeout).

        Request deadlines are swept here too: entries whose deadline
        (policy clock) has passed move into ``expired`` — the caller
        fails them with DeadlineExceeded outside the lock — and pending
        request deadlines join the wakeup computation so an expiring
        entry is failed promptly even when no batch deadline is near.
        """
        # ONE skewable policy clock for both request deadlines and
        # batch-window aging: a seeded skew must age queued entries
        # exactly like it expires deadlines, or the two sweeps drift.
        now = faults.monotonic()
        pnow = now
        best_sig, best_t = None, None
        self._next_deadline = None

        def note_wake(at: float) -> None:
            if self._next_deadline is None or at < self._next_deadline:
                self._next_deadline = at

        for sig in list(self._groups):
            q = self._groups[sig]
            keep = []
            for e in q:
                d = e["deadline"]
                if d is not None and d <= pnow:
                    expired.append(e)
                    continue
                keep.append(e)
                if d is not None:
                    note_wake(d)
            if len(keep) != len(q):
                self._pending_total -= len(q) - len(keep)
                if not keep:
                    del self._groups[sig]
                    continue
                self._groups[sig] = q = keep
            deadline = q[0]["t"] + self.batch_timeout_s
            if (len(q) >= self.max_batch_size or deadline <= now
                    or self._stopped):
                if best_t is None or q[0]["t"] < best_t:
                    best_sig, best_t = sig, q[0]["t"]
            else:
                note_wake(deadline)
        if best_sig is None:
            return None
        q = self._groups[best_sig]
        batch, rest = q[:self.max_batch_size], q[self.max_batch_size:]
        if rest:
            self._groups[best_sig] = rest
        else:
            del self._groups[best_sig]
        self._pending_total -= len(batch)
        return batch

    def _record_queue_wait(self, entries: List[dict],
                           status: str = "ok") -> None:
        """The one batcher.queue_wait stamping site (span names are
        unique per module — span-discipline): dispatched and
        deadline-expired entries both land here."""
        if not any(e["trace"] is not None for e in entries):
            return
        now_perf = time.perf_counter()
        for e in entries:
            if e["trace"] is not None:
                tracing.record_span(
                    "batcher.queue_wait", e["trace"], e["t_perf"],
                    now_perf, status=status,
                    attrs={"batcher": self._metric_name})

    def _run(self) -> None:
        while True:
            expired: List[dict] = []
            with self._lock:
                batch = None
                while batch is None and not expired:
                    if not self._groups:
                        if self._stopped:
                            return
                        self._flusher.wait()
                        continue
                    batch = self._take_batch_locked(expired)
                    if batch is None and not expired:
                        # Sleep only until the earliest group's own
                        # deadline — each shape ages independently —
                        # or the earliest request deadline, whichever
                        # comes first.
                        self._flusher.wait(
                            timeout=None if self._next_deadline is None
                            else max(0.0, self._next_deadline
                                     - faults.monotonic()))
                if expired:
                    self._expired += len(expired)
                if batch is not None:
                    # stats() and the scrapeable histogram record the
                    # same quantity at the same site.
                    self._batch_sizes[len(batch)] = \
                        self._batch_sizes.get(len(batch), 0) + 1
                    self._requests += len(batch)
                    self._size_hist.observe(
                        float(len(batch)), batcher=self._metric_name)
                    self._cycle["queue_wait"] += (
                        faults.monotonic() - batch[0]["t"])
                    self._in_process += 1
                    self._max_in_process = max(self._max_in_process,
                                               self._in_process)
            if expired:
                # Failed OUTSIDE the lock: waking a waiter is not queue
                # work, and the swept entries are no longer reachable
                # from the groups.
                self._expired_ctr.inc(len(expired),
                                      batcher=self._metric_name)
                err = DeadlineExceeded(
                    f"deadline expired in batcher "
                    f"{self._metric_name!r} queue")
                self._record_queue_wait(expired,
                                        status="deadline_expired")
                for e in expired:
                    e["err"] = err
                    e["event"].set()
            if batch is None:
                continue
            self._record_queue_wait(batch)
            try:
                self._process(batch)
            finally:
                with self._lock:
                    self._in_process -= 1

    def _pad_size(self, n: int) -> int:
        for size in self.allowed:
            if n <= size:
                return size
        return self.allowed[-1]

    def _process(self, batch: List[dict]) -> None:
        try:
            # Chaos hook: a scripted stall here simulates a wedged
            # dispatch (queue builds, deadlines expire, admission
            # sheds); a scripted raise takes the same propagate-to-
            # waiters path as a device failure.  See
            # kubeflow_tpu/testing/faults.py.
            faults.fire("batcher.dispatch")
            # Stage timings accumulate LOCALLY and merge into
            # self._cycle under the queue lock at the end — _process
            # runs on dispatch threads while stats()/the /metrics
            # scrape snapshot the counters, and an unlocked float +=
            # against that read shows torn cycle profiles (impossible
            # occupancy was the observed symptom).
            cyc = {k: 0.0 for k in self._cycle}
            t0 = time.perf_counter()
            metas: Optional[List[Any]] = None
            n = len(batch)
            size = self._pad_size(n)
            if self._collate is not None:
                stacked, metas = self._collate(
                    [e["inputs"] for e in batch])
                t1 = time.perf_counter()
                cyc["collate"] += t1 - t0
                if size > n:
                    stacked = {
                        k: np.concatenate(
                            [v] + [v[:1]] * (size - n), axis=0
                        ) for k, v in stacked.items()
                    }
                t2 = time.perf_counter()
                cyc["pad"] += t2 - t1
            else:
                # One preallocated buffer per key, filled row-by-row and
                # tail-padded in place: the earlier concatenate-of-N
                # (plus a second concatenate for padding) built the
                # batch from dozens of small Python-level array ops —
                # measured 38 ms collate + 62 ms pad per batch-64 cycle
                # under a 192-client GIL storm, pure assembly overhead
                # on the serving hot path.
                stacked = {}
                pad_s = 0.0
                for k in batch[0]["inputs"].keys():
                    first = np.asarray(batch[0]["inputs"][k])
                    out = np.empty((size,) + first.shape[1:],
                                   first.dtype)
                    out[0] = first[0]
                    for i, e in enumerate(batch[1:], 1):
                        out[i] = np.asarray(e["inputs"][k])[0]
                    if size > n:
                        tp = time.perf_counter()
                        out[n:] = out[0]
                        pad_s += time.perf_counter() - tp
                    stacked[k] = out
                t2 = time.perf_counter()
                cyc["collate"] += t2 - t0 - pad_s
                cyc["pad"] += pad_s
            outputs = self._predict(stacked)
            t3 = time.perf_counter()
            cyc["predict"] += t3 - t2
            # One device->host transfer per output key, then row views.
            host = {k: np.asarray(v) for k, v in outputs.items()}
            t4 = time.perf_counter()
            cyc["to_host"] += t4 - t3
            for i, e in enumerate(batch):
                row = {k: v[i:i + 1] for k, v in host.items()}
                if metas is not None and self._finish is not None:
                    row = self._finish(row, metas[i])
                e["out"] = row
                e["event"].set()
            t5 = time.perf_counter()
            cyc["deliver"] += t5 - t4
            with self._lock:
                for k, v in cyc.items():
                    self._cycle[k] += v
            # Batch-assembly span per traced entry: the whole dispatch
            # cycle (collate -> pad -> predict -> deliver) each row
            # rode, annotated with the occupied/padded batch shape.
            for e in batch:
                if e["trace"] is not None:
                    tracing.record_span(
                        "batcher.dispatch", e["trace"], t0, t5,
                        attrs={"batcher": self._metric_name,
                               "batch_size": n, "padded_to": size})
        except Exception as exc:
            # Propagate to all waiters still pending.  Rows already
            # delivered (event set) keep their results — a `finish`
            # hook raising on row i must not retroactively poison rows
            # 0..i-1, whose waiters may not have woken yet.
            for e in batch:
                if not e["event"].is_set():
                    e["err"] = exc
                    e["event"].set()


class BucketedLMBatcher:
    """Mixed-length LM decode batching: one queue, pad at dispatch.

    The MicroBatcher shares a device batch only among requests of one
    shape signature — correct (concatenation needs it), but it means
    mixed-length prompts NEVER coalesce and concurrent clients fall
    back to batch-1 throughput.  Left-padding fixes that:
    models/generate.py masks the pad keys and offsets rope so a padded
    row with its real length in ``prompt_len`` decodes exactly as it
    would alone, which makes ANY two prompts batch-compatible.

    So all requests share ONE queue, and padding happens at DISPATCH:
    the batch pads to the smallest bucket covering its longest member
    (bucket promotion).  Padding each prompt to its own bucket at
    submit time — the obvious design — re-splits the clients across
    per-bucket programs: measured on-chip, an 8-client mixed-length
    workload ran at mean batch 2.67 and ~5x below the uniform-length
    req/s, because every dispatch costs a full device round trip no
    matter how few rows it carries.  Promotion buys full batches at a
    padding cost paid on prefill FLOPs AND on every decode step:
    generate() sizes the KV cache from the padded width, so each step
    of a promoted row attends over the batch bucket's key span, not
    its own.  The bound is the largest bucket a co-batched prompt
    occupies (not the bucket spacing) — a losing trade only when the
    length distribution is wide and batched decode is compute-bound,
    and a winning one whenever round trips or batch count dominate,
    as in interactive decode (measured ~6x at the bench config).

    Buckets still bound the program count: one jitted generate program
    per (bucket, allowed batch size) that actually occurs, compiled on
    first use.  A uniform-length workload pads to its own bucket and
    behaves exactly as before.

    Promotion is BOUNDED (VERDICT r4 item 7): unbounded promotion is a
    cliff on a wide length spread — a 128-token prompt co-batched with
    a 4096-token one pays the 4096 bucket's KV span on every decode
    step.
    ``max_promotion_factor`` partitions the buckets into bands whose
    largest/smallest ratio stays <= the factor; only requests in the
    same band share a queue, so a request's worst-case padded bucket is
    bounded at factor x its own.  The trade is explicit: more bands =
    tighter per-request KV bound but fewer co-batching partners (a
    uniform workload is unaffected; a maximally-wide one degrades
    toward per-band batching).  ``None`` restores the single queue.
    """

    def __init__(
        self,
        predict: Callable[[Dict[str, Any]], Dict[str, Any]],
        *,
        buckets: Optional[List[int]] = None,
        pad_token: int = 0,
        max_promotion_factor: Optional[float] = 4.0,
        **batcher_kwargs,
    ):
        self.buckets = sorted(buckets or [32, 64, 128, 256, 512, 1024])
        self.pad_token = pad_token
        # Band id per bucket: a new band starts when the bucket exceeds
        # factor x the band's smallest member.
        self._band: Dict[int, int] = {}
        if max_promotion_factor is None:
            self._band = {b: 0 for b in self.buckets}
        else:
            band, band_min = -1, None
            for b in self.buckets:
                if band_min is None or b > band_min * max_promotion_factor:
                    band, band_min = band + 1, b
                self._band[b] = band
        self._inner = MicroBatcher(
            predict,
            group_key=lambda inputs: (
                "lm", self._band[self.bucket_for(
                    np.asarray(inputs["tokens"]).shape[-1])]),
            collate=self._collate,
            finish=self._strip,
            **batcher_kwargs)

    def _collate(self, rows: List[Dict[str, Any]]):
        """Stack raw single-row submissions, left-padding every prompt
        to the batch bucket (smallest bucket >= the longest prompt).

        A per-request ``max_new_tokens`` never reaches the device (the
        generate program bakes the config budget in); it rides the
        per-row meta so _strip trims the surplus on the way out — the
        same budget contract as the DecodeEngine and the direct path,
        minus the decode compute savings only the engine can deliver.
        """
        tokens = [np.asarray(r["tokens"]) for r in rows]
        lengths = [t.shape[1] for t in tokens]
        bucket = self.bucket_for(max(lengths))
        padded = [
            np.concatenate(
                [np.full((1, bucket - n), self.pad_token, t.dtype), t],
                axis=1) if bucket > n else t
            for t, n in zip(tokens, lengths)
        ]
        stacked = {
            "tokens": np.concatenate(padded, axis=0),
            "prompt_len": np.asarray(lengths, np.int32),
        }
        meta = [
            (bucket - n, n,
             max(1, int(np.asarray(r["max_new_tokens"]).reshape(())))
             if r.get("max_new_tokens") is not None else None)
            for r, n in zip(rows, lengths)
        ]
        return stacked, meta

    # Output keys aligned to the FULL padded position axis (pad keys at
    # the left, like the input tokens), stripped per-row on the way
    # out.  Any NEW per-position output a loader grows MUST either be
    # added here (if it spans the padded prompt+completion axis) or be
    # returned pad-free by the loader (e.g. per-NEW-token logprobs of
    # shape [b, new] carry no pad and must NOT be listed) — an
    # unlisted padded key returns silently left-padded.
    _POSITIONAL_KEYS = ("tokens",)

    @classmethod
    def _strip(cls, row: Dict[str, Any], meta) -> Dict[str, Any]:
        pad, prompt_len, new = meta

        def cut(v):
            if pad:
                v = v[:, pad:]
            if new is not None:
                v = v[:, : prompt_len + new]  # per-request budget trim
            return v

        return {
            k: (cut(v) if k in cls._POSITIONAL_KEYS else v)
            for k, v in row.items()
        }

    def bucket_for(self, length: int) -> int:
        for b in self.buckets:
            if length <= b:
                return b
        raise ValueError(
            f"prompt length {length} exceeds largest bucket "
            f"{self.buckets[-1]}")

    def accepts(self, inputs: Dict[str, Any]) -> bool:
        """ModelServer routing hook: prompts beyond the largest bucket
        fall back to the direct predict path (they served fine before
        batching was enabled; enabling it must not break them).  Seeded
        requests also go direct: all rows of a batched generate program
        share one sample stream, so a per-request seed can only be
        honored unbatched (the DecodeEngine, with per-slot keys, keeps
        them batched)."""
        if inputs.get("seed") is not None:
            return False
        tokens = np.asarray(inputs.get("tokens", ()))
        length = tokens.shape[-1] if tokens.ndim else 0
        return bool(length and length <= self.buckets[-1])

    def submit(self, inputs: Dict[str, Any],
               deadline: Optional[float] = None) -> Dict[str, Any]:
        """One logical request: tokens [t] or [1, t] (the MicroBatcher
        hands each entry exactly one result row back, so multi-row
        submissions would silently lose rows — rejected up front)."""
        tokens = np.asarray(inputs["tokens"])
        if tokens.ndim == 1:
            tokens = tokens[None]
        n, length = tokens.shape
        if n != 1:
            raise ValueError(
                f"BucketedLMBatcher.submit takes one prompt per call "
                f"(got batch dim {n}); submit rows separately")
        self.bucket_for(length)  # reject oversize up front, pre-queue
        # Raw tokens go into the shared queue; _collate pads the whole
        # batch to one bucket at dispatch and _strip restores this
        # row's natural shape on the way out.  A per-request
        # max_new_tokens rides along as row meta (never a device
        # input): _strip trims the surplus of the config budget.
        row = {"tokens": tokens}
        if inputs.get("max_new_tokens") is not None:
            row["max_new_tokens"] = inputs["max_new_tokens"]
        return self._inner.submit(row, deadline=deadline)

    def stats(self) -> Dict[str, Any]:
        return self._inner.stats()

    def close(self) -> None:
        self._inner.close()

"""Dependency-injection container.

The reference's Container (pkg/gofr/container/container.go:43-66) is the hub
holding logger, metrics manager, every datasource handle, and inter-service
HTTP clients; construction is conditional on config presence
(container.go:83-150), framework metrics are registered at build time
(container.go:218-250), and ``Health()`` aggregates per-datasource health into
UP/DEGRADED (container/health.go:8-94).

This implementation keeps the same shape and adds the TPU-native member the
reference never had: ``ml`` — the model runtime datasource (engines, mesh,
dynamic batcher) that BASELINE.json's north star demands.
"""

from __future__ import annotations

import asyncio
import inspect
import time
from typing import Any, Protocol, runtime_checkable

from ..config import Config, MapConfig
from ..logging import Logger, new_logger
from ..metrics import Manager

__all__ = ["Container", "HealthStatus", "new_container"]

STATUS_UP = "UP"
STATUS_DOWN = "DOWN"
STATUS_DEGRADED = "DEGRADED"


@runtime_checkable
class HealthChecker(Protocol):
    def health_check(self) -> dict: ...


@runtime_checkable
class Provider(Protocol):
    """Externally-injected datasource contract (reference
    container/datasources.go:278-290): the app injects observability then
    connects."""

    def use_logger(self, logger: Any) -> None: ...
    def use_metrics(self, metrics: Any) -> None: ...
    def use_tracer(self, tracer: Any) -> None: ...
    def connect(self) -> None: ...


class HealthStatus(dict):
    """dict payload for /.well-known/health."""


class Container:
    """Holds every injectable the handler Context exposes."""

    def __init__(self, config: Config | None = None, logger: Logger | None = None) -> None:
        self.config: Config = config or MapConfig()
        self.logger: Logger = logger or new_logger(
            self.config.get("LOG_LEVEL") if self.config else None
        )
        self.metrics_manager: Manager = Manager()
        self.tracer = None  # set by App (gofr_tpu.tracing.Tracer)
        self.app_name = self.config.get_or_default("APP_NAME", "gofr-app")
        self.app_version = self.config.get_or_default("APP_VERSION", "dev")

        # datasources (None until configured/added)
        self.sql = None
        self.redis = None
        self.kv = None
        self.file = None
        self.pubsub = None
        self.cassandra = None
        self.clickhouse = None
        self.mongo = None
        self.dgraph = None
        self.solr = None
        self.opentsdb = None
        self.ml = None  # TPU model runtime — the new first-class datasource

        self.services: dict[str, Any] = {}  # inter-service HTTP clients
        self._extra_datasources: dict[str, Any] = {}
        self.websocket_connections: dict[str, Any] = {}

    # -- registration --------------------------------------------------------
    def register_framework_metrics(self) -> None:
        """Default metric set (reference container.go:218-250) + TPU gauges."""
        m = self.metrics_manager
        m.new_gauge("app_info", "app info: name and version")
        m.set_gauge("app_info", 1, app_name=self.app_name, app_version=self.app_version)
        m.new_histogram("app_http_response", "HTTP response time in seconds")
        m.new_histogram("app_http_service_response", "outbound HTTP call time in seconds")
        m.new_histogram("app_sql_stats", "SQL statement time in seconds")
        m.new_histogram("app_redis_stats", "Redis command time in seconds")
        m.new_counter("app_pubsub_publish_total_count", "messages published")
        m.new_counter("app_pubsub_publish_success_count", "messages published OK")
        m.new_counter("app_pubsub_subscribe_total_count", "messages received")
        m.new_counter("app_pubsub_subscribe_success_count", "messages handled OK")
        # process gauges (reference exposes go runtime stats; here: python/proc)
        m.new_gauge("app_process_memory_bytes", "resident set size")
        m.new_gauge("app_process_threads", "thread count")
        m.new_gauge("app_process_uptime_seconds", "seconds since start")
        # TPU runtime metrics — green-field (BASELINE.json north star)
        m.new_histogram(
            "app_tpu_step_seconds", "on-device execute time per step",
        )
        m.new_gauge("app_tpu_hbm_bytes_in_use", "HBM bytes in use per device")
        m.new_gauge("app_tpu_hbm_bytes_limit", "HBM bytes limit per device")
        m.new_histogram("app_ml_batch_size", "dynamic batcher batch sizes",
                        buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256))
        m.new_histogram("app_ml_queue_seconds", "request time in batch queue")
        m.new_histogram(
            "app_llm_ttft_seconds", "LLM time to first token",
            buckets=(0.01, 0.025, 0.05, 0.1, 0.2, 0.4, 0.8, 1.6, 3.2),
        )
        m.new_histogram(
            "app_llm_tpot_seconds",
            "LLM time per output token after the first (stream cadence)",
            buckets=(0.002, 0.005, 0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0),
        )
        m.new_counter("app_llm_tokens_total", "LLM tokens streamed to consumers")
        m.new_gauge("app_llm_active_slots", "decode slots currently live")
        m.new_histogram("app_llm_queue_seconds",
                        "LLM request wait before slot admission")
        m.new_gauge(
            "app_ml_queue_depth",
            "pending work per serving component (engine dispatch queue, "
            "batcher backlog, llm waiting requests)",
        )
        m.new_histogram(
            "app_llm_spec_accept",
            "per-stream speculative draft acceptance rate [0, 1]",
            buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 1.0),
        )
        m.new_counter("app_ml_prefix_hits_total",
                      "admissions served from a cached shared KV prefix")
        m.new_counter("app_ml_prefix_misses_total",
                      "admissions with no usable cached prefix")
        m.new_counter("app_ml_prefix_evictions_total",
                      "cached prefixes dropped (cap or pool pressure)")
        m.new_counter("app_ml_prefill_tokens_saved_total",
                      "prompt tokens NOT re-prefilled thanks to prefix reuse")
        m.new_counter("app_ml_kv_offload_spills_total",
                      "evicted prefix KV page sets copied device->host")
        m.new_counter("app_ml_kv_offload_restores_total",
                      "offloaded prefix KV page sets copied host->device "
                      "on a cache hit")
        m.new_gauge("app_ml_kv_offload_bytes",
                    "bytes held by the host-RAM KV offload tier")
        m.new_counter("app_ml_kv_transport_ships_total",
                      "prefix KV page sets exported off a prefill replica "
                      "by the disaggregated-serving KV transport")
        m.new_counter("app_ml_kv_transport_lands_total",
                      "transported prefix KV page sets landed in a decode "
                      "replica's host tier")
        m.new_counter("app_ml_kv_transport_bytes",
                      "payload bytes moved by the KV transport "
                      "(successful ships)")
        m.new_counter("app_ml_kv_migrations_total",
                      "live-KV-migration attempts during elastic scale "
                      "events, by outcome (adopted / failed / skipped)")
        m.new_counter("app_ml_sp_prefills_total",
                      "prompts prefilled sequence-parallel across the "
                      "replica's sp mesh (GOFR_ML_SP)")
        m.new_counter("app_ml_sp_fallbacks_total",
                      "sequence-parallel prefills that fell back to the "
                      "single-device full prefill (bit-identical output)")
        m.new_gauge("app_ml_sp_shards",
                    "shard count of the generator's sequence-parallel "
                    "serving plan (the sp mesh axis size)")
        m.new_gauge("app_llm_fleet_size",
                    "live (non-retired) replicas in an elastic pool")
        m.new_counter("app_ml_events_dropped_total",
                      "fleet-event-log ring overwrites: events consumers "
                      "polling /debug/events can no longer read (their "
                      "cursor gapped)")
        m.new_counter("app_ml_journeys_total",
                      "request journeys sealed, by finish reason "
                      "(stop / length / eviction / deadline / shed / "
                      "crashed / cancelled / error)")
        m.new_gauge("app_ml_host_rss_bytes",
                    "current process resident set size (the offload "
                    "tier's footprint lives here)")
        m.new_histogram(
            "app_llm_priority_queue_seconds",
            "LLM request wait before slot admission per priority class",
        )
        m.new_histogram(
            "app_llm_chunk_tokens",
            "decode steps per dispatch picked from the chunk ladder",
            buckets=(1, 2, 4, 8, 16, 32, 64),
        )
        m.new_gauge("app_llm_token_budget",
                    "per-dispatch token budget (decode + chunked prefill)")
        m.new_gauge("app_llm_prefill_share",
                    "budget fraction reserved for chunked prefill "
                    "(SLO-steered)")
        m.new_counter("app_ml_generator_restarts_total",
                      "LLM generator crashes recovered by the serving "
                      "watchdog (decode state rebuilt, queue resumed)")
        m.new_counter("app_llm_deadline_exceeded_total",
                      "LLM requests reaped past their deadline (queued or "
                      "mid-decode)")
        m.new_counter("app_llm_shed_total",
                      "LLM requests shed at admission under overload, per "
                      "priority class")
        m.new_gauge("app_llm_replica_state",
                    "per-replica serving state ordinal (0 serving, "
                    "1 degraded, 2 recovering, 3 dead) — alert on >= 2")
        m.new_gauge("app_llm_replica_outstanding",
                    "requests in flight toward a replica from the fleet "
                    "router (slots + staged margin)")
        m.new_counter("app_llm_replica_routed_total",
                      "requests routed to a replica, by routing reason "
                      "(affinity / least_loaded / failover)")
        m.new_counter("app_llm_replica_failovers_total",
                      "requests re-admitted to a surviving replica after "
                      "their first replica crashed or died")
        m.new_histogram(
            "app_llm_dispatch_phase_seconds",
            "serving dispatch wall time per phase (flight recorder: "
            "queue_pop / decide / assemble / launch / d2h_issue / "
            "device_wait / emit / route / ship / land / other)",
            # phases run from microseconds (a scheduler plan) to a whole
            # device step — the default buckets' 1 ms floor would flatten
            # every host-side phase into one bucket
            buckets=(5e-5, 2e-4, 5e-4, 1e-3, 3e-3, 5e-3, 0.01, 0.02,
                     0.03, 0.05, 0.1, 0.2, 0.5, 1.0),
        )
        m.new_counter("app_llm_tokens_wasted_total",
                      "device-computed tokens that never delivered, by "
                      "reason (spec_rejected / deadline_cancelled / "
                      "crashed / disconnected / failover_recompute / "
                      "restore_fallback / migration_cold) — the goodput "
                      "ledger's waste side")
        m.new_gauge("app_llm_goodput_fraction",
                    "delivered / device-computed tokens per model (the "
                    "goodput ledger's headline ratio)")
        m.new_counter("app_ml_compile_seconds_total",
                      "wall seconds spent compiling jitted programs "
                      "(warmup ladder, prefill buckets, paged ops, "
                      "engine batch buckets, native pjrt executables)")
        m.new_counter("app_ml_compile_cache_hits_total",
                      "program compiles served by the persistent XLA "
                      "compilation cache")
        m.new_gauge("app_ml_programs",
                    "jitted/native programs in a model's compiled "
                    "inventory (the /debug/programs row count)")
        m.new_gauge("app_llm_evictions",
                    "streams truncated because the KV page pool ran dry")
        m.new_gauge("app_llm_prefix_evictions",
                    "idle shared prefixes LRU-dropped under pool pressure")
        m.new_gauge("app_llm_free_pages", "free KV pages in the paged pool")
        self._start_time = time.time()

    def refresh_process_metrics(self) -> None:
        import threading

        m = self.metrics_manager
        try:
            import resource

            rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
            m.set_gauge("app_process_memory_bytes", rss_kb * 1024)
        except Exception:
            pass
        m.set_gauge("app_process_threads", threading.active_count())
        m.set_gauge("app_process_uptime_seconds", time.time() - getattr(self, "_start_time", time.time()))
        if self.ml is not None and hasattr(self.ml, "refresh_device_metrics"):
            try:
                self.ml.refresh_device_metrics(m)
            except Exception:
                pass

    def metrics(self) -> Manager:
        return self.metrics_manager

    def add_datasource(self, name: str, ds: Any) -> None:
        """Inject an external datasource through the Provider protocol
        (reference external_db.go:10-146)."""
        if hasattr(ds, "use_logger"):
            ds.use_logger(self.logger)
        if hasattr(ds, "use_metrics"):
            ds.use_metrics(self.metrics_manager)
        if hasattr(ds, "use_tracer"):
            ds.use_tracer(self.tracer)
        if hasattr(ds, "connect"):
            ds.connect()
        if hasattr(self, name) and getattr(self, name, None) is None:
            setattr(self, name, ds)
        else:
            self._extra_datasources[name] = ds

    def get_datasource(self, name: str) -> Any:
        if hasattr(self, name) and getattr(self, name) is not None:
            return getattr(self, name)
        return self._extra_datasources.get(name)

    def get_http_service(self, name: str) -> Any:
        return self.services.get(name)

    # -- health --------------------------------------------------------------
    def _datasource_items(self) -> list[tuple[str, Any]]:
        names = [
            "sql", "redis", "kv", "file", "pubsub", "cassandra", "clickhouse",
            "mongo", "dgraph", "solr", "opentsdb", "ml",
        ]
        items = [(n, getattr(self, n)) for n in names if getattr(self, n) is not None]
        items.extend(self._extra_datasources.items())
        return items

    async def health(self) -> HealthStatus:
        """Aggregate readiness (reference container/health.go:8-94): overall
        DEGRADED if any datasource or service reports DOWN."""
        out = HealthStatus()
        overall = STATUS_UP
        for name, ds in self._datasource_items():
            checker = getattr(ds, "health_check", None)
            if checker is None:
                continue
            try:
                result = checker()
                if inspect.isawaitable(result):
                    result = await result
            except Exception as exc:
                result = {"status": STATUS_DOWN, "error": str(exc)}
            if not isinstance(result, dict):
                result = {"status": STATUS_UP, "details": result}
            if result.get("status") != STATUS_UP:
                overall = STATUS_DEGRADED
            out[name] = result
        for name, svc in self.services.items():
            checker = getattr(svc, "health_check", None)
            if checker is None:
                continue
            try:
                result = checker()
                if inspect.isawaitable(result):
                    result = await result
            except Exception as exc:
                result = {"status": STATUS_DOWN, "error": str(exc)}
            if result.get("status") != STATUS_UP:
                overall = STATUS_DEGRADED
            out[f"{name}-service"] = result
        out["status"] = overall
        out["name"] = self.app_name
        out["version"] = self.app_version
        return out

    # -- lifecycle -----------------------------------------------------------
    async def close(self) -> None:
        for _, ds in self._datasource_items():
            closer = getattr(ds, "close", None)
            if closer is None:
                continue
            try:
                result = closer()
                if inspect.isawaitable(result):
                    await result
            except Exception as exc:
                self.logger.warnf("error closing datasource: %s", exc)
        for svc in self.services.values():
            closer = getattr(svc, "close", None)
            if closer is not None:
                try:
                    result = closer()
                    if inspect.isawaitable(result):
                        await result
                except Exception:
                    pass


def new_container(config: Config, logger: Logger | None = None) -> Container:
    """Build a container from config, conditionally constructing datasources
    whose configs are present (reference container.go:83-150)."""
    c = Container(config, logger=logger)
    c.register_framework_metrics()

    # SQL: DB_DIALECT in {sqlite, mysql, postgres}; only sqlite is available
    # in-image, others require network drivers and are constructed lazily.
    dialect = config.get("DB_DIALECT")
    if dialect:
        from ..datasource.sql import new_sql

        c.sql = new_sql(config, c.logger, c.metrics_manager)

    if config.get("REDIS_HOST"):
        from ..datasource.redis import Redis

        c.redis = Redis(
            host=config.get_or_default("REDIS_HOST", "localhost"),
            port=int(config.get_or_default("REDIS_PORT", "6379")),
            logger=c.logger,
            metrics=c.metrics_manager,
        )
        try:
            c.redis.connect()
        except Exception as exc:
            c.logger.errorf("could not connect to redis: %s", exc)

    backend = (config.get("PUBSUB_BACKEND") or "").lower()
    if backend:
        from ..datasource.pubsub import new_pubsub

        c.pubsub = new_pubsub(backend, config, c.logger, c.metrics_manager)

    if config.get("KV_STORE_PATH"):
        from ..datasource.kv import BadgerLikeKV

        c.kv = BadgerLikeKV(config.get("KV_STORE_PATH"), logger=c.logger)
        c.kv.connect()

    return c

"""Async LLM serving: the bridge from the request plane to the decode loop.

The reference's request plane is goroutine-per-request (handler.go:77-97);
here many concurrent asyncio handlers feed ONE device-resident
continuous-batching Generator (generate.py) owned by a dedicated thread —
the same thread-confinement pattern as Engine (engine.py): the asyncio
event loop never blocks on device work, and all device dispatch happens
from one thread.

Flow per request: handler awaits ``stream()``/``generate()`` → request goes
on a thread-safe queue → the serving thread admits it into a free slot
(prefill) or parks it until one frees → each burst of sampled tokens is
pushed back to the handler's asyncio queue via ``call_soon_threadsafe``,
one wakeup a dispatch for all streams (``_post``) → slot release on
completion. Metrics: queue wait, TTFT, tokens out.

Paged generators additionally get the framework shared-prefix cache
(prefix_cache.py): admission longest-matches each prompt against a radix
trie of cached prefixes, prefills only the suffix on a hit, and
auto-registers hot prefixes — no caller opt-in; ``register_prefix``
remains as the pinning API on top.

Resilience (errors.py + the watchdog in ``_serve``): every device
dispatch runs supervised — a crash fails only the in-flight slots with a
typed error, rebuilds the generator, and resumes the waiting queue, with
a restart budget against crash-loops; requests carry deadlines
(``deadline_s=``), admission is bounded with lowest-priority-first
shedding (429 + Retry-After), and ``GOFR_ML_FAULT`` arms the chaos hook
that exercises all of it (testutil/faults.py).
"""

from __future__ import annotations

import asyncio
import collections
import os
import queue as _queue
import threading
import time
import traceback
from typing import Any, AsyncIterator

from ..testutil.faults import FaultInjector, fault_snapshot
from ..tracing import current_context
from .capture import sampler_snapshot, traffic_capture
from .errors import (DeadlineExceeded, GeneratorCrashed, Overloaded,
                     ServerClosed)
from ..flight_recorder import (AutoProfiler, DispatchRecorder, phase,
                              autoprof_enabled, crash_vault, event_log,
                              recorder_enabled)
from .generate import PagePoolExhausted, PrefixEvicted
from .goodput import goodput_ledger
from .journey import Journey, journey_log, next_rid
from .journey import seal as seal_journey
from .prefix_cache import PrefixCacheConfig, RadixPrefixCache
from .scheduler import (PRIORITIES, AgingPriorityQueue, SLOController,
                        normalize_priority, retry_after_s)

__all__ = ["LLMServer", "drain_s_from_env"]

_DONE = object()


def _abort_reason(exc: Exception) -> str | None:
    """``ml.finish_reason`` for a request terminated by a typed error —
    the abort-side extension of the generator's stop|length|eviction
    (replica.py adds ``rerouted`` for requests that moved on instead)."""
    if isinstance(exc, DeadlineExceeded):
        return "deadline"
    if isinstance(exc, Overloaded):
        return "shed"
    if isinstance(exc, GeneratorCrashed):
        return "crashed"
    return None


def drain_s_from_env() -> float:
    """``GOFR_ML_DRAIN_S`` as a drain budget in seconds (0 = immediate
    close). The ONE parse behind ``LLMServer.close`` and
    ``ReplicaPool.close`` so the two shutdown paths cannot diverge.
    A malformed value fails loudly (like ``GOFR_ML_REPLICAS``) rather
    than silently becoming the request-dropping immediate close the
    operator set the knob to prevent."""
    raw = os.environ.get("GOFR_ML_DRAIN_S", "").strip()
    if not raw:
        return 0.0
    try:
        drain_s = float(raw)
    except ValueError:
        raise ValueError(
            f"GOFR_ML_DRAIN_S must be seconds, got {raw!r}") from None
    # reject sign typos, nan, and inf too — each silently degrades to an
    # immediate drop (or an unbounded wait) instead of the intended drain
    if not 0.0 <= drain_s < float("inf"):
        raise ValueError(
            f"GOFR_ML_DRAIN_S must be finite and >= 0, got {raw!r}")
    return drain_s


def _deliver(items: list) -> None:
    """On a consumer's loop: what one pass of the serving thread had for
    its streams, each into its own queue, in the order it was posted."""
    for out_q, item in items:
        out_q.put_nowait(item)


class _Finish:
    """Completion marker with the slot's real finish reason — 'stop' (eos),
    'length' (max_new reached), or 'eviction' (page pool dry, answer
    truncated). Streamed last so consumers can report truncation honestly
    instead of a false natural stop (ADVICE r4 #4)."""

    __slots__ = ("reason",)

    def __init__(self, reason: str) -> None:
        self.reason = reason


class _Request:
    __slots__ = ("prompt", "max_new", "out_q", "loop", "enqueued_at", "slot",
                 "first_token_at", "cancelled", "prefix", "trace_ctx",
                 "queue_span", "decode_span", "full_prompt", "cache_seen",
                 "priority", "last_burst_at", "deadline_at", "deadline_hit",
                 "n_tokens", "rid", "journey", "journey_owned")

    def __init__(self, prompt, max_new, out_q, loop, prefix=None,
                 trace_ctx=None, queue_span=None, priority: int = 1,
                 deadline_s: float = 0.0, rid: str | None = None,
                 journey=None, journey_owned: bool = False) -> None:
        self.prompt = prompt
        self.max_new = max_new
        self.out_q = out_q
        self.loop = loop
        self.priority = priority  # class index into scheduler.PRIORITIES
        self.enqueued_at = time.perf_counter()
        # absolute TTL: past it the request is reaped wherever it sits —
        # queued (never prefilled) or mid-decode (slot cancelled)
        self.deadline_at = (self.enqueued_at + deadline_s
                            if deadline_s > 0 else None)
        self.deadline_hit = False
        try:  # queued-token accounting for the shedding bound
            self.n_tokens = len(prompt)
        except TypeError:
            self.n_tokens = 0
        self.last_burst_at = None  # SLO controller's live-cadence anchor
        self.slot = None
        self.first_token_at = None
        self.cancelled = False  # consumer went away: stop decoding the slot
        self.prefix = prefix    # registered shared-prefix id (paged mode)
        self.trace_ctx = trace_ctx    # request span ctx from enqueue time
        self.queue_span = queue_span  # ml.queue, ends at slot admission
        self.decode_span = None       # ml.decode, admission -> finish
        self.full_prompt = None  # original ids when the framework prefix
        self.cache_seen = False  # cache split the prompt (eviction fallback)
        self.rid = rid           # process-unique request id (journey key)
        self.journey = journey   # request-journey timeline (None = off)
        self.journey_owned = journey_owned  # this server seals it; a pool
        # -owned journey survives core rejects so failover keeps ONE record

    def finish_spans(self, status: str = "OK", message: str = "") -> None:
        """End whichever phase spans are still open (admission rejects and
        close-flush paths may finish a request that never decoded)."""
        for span in (self.queue_span, self.decode_span):
            if span is not None and span.end_time is None:
                if status != "OK":
                    span.set_status(status, message)
                span.end()


class LLMServer:
    """Owns a Generator on a serving thread; async API for handlers.

    Register through MLDatasource (``ml.register_llm``) so health/metrics
    flow like every other datasource, or standalone in tests.
    """

    def __init__(self, generator, *, name: str = "llm", logger=None,
                 metrics=None, tracer=None, idle_wait_s: float = 0.002,
                 admit_window_s: float = 0.004, prefix_cache=None,
                 max_restarts: int | None = None,
                 restart_window_s: float | None = None,
                 default_deadline_s: float | None = None,
                 max_queue: int | None = None,
                 max_queued_tokens: int | None = None,
                 fault: Any = None) -> None:
        self.gen = generator
        self.name = name
        self._logger = logger
        self._metrics = metrics
        self._tracer = tracer
        # Framework shared-prefix cache (prefix_cache.py): ON by default
        # whenever the generator is paged — submit longest-matches the
        # prompt against cached prefixes, prefills only the suffix, and
        # hot prefixes auto-register with no caller opt-in. Pass
        # ``prefix_cache=False`` to disable, or a PrefixCacheConfig to
        # tune the promotion/eviction policy.
        self.prefix_cache = None
        if getattr(generator, "page_size", 0) and prefix_cache is not False:
            cfg = (prefix_cache
                   if isinstance(prefix_cache, PrefixCacheConfig) else None)
            self.prefix_cache = RadixPrefixCache(
                generator, cfg, metrics=metrics, model=name)
        self._ended_at = -1  # gen.settled when a stream was last ended
        self._idle_wait = idle_wait_s
        self._idle_backoff = idle_wait_s
        self._admit_window = admit_window_s
        self._requests: _queue.Queue[_Request | None] = _queue.Queue()
        self._setup_q: _queue.Queue = _queue.Queue()  # run-on-serving-thread
        # priority admission: weighted ready queues with aging (strict FIFO
        # within a class, starvation-free across classes)
        self._waiting = AgingPriorityQueue(
            aging_s=float(os.environ.get("GOFR_ML_PRIORITY_AGING_S", "2.0")))
        # SLO steering: when the generator runs the token-budget scheduler,
        # close the loop from observed TTFT/TPOT percentiles to the
        # prefill/decode budget split (targets from GOFR_ML_TTFT_TARGET_MS
        # / GOFR_ML_TPOT_TARGET_MS). Serving-thread-only state.
        self._controller = (
            SLOController.from_env(generator.scheduler)
            if getattr(generator, "scheduler", None) is not None else None)
        self._steered_dispatches = -1  # ladder dispatches recorded so far
        # offload-counter watermarks: the generator counts spills/restores
        # monotonically; the gauge pass publishes the deltas as Prometheus
        # counters so the generator itself stays metrics-free (same
        # pattern for the adaptive-speculation disable counter)
        self._kv_spills_seen = 0
        self._kv_restores_seen = 0
        self._spec_disables_seen = 0
        # sequence-parallel serving watermarks (GOFR_ML_SP): prefill and
        # fallback counters publish as deltas like the offload pair
        self._sp_prefills_seen = 0
        self._sp_fallbacks_seen = 0
        self._active: dict[int, _Request] = {}
        self._closed = False
        self.served = 0
        # -- resilience layer -------------------------------------------------
        # watchdog restart budget: at most GOFR_ML_MAX_RESTARTS generator
        # recoveries per GOFR_ML_RESTART_WINDOW_S sliding window; past it
        # the server goes ``dead`` instead of crash-looping
        self._max_restarts = (int(os.environ.get("GOFR_ML_MAX_RESTARTS", "3"))
                              if max_restarts is None else int(max_restarts))
        self._restart_window = (
            float(os.environ.get("GOFR_ML_RESTART_WINDOW_S", "60"))
            if restart_window_s is None else float(restart_window_s))
        # per-request TTL default (0 = off); deadline_s= on the request
        # overrides it per call
        self._default_deadline = (
            float(os.environ.get("GOFR_ML_DEFAULT_DEADLINE_S", "0"))
            if default_deadline_s is None else float(default_deadline_s))
        # admission bounds (0 = unbounded): requests and/or queued prompt
        # tokens; past either, lowest-priority-first shedding with a 429
        self._max_queue = (int(os.environ.get("GOFR_ML_MAX_QUEUE", "0"))
                           if max_queue is None else int(max_queue))
        self._max_queued_tokens = (
            int(os.environ.get("GOFR_ML_MAX_QUEUED_TOKENS", "0"))
            if max_queued_tokens is None else int(max_queued_tokens))
        self._state = "serving"  # serving | recovering | degraded | dead
        self._draining = False  # close(drain_s=): admission stopped
        # the restart deques are written by the serving thread mid-crash
        # and read by health/debug endpoints on the event-loop thread —
        # exactly when they matter most; the lock keeps a concurrent
        # append from turning a health scrape into a RuntimeError
        self._restart_lock = threading.Lock()
        self._restart_times: collections.deque[float] = collections.deque()
        self._restart_history: collections.deque[dict] = collections.deque(
            maxlen=16)
        self._restarts_total = 0
        self._deadline_expired = 0
        self._shed_counts = dict.fromkeys(PRIORITIES, 0)
        # admission timestamps feed the Retry-After estimate (observed
        # queue drain rate); serving-thread-only like the rest
        self._admit_times: collections.deque[float] = collections.deque(
            maxlen=64)
        self.closed_cleanly = True  # False once close() leaks the thread
        # parse the drain budget NOW so a malformed GOFR_ML_DRAIN_S is a
        # loud startup error, not a silent drop-everything at SIGTERM
        self._drain_default = drain_s_from_env()
        # flight recorder (flight_recorder.py): per-dispatch stall
        # attribution (the generator stamps decide/dispatch/device_wait/
        # emit through the shared recorder; this thread stamps queue_pop/
        # assemble and commits once per dispatch), the fleet event log,
        # and the crash vault the watchdog snapshots bundles into
        self.recorder = (DispatchRecorder(model=name, metrics=metrics)
                         if recorder_enabled() else None)
        generator.recorder = self.recorder
        # what the serving thread has for its consumers, by loop, until
        # ``_flush_posts`` hands it over: see ``_post``
        self._outbox: dict = {}
        generator.after_bursts = self._flush_posts
        # anomaly-triggered auto-profiler (flight_recorder.py): observes
        # every committed dispatch record through recorder.observer and
        # captures a bounded jax.profiler trace when step time or a phase
        # share regresses past its baseline. GOFR_ML_AUTOPROF=0 disables
        # (observer stays None — zero per-commit work, like the recorder)
        self.autoprof = None
        if self.recorder is not None and autoprof_enabled():
            self.autoprof = AutoProfiler(model=name)
            self.recorder.observer = self.autoprof.observe
        # goodput ledger (ml/goodput.py): classify every device-computed
        # token at the point its fate is decided. The generator, prefix
        # cache, and host KV tier get model-bound handles so their
        # classification points stay one-liners; GOFR_ML_GOODPUT=0
        # disables via the same is-not-None contract
        self._goodput = goodput_ledger()
        # what this server's DELIVERED tokens bill as. A shadow-canary
        # core (replica.py) flips this to "canary": its output never
        # reaches a client, so every token it computes is waste by
        # definition — and the flip is the ONE switch that keeps the
        # ledger balanced without touching any classification site
        self.delivery_reason = "delivered"
        handle = (self._goodput.handle(name)
                  if self._goodput is not None else None)
        generator.goodput = handle
        if self.prefix_cache is not None:
            self.prefix_cache.goodput = handle
        if getattr(generator, "host_kv", None) is not None:
            generator.host_kv.goodput = handle
        # request journeys (journey.py): per-request lifecycle timelines,
        # tail-sampled at /debug/requests. GOFR_ML_JOURNEY=0 disables —
        # every instrumented site guards on is-not-None like the recorder
        self._journeys = journey_log()
        self._events = event_log()
        self._crashes = crash_vault()
        # traffic capture (ml/capture.py): record every request THIS
        # front admits (a pool core sees rid= from its front and skips —
        # the front already captured it) for deterministic replay.
        # GOFR_ML_CAPTURE unset/0 constructs no capture machinery at all
        # — the stream path guards on is-not-None like every recorder
        self._capture = traffic_capture()
        self._cap_sampler = None
        if self._capture is not None:
            self._cap_sampler = sampler_snapshot(generator)
            self._capture.note_model(
                name, kind="server", slots=generator.batch_slots,
                page_size=getattr(generator, "page_size", 0))
        # a ReplicaPool front installs a fleet-shape provider here so a
        # core's crash bundle snapshots the CURRENT membership (elastic
        # fleets change shape at runtime); standalone servers leave None
        self.fleet_info = None
        if getattr(generator, "host_kv", None) is not None:
            # label the host tier's spill/restore events with this model
            generator.host_kv.model = name
        # chaos hook (GOFR_ML_FAULT / testutil.faults): installed on the
        # generator's dispatch points + the emit path; None = zero overhead
        self._fault = FaultInjector.from_env() if fault is None else (
            fault or None)
        if self._fault is not None:
            generator.fault = self._fault
            if logger is not None:
                try:
                    logger.warnf("llm %s: fault injection ARMED (%s)",
                                 name, os.environ.get("GOFR_ML_FAULT", ""))
                except Exception:
                    pass
        self._thread = threading.Thread(
            target=self._serve_loop, daemon=True, name=f"gofr-llm-{name}"
        )
        self._thread.start()

    # -- serving thread -------------------------------------------------------
    def _serve_loop(self) -> None:
        try:
            self._serve()
        finally:
            self._flush_on_close()

    def _serve(self) -> None:
        if self.recorder is not None:
            self.recorder.reset()  # the first pass starts here
        while not self._closed:
            # WATCHDOG: every device dispatch this pass makes (step, drain,
            # batched/chunked/suffix prefill, offload spill/restore) plus
            # the emit callbacks runs supervised. An unexpected exception
            # fails only the in-flight requests bound to live slots,
            # rebuilds the generator's decode state, and resumes draining
            # the untouched waiting queue — until the restart budget is
            # spent and the server goes dead instead of crash-looping.
            rec = self.recorder
            try:
                self._run_setup_tasks()
                self._reap_cancelled()
                # assemble: admission-wave work — validation, radix
                # split, batch build, and the prefill dispatches. The
                # device_wait/emit of _admit_waiting's internal
                # gen.drain() nest inside and come off its self time
                with phase(rec, "assemble"):
                    self._admit_waiting()
                if self._closed:
                    return
                if self.gen.n_live:
                    self.gen.step()
                    self._take_arrivals()
                    self._finish_dead_slots()
                    self._steer()
                    if rec is not None:
                        # one record per device dispatch: whatever this
                        # pass didn't stamp lands honestly in "other"
                        rec.commit()
                    continue
                self.gen.drain()
                self._finish_dead_slots()
                if rec is not None and rec.pending_device_work:
                    # tail flush of the last in-flight chunk: its
                    # device_wait/emit belong to a record, not the void
                    # (an idle pass's empty-queue glance does NOT commit —
                    # junk records would flush real dispatches from the
                    # ring at idle-poll frequency)
                    rec.commit()
            except Exception as exc:
                # a crash racing close() skips recovery: the finally-flush
                # wakes every consumer with the typed closed error anyway
                if self._closed or not self._recover_or_die(exc):
                    return
                if rec is not None:
                    # the crashed pass and the whole recovery (pool
                    # rebuild + re-warmup, possibly seconds) must not be
                    # billed to the next dispatch's record — one such
                    # record would dominate the rolling window and report
                    # a phantom "other" stall
                    rec.reset()
                continue
            # queue pop: blocking for the arrival that wakes us plus the
            # burst-collection window before the admission wave
            with phase(rec, "queue_pop"):
                arrived = self._await_burst()
            if arrived is None:
                return
            if not arrived and rec is not None:
                # pure idle: nothing arrived, no dispatch to charge
                # the wait to — drop the pass from the attribution
                rec.reset()

    def _await_burst(self) -> bool | None:
        """Block briefly for the next request, then collect the rest of
        its burst. False when nothing arrived, None at the close."""
        try:  # idle: backing off toward 50 ms so an idle server doesn't
            # spin at hundreds of wakeups/s (admission latency cost is at
            # most one backoff interval, well under a prefill)
            req = self._requests.get(timeout=self._idle_backoff)
        except _queue.Empty:
            # floor keeps idle_wait_s=0 from spinning; ceiling never
            # clamps below a caller's own (larger) configured wait
            self._idle_backoff = min(
                max(self._idle_backoff * 2, 0.001),
                max(0.05, self._idle_wait),
            )
            return False
        self._idle_backoff = self._idle_wait
        if req is None:
            return None
        self._enqueue_waiting(req)
        # collect the rest of the burst before admitting: concurrent
        # clients arrive over a few ms, and one wave (its prefills
        # back to back + one mini-chunk) gives every stream the first
        # wave's TTFT instead of the second's. The burst is over when
        # nothing has come for the admit window, when every free slot has
        # a taker, or at the idle backoff's ceiling: a fixed window from
        # the first arrival cut a burst of 128 at 41 to 66, as the
        # transport happened to deliver
        free = sum(not s.live for s in self.gen.slots)
        deadline = time.perf_counter() + max(0.05, self._idle_wait)
        while len(self._waiting) < free:
            remaining = min(self._admit_window,
                            deadline - time.perf_counter())
            if remaining <= 0:
                return True
            try:
                more = self._requests.get(timeout=remaining)
            except _queue.Empty:
                return True
            if more is None:
                self._closed = True
                return None
            self._enqueue_waiting(more)
        return True

    def _run_setup_tasks(self) -> None:
        """Drain device-touching setup work (e.g. register_prefix) onto
        the serving thread — the one thread allowed to dispatch."""
        while True:
            try:
                work = self._setup_q.get_nowait()
            except _queue.Empty:
                return
            work()

    def _run_on_serving(self, work, timeout_s: float, what: str):
        """Run ``work`` on the SERVING thread (the one thread allowed to
        dispatch device programs) and relay its result/exception here.
        The one mechanism behind register_prefix/drop_prefix and the KV
        transport's export/import — it may wait one idle-poll interval
        (<= 50 ms) plus whatever device work (and first-use compiles)
        ``work`` itself dispatches."""
        done = threading.Event()
        box: dict = {}

        def wrapped() -> None:
            try:
                box["out"] = work()
            except Exception as exc:  # relayed to the caller below
                box["err"] = exc
            finally:
                done.set()

        if self._closed:
            raise self._closed_error()
        self._setup_q.put(wrapped)
        deadline = time.monotonic() + timeout_s
        while not done.wait(0.1):
            if self._closed:  # serving thread gone: fail fast, not 120 s
                raise self._closed_error()
            if time.monotonic() > deadline:
                raise DeadlineExceeded(
                    f"{what} timed out after {timeout_s:g}s")
        if "err" in box:
            raise box["err"]
        return box.get("out")

    def register_prefix(self, prefix_ids, timeout_s: float = 120.0) -> int:
        """PIN a shared prefix (system prompt): registered through the
        framework prefix cache when one is active, so the registration is
        evicted under pool pressure only as a last resort (after every
        auto-promoted candidate) and never while borrowed. Returns the id
        to pass as ``prefix=`` to stream/generate — though with the cache
        on, plain submissions longest-match automatically and the explicit
        id is only needed to guarantee residency. Thread-safe: the prefill
        runs on the serving thread (it may wait one idle-poll interval,
        <= 50 ms, plus the prefix compile on first use)."""
        def work() -> int:
            if self.prefix_cache is not None:
                return self.prefix_cache.pin(prefix_ids)
            return self.gen.register_prefix(prefix_ids)

        return self._run_on_serving(work, timeout_s, "register_prefix")

    def drop_prefix(self, pid: int, timeout_s: float = 30.0) -> None:
        """Release a registered prefix's pages (raises if slots still
        borrow them). Runs on the serving thread like register_prefix."""
        def work() -> None:
            if self.prefix_cache is not None:
                self.prefix_cache.drop(pid)
            else:
                self.gen.drop_prefix(pid)

        self._run_on_serving(work, timeout_s, "drop_prefix")

    # -- KV transport (ml/kv_transport.py): disaggregated prefill/decode -----
    def export_prefix_kv(self, prefix_ids,
                         timeout_s: float = 120.0) -> tuple | None:
        """PREFILL-replica half of a KV-transport ship: compute the
        prefix's KV pages (``register_prefix`` — chunked-ladder segments
        for prefixes longer than any prefill bucket), spill them through
        the host tier (``drop_prefix(spill=True)``), and take the settled
        numpy slabs out of the store for the transport. Returns ``(key,
        arrays, meta)`` or ``None`` when this core cannot ship (dense
        cache, host tier off, nothing page-whole to share, pool too
        tight, entry over the host budget) — the transport then falls
        back to a full prefill on the decode replica. Runs on the serving
        thread; the ``ship`` fault point and flight-recorder phase fire
        there."""
        def work() -> tuple | None:
            gen = self.gen
            if not getattr(gen, "page_size", 0) \
                    or getattr(gen, "host_kv", None) is None:
                return None
            ids = tuple(int(t) for t in prefix_ids)
            with phase(self.recorder, "ship"):
                try:
                    pid = gen.register_prefix(ids)
                except (PagePoolExhausted, ValueError):
                    return None  # pool too tight / shape-impossible
                try:
                    spilled = gen.drop_prefix(pid, spill=True)
                except Exception:
                    # the spill path failed mid-handoff (e.g. an armed
                    # ``spill`` fault): the registration is still idle
                    # device-side — discard it so its pages return to the
                    # pool instead of parking until a reclaim pass
                    if gen.has_prefix(pid):
                        gen.drop_prefix(pid)
                    raise
                entry = gen.host_kv.take(ids) if spilled else None
                if self._fault is not None:
                    self._fault("ship")  # chaos: pages lost mid-handoff
            if entry is None:
                return None
            return ids, entry[0], entry[1]

        return self._run_on_serving(work, timeout_s, "export_prefix_kv")

    def export_resident_prefix(self, prefix_ids, pid: int | None = None,
                               timeout_s: float = 30.0) -> tuple | None:
        """MIGRATION-side export (elastic scale-down, ml/replica.py):
        hand over KV this core ALREADY HOLDS — a registered radix-cache
        prefix (spilled device→host with ``drop_prefix(spill=True)``,
        then taken out of the store) or an already-offloaded host-tier
        entry — WITHOUT recomputing anything, unlike ``export_prefix_kv``
        (whose job is to compute fresh KV on a prefill replica). Returns
        ``(key, arrays, meta)`` or ``None`` when there is nothing
        migratable under this key (borrowed registration, spill rejected
        by the host budget, entry already gone) — the caller counts it
        and moves on; the worst case is a cold cache on the survivor,
        never a wrong token. Runs on the serving thread; the ``migrate``
        fault point fires there, so ``GOFR_ML_FAULT_REPLICA`` narrows
        chaos to one replica's exports."""
        def work() -> tuple | None:
            gen = self.gen
            if not getattr(gen, "page_size", 0) \
                    or getattr(gen, "host_kv", None) is None:
                return None
            ids = tuple(int(t) for t in prefix_ids)
            with phase(self.recorder, "ship"):
                if self._fault is not None:
                    self._fault("migrate")  # chaos: export lost mid-handoff
                if pid is not None and gen.has_prefix(pid):
                    info = gen._prefixes[pid]
                    if info["refs"] > 0:
                        return None  # borrowed: drains with its slots
                    key = tuple(int(t) for t in info["ids_full"])
                    spilled = gen.drop_prefix(pid, spill=True)
                    if self.prefix_cache is not None:
                        # registered → offloaded in the trie bookkeeping
                        # (cleared again below once the entry leaves)
                        self.prefix_cache.invalidate(pid)
                    if not spilled:
                        return None  # host budget rejected it: discarded
                    ids = key
                entry = gen.host_kv.take(ids)
                if self.prefix_cache is not None:
                    self.prefix_cache.forget_offloaded(ids)
            if entry is None:
                return None
            return ids, entry[0], entry[1]

        return self._run_on_serving(work, timeout_s, "export_resident_prefix")

    def import_prefix_kv(self, key, arrays: dict, meta: dict,
                         timeout_s: float = 30.0) -> bool:
        """DECODE-replica half of a KV-transport ship: land the settled
        slabs in this core's host tier and seed the radix trie with the
        OFFLOADED node, so the next prompt longest-matching ``key``
        restores the shipped pages at admission — suffix-only prefill,
        restore debt charged to this core's token-budget scheduler
        exactly like a local offload hit. False when the entry cannot
        land (host tier off or the entry exceeds its budget). Runs on the
        serving thread; the ``land`` fault point and flight-recorder
        phase fire there."""
        def work() -> bool:
            gen = self.gen
            if getattr(gen, "host_kv", None) is None:
                return False
            ids = tuple(int(t) for t in key)
            with phase(self.recorder, "land"):
                if self._fault is not None:
                    self._fault("land")  # chaos: arrival dropped
                ok = gen.host_kv.receive(ids, arrays, dict(meta))
                if ok and self.prefix_cache is not None:
                    self.prefix_cache.adopt_offloaded(ids)
            return ok

        return self._run_on_serving(work, timeout_s, "import_prefix_kv")

    def has_prefix(self, pid: int) -> bool:
        """False once the prefix was dropped or LRU-evicted under pool
        pressure — callers re-register before admitting suffix-only ids."""
        return self.gen.has_prefix(pid)

    def _steer(self) -> None:
        """One controller pass per serve-loop iteration: record the realized
        dispatch size and, at most every controller interval, re-steer the
        prefill share from the observed TTFT/TPOT windows."""
        sched = getattr(self.gen, "scheduler", None)
        if sched is None:
            return
        dispatched = sum(sched.dispatches.values())
        if self._metrics is not None and dispatched != self._steered_dispatches:
            # only when step() made a LADDER dispatch — prefill-only
            # passes and TTFT mini-chunks must not re-count the previous
            # chunk size
            self._steered_dispatches = dispatched
            try:
                self._metrics.record_histogram(
                    "app_llm_chunk_tokens", float(sched.last_chunk),
                    model=self.name)
            except Exception:
                pass
        if self._controller is not None:
            self._controller.maybe_update()

    def _flush_on_close(self) -> None:
        """The serving thread is exiting: every parked or still-queued
        consumer must be woken with an error + _DONE, or its
        ``await out_q.get()`` blocks forever. The error is typed — a dead
        server (crash-loop) flushes ``GeneratorCrashed``, a clean close
        ``ServerClosed`` — so transports answer 503, not a 500 panic."""
        self._closed = True
        leftovers = self._waiting.drain()
        while True:
            try:
                req = self._requests.get_nowait()
            except _queue.Empty:
                break
            if req is not None:
                leftovers.append(req)
        for slot, req in list(self._active.items()):
            # tokens computed for an in-flight slot a force-close dropped
            # never ship as a completed answer (a graceful drain finishes
            # them before this runs)
            self._note_goodput("disconnected", self._slot_produced(slot))
            leftovers.append(req)
            del self._active[slot]
        exc = self._closed_error()
        for req in leftovers:
            self._reject(req, exc)

    def _closed_error(self) -> Exception:
        """The typed error consumers of a no-longer-serving server get."""
        if self._state == "dead":
            return GeneratorCrashed(
                "llm server is dead: generator restart budget exhausted "
                f"({self._max_restarts} restarts/"
                f"{self._restart_window:g}s)")
        return ServerClosed()

    def _note_goodput(self, reason: str, tokens: int) -> None:
        """Classify device-computed tokens in the goodput ledger — one
        call per fate decision, never per token. ``delivered`` routes
        through ``delivery_reason`` so a shadow-canary core's completed
        answers bill as ``canary`` waste (they never reach a client)."""
        if self._goodput is not None and tokens > 0:
            if reason == "delivered":
                reason = self.delivery_reason
            self._goodput.note(self.name, reason, int(tokens))

    def _slot_produced(self, slot: int | None) -> int:
        """Tokens the device computed for a slot, read defensively (the
        crash paths run while the wreck is mid-teardown)."""
        try:
            if slot is None:
                return 0
            return int(getattr(self.gen.slots[slot], "produced", 0))
        except Exception:
            return 0

    def _finish_journey(self, req: _Request, reason: str,
                        error: str | None = None) -> None:
        """Seal a request's journey into retention (journey.seal — the
        shared idempotent sequence; the pool and its core may both get
        here, first caller wins)."""
        seal_journey(req.journey, reason, error,
                     log=self._journeys, metrics=self._metrics)

    def _reject(self, req: _Request, exc: Exception) -> None:
        """Terminate a request that will never (or no longer) decode: end
        its spans — stamped with the typed outcome as ``ml.finish_reason``
        (``deadline`` | ``shed`` | ``crashed``), so a trace reads the same
        story as the error counters — and wake its consumer with the
        typed error + _DONE."""
        reason = _abort_reason(exc)
        if reason is not None:
            for span in (req.queue_span, req.decode_span):
                if span is not None and span.end_time is None:
                    span.set_attribute("ml.finish_reason", reason)
        req.finish_spans("ERROR", str(exc))
        if req.journey is not None:
            if req.journey_owned:
                self._finish_journey(req, reason or "error", str(exc))
            else:
                # a pool-owned journey is NOT sealed here: the front may
                # reroute this request to a survivor, and the journey
                # must keep recording — the reject is just one mark
                req.journey.mark("reject", reason=reason or "error")
        self._post(req, exc)
        self._post(req, _DONE)
        self._flush_posts()

    # -- watchdog / crash recovery --------------------------------------------
    def _recover_or_die(self, exc: BaseException) -> bool:
        """A supervised dispatch raised unexpectedly. Fail ONLY the
        in-flight requests bound to live slots with ``GeneratorCrashed``,
        rebuild the generator's decode state (``Generator.recover``:
        re-warmup from the pre-jitted ladder, borrowed prefix
        registrations invalidated, host-tier KV entries kept), and return
        True so the serve loop resumes draining the waiting queue —
        queued requests survive a crash untouched. Once the restart
        budget (GOFR_ML_MAX_RESTARTS per GOFR_ML_RESTART_WINDOW_S) is
        spent — or recovery itself fails — returns False: the server is
        ``dead``, consumers flush with typed errors, health reports
        unhealthy."""
        if self._logger is not None:
            try:
                self._logger.error(
                    "llm generator crashed", model=self.name,
                    error=str(exc), type=type(exc).__name__,
                    stack=traceback.format_exc())
            except Exception:
                pass
        # FORENSICS FIRST, while the wreck is still intact: the slot table
        # below is about to be failed and cleared, and recovery rebuilds
        # the decode state — snapshot the last events + scheduler/pool
        # state + in-flight slots into a crash bundle an operator reads at
        # /debug/crash/<id> long after the server recovered (or died)
        crash_id = self._capture_crash(exc)
        crash = GeneratorCrashed(
            f"generator dispatch failed ({type(exc).__name__}: {exc})")
        # STATE TRANSITION BEFORE THE REJECTS: a rejected consumer wakes
        # immediately (call_soon_threadsafe) and routinely reads
        # ``health()`` — or /debug/serving — right away; flipping the
        # state first means what it reads is never a stale ``serving``
        now = time.monotonic()
        with self._restart_lock:
            while (self._restart_times
                   and now - self._restart_times[0] > self._restart_window):
                self._restart_times.popleft()
            in_window = len(self._restart_times)
        if in_window >= self._max_restarts:
            self._state = "dead"
            self._record_restart(exc, recovered=False, crash_id=crash_id)
            self._events.emit("dead", model=self.name, crash_id=crash_id,
                              restarts=self._restarts_total,
                              budget=self._max_restarts)
            for slot, req in list(self._active.items()):
                self._note_goodput("crashed", self._slot_produced(slot))
                self._reject(req, crash)
                del self._active[slot]
            if self._logger is not None:
                try:
                    self._logger.error(
                        "llm restart budget exhausted; server is dead",
                        model=self.name, restarts=self._restarts_total,
                        budget=self._max_restarts,
                        window_s=self._restart_window)
                except Exception:
                    pass
            return False
        with self._restart_lock:
            self._restart_times.append(now)
        # visible to routers for the whole rebuild: a replica pool skips a
        # ``recovering`` replica instead of queueing behind its re-warmup
        self._state = "recovering"
        # quarantine the borrowed prefix registrations BEFORE waking the
        # crashed slots' consumers: a woken consumer's first read is often
        # has_prefix()/re-register, and it must never observe a suspect
        # registration as still live while recover() races toward the
        # invalidation (pure host bookkeeping; recover stays idempotent)
        try:
            quarantined = self.gen.quarantine_borrowed()
        except Exception:
            quarantined = []
        for slot, req in list(self._active.items()):
            self._note_goodput("crashed", self._slot_produced(slot))
            self._reject(req, crash)
            del self._active[slot]
        t0 = time.perf_counter()
        try:
            invalidated = self.gen.recover()
        except Exception as rexc:
            self._state = "dead"
            self._record_restart(exc, recovered=False, crash_id=crash_id)
            self._events.emit("dead", model=self.name, crash_id=crash_id,
                              error=f"recovery failed: {rexc}")
            if self._logger is not None:
                try:
                    self._logger.error(
                        "llm generator recovery failed; server is dead",
                        model=self.name, error=str(rexc),
                        stack=traceback.format_exc())
                except Exception:
                    pass
            return False
        if self.prefix_cache is not None:
            for pid in (*quarantined, *invalidated):
                try:
                    self.prefix_cache.invalidate(pid)
                except Exception:
                    pass
        self._restarts_total += 1
        self._state = "degraded"  # until the restart window drains
        recovery_ms = round((time.perf_counter() - t0) * 1e3, 1)
        self._record_restart(exc, recovered=True, recovery_ms=recovery_ms,
                             crash_id=crash_id)
        self._events.emit("recover", model=self.name, crash_id=crash_id,
                          recovery_ms=recovery_ms,
                          queued=len(self._waiting))
        self._steered_dispatches = -1
        if self._metrics is not None:
            try:
                self._metrics.add_counter(
                    "app_ml_generator_restarts_total", 1, model=self.name)
            except Exception:
                pass
        if self._logger is not None:
            try:
                self._logger.warnf(
                    "llm %s generator recovered (restart %d/%d in window); "
                    "resuming the waiting queue (%d queued)", self.name,
                    len(self._restart_times), self._max_restarts,
                    len(self._waiting))
            except Exception:
                pass
        return True

    def _record_restart(self, exc: BaseException, recovered: bool,
                        recovery_ms: float | None = None,
                        crash_id: str | None = None) -> None:
        with self._restart_lock:
            self._restart_history.append({
                "at": time.time(),
                "error": f"{type(exc).__name__}: {exc}",
                "recovered": recovered,
                "recovery_ms": recovery_ms,
                "crash_id": crash_id,  # the /debug/crash/<id> bundle
            })

    def _capture_crash(self, exc: BaseException) -> str | None:
        """Snapshot the crash into an in-memory forensics bundle (the
        trigger event, the last fleet events, the scheduler/pool state,
        and the in-flight slot table about to be failed) and return its
        ``/debug/crash/<id>`` id. Runs on the serving thread BEFORE the
        slots are rejected; a failure here must never block recovery."""
        try:
            now = time.perf_counter()
            slot_table = [{
                "slot": slot,
                "rid": req.rid,
                "prompt_tokens": req.n_tokens,
                "produced": getattr(self.gen.slots[slot], "produced", 0),
                "priority": PRIORITIES[req.priority],
                "age_s": round(now - req.enqueued_at, 4),
                "streamed": req.first_token_at is not None,
            } for slot, req in sorted(self._active.items())]
            trigger = self._events.emit(
                "crash", model=self.name,
                error=f"{type(exc).__name__}: {exc}",
                in_flight=len(slot_table), queued=len(self._waiting))
            state: dict = {
                "server_state": self._state,
                "restarts_total": self._restarts_total,
                "slots": slot_table,
                "scheduler": self.scheduler_snapshot(),
            }
            # each victim's FULL path, not just its final state: the
            # journey timelines of the in-flight slots, plus the newest
            # dispatch records (with the rids they served) so a
            # postmortem pivots request↔dispatch without a live repro
            journeys = [req.journey.snapshot()
                        for _, req in sorted(self._active.items())
                        if req.journey is not None]
            if journeys:
                state["journeys"] = journeys
            if self.recorder is not None:
                state["dispatches"] = self.recorder.tail(16)
            if self.fleet_info is not None:
                try:  # the fleet shape at crash time (elastic pools
                    # scale at runtime, so "2 replicas" is a timestamped
                    # fact, not a config constant)
                    state["fleet"] = self.fleet_info()
                except Exception:
                    pass
            try:  # the pool counters may be mid-wreck; best effort
                state["pool"] = self.gen.pool_stats()
            except Exception:
                pass
            # capture-on only: the newest captured requests ride the
            # bundle, so the crash replays offline straight from a saved
            # /debug/crash/<id> body (python -m gofr_tpu.ml.replay)
            capture_tail = (self._capture.export(newest=32)
                            if self._capture is not None else None)
            return self._crashes.capture(
                model=self.name, trigger=trigger, state=state,
                events=self._events.tail(128), capture=capture_tail)
        except Exception:
            return None

    # -- admission bounds / load shedding -------------------------------------
    def _enqueue_waiting(self, req: _Request) -> None:
        """Queue boundary admission control: within bounds the request
        simply joins its priority class; past GOFR_ML_MAX_QUEUE /
        GOFR_ML_MAX_QUEUED_TOKENS the LOWEST-priority queued request is
        shed (newest first) when the arrival outranks it — high-priority
        admission preempts queued low-priority work — otherwise the
        arrival itself is shed. Shed consumers get a typed ``Overloaded``
        (HTTP 429) carrying Retry-After from the observed drain rate.

        The request-count bound measures BACKLOG, not staging: queued
        requests covered by currently-free slots admit on the very next
        pass, so they get a free-slot credit — an idle server never
        sheds a burst it is about to serve."""
        w = self._waiting
        n_free = sum(1 for s in self.gen.slots if not s.live)
        over = ((self._max_queue > 0
                 and len(w) - n_free >= self._max_queue)
                or (self._max_queued_tokens > 0 and len(w) > n_free
                    and w.tokens + req.n_tokens > self._max_queued_tokens))
        if not over:
            w.push(req)
            return
        victim = w.shed_lowest(worse_than=req.priority)
        if victim is None:
            victim = req  # nothing queued is worse: shed the arrival
        else:
            w.push(req)
        self._shed(victim)

    def _shed(self, req: _Request) -> None:
        retry_after = self._retry_after_s()
        prio = PRIORITIES[req.priority]
        self._shed_counts[prio] += 1
        self._events.emit("shed", model=self.name, priority=prio,
                          rid=req.rid,
                          queued=len(self._waiting),
                          queued_tokens=self._waiting.tokens,
                          retry_after_s=round(retry_after, 3))
        if self._metrics is not None:
            try:
                self._metrics.add_counter("app_llm_shed_total", 1,
                                          model=self.name, priority=prio)
            except Exception:
                pass
        self._reject(req, Overloaded(
            f"server overloaded ({len(self._waiting)} queued, "
            f"{self._waiting.tokens} queued tokens); "
            f"retry in ~{retry_after:.1f}s", retry_after=retry_after))

    def _retry_after_s(self) -> float:
        """Retry-After from the observed queue drain rate (the scheduler's
        realized dispatch cadence), scaled by the backlog ahead of a
        retry — scheduler.retry_after_s over this instance's window."""
        return retry_after_s(self._admit_times, len(self._waiting))

    def _take_arrivals(self) -> None:
        """Move what has arrived from the queue to the waiting list: at
        the top of a pass and, in a busy one, once more after the step's
        wait on the device and BEFORE the finished streams are ended. Where a
        stream was ended and the device has not been waited for since,
        the queue is left alone: a client that answers the end of its
        stream at once is seen after the next wait on the device, a
        program's run later, in every pass alike. Read a millisecond
        after the finish markers, whether such a request caught the very
        next launch was a race between this thread and the transport's,
        and at 128 streams it came out either way, run by run."""
        if self._ended_at == self.gen.settled:
            return
        while True:
            try:
                req = self._requests.get_nowait()
            except _queue.Empty:
                return
            if req is None:
                self._closed = True
                return
            self._enqueue_waiting(req)

    def _admit_waiting(self) -> None:
        # pull what is queued, then admit as long as slots are free
        self._take_arrivals()
        if self._closed:
            return
        while len(self._waiting):
            if self._draining:
                # graceful drain (close(drain_s=)): in-flight decode keeps
                # stepping, but nothing new admits — still-queued requests
                # flush typed at teardown
                break
            if self.gen.free_slot() is None:
                # no admission possible: break WITHOUT draining, so the
                # chunk-decode pipeline stays one dispatch deep under
                # backlog (a drain here would sync the device every loop)
                break
            # About to admit: settle device bookkeeping and release finished
            # slots FIRST — add_requests' internal drain() could otherwise
            # finish another slot mid-admission and free_slot() would hand
            # back a slot still present in self._active, overwriting its
            # request (which then never receives _DONE). Draining here makes
            # the drain inside add_requests a no-op; it can only free MORE
            # slots, never consume the ones we just saw.
            self.gen.drain()
            self._finish_dead_slots()
            # admit everything that fits as ONE wave: the generator launches
            # one prefill program a prompt, back to back, and the device
            # queue holds them all ahead of the next decode chunk.
            # Paged mode admits one request per call instead — add_requests
            # is all-or-nothing, so a multi-request batch that hit
            # PagePoolExhausted on its LAST member would unwind the
            # admitted ones too and livelock on retry; single admission
            # keeps partial progress (paged prefill is per-request anyway).
            n_free = sum(not s.live for s in self.gen.slots)
            if getattr(self.gen, "page_size", 0):
                n_free = min(n_free, 1)
            batch, rejected = [], []
            req = None
            try:
                while len(self._waiting) and len(batch) < n_free:
                    # weighted-priority pop with aging, not FIFO: high
                    # beats normal beats low, but a parked request gains
                    # one class per aging interval so nothing starves
                    req = self._waiting.pop()
                    if (req.deadline_at is not None
                            and time.perf_counter() >= req.deadline_at):
                        # expired while queued: reaped at the admission
                        # gate, never prefilled — the deadline contract
                        self._expire(req, "while queued")
                        req = None
                        continue
                    try:
                        ids = self._validate(req)
                    except Exception as exc:
                        rejected.append((req, exc))
                        req = None
                        continue
                    ids = self._maybe_split_prefix(req, ids)
                    batch.append((req, ids))
                    req = None
            except Exception as exc:
                # the radix lookup dispatches device work (KV restore,
                # spill-on-eviction, prefix prefill): a crash there leaves
                # the popped request and earlier batch members in neither
                # _waiting nor _active, where the watchdog cannot see them
                # — fail them typed HERE or their consumers hang forever
                crash = GeneratorCrashed(
                    f"admission dispatch failed "
                    f"({type(exc).__name__}: {exc})")
                if req is not None:
                    self._reject(req, crash)
                for r, _ in batch:
                    self._reject(r, crash)
                for r, rexc in rejected:
                    self._reject(r, rexc)
                raise
            for req, exc in rejected:
                self._reject(req, exc)
            if not batch:
                continue
            try:
                if len(batch) == 1 and batch[0][0].prefix is not None:
                    req, ids = batch[0]
                    slots = [self.gen.add_request(
                        ids, req.max_new,
                        (lambda i, toks, r=req: self._emit(r, toks)),
                        prefix=req.prefix)]
                else:
                    slots = self.gen.add_requests([
                        (ids, req.max_new,
                         (lambda i, toks, r=req: self._emit(r, toks)))
                        for req, ids in batch
                    ])
            except PrefixEvicted as exc:
                # paged batches are size 1, so this is batch[0]'s prefix
                req = batch[0][0]
                if req.full_prompt is not None:
                    # the FRAMEWORK cache split this prompt and the
                    # generator evicted the prefix under pool pressure
                    # before admission: clear the stale registration and
                    # requeue with the original full prompt — the caller
                    # never learns caching was attempted
                    if self.prefix_cache is not None:
                        self.prefix_cache.invalidate(req.prefix)
                        # nothing saved — and the prefix-length tokens the
                        # fleet already computed once re-prefill with the
                        # full prompt (goodput: restore_fallback)
                        self.prefix_cache.record_miss(
                            lost_tokens=len(req.full_prompt)
                            - len(req.prompt))
                    req.prompt = req.full_prompt
                    req.prefix = None
                    req.full_prompt = None
                    self._waiting.push_front(req)
                    continue
                # explicitly-passed prefix: the caller owns re-registration
                self._reject(req, exc)
                continue
            except PagePoolExhausted:
                # transient paged-KV back-pressure: pages free as live
                # slots finish, so requeue the whole batch at the FRONT of
                # each request's class (retry order preserved) and let
                # decode progress instead of erroring clients
                for req, _ in reversed(batch):
                    self._waiting.push_front(req)
                break
            except ValueError as exc:
                # a client mistake the generator's own admission checks
                # caught (bucket overflow, draft-history limits): reject
                # the batch, keep serving — nothing device-side broke
                for req, _ in batch:
                    self._reject(req, exc)
                continue
            except Exception as exc:
                # device-side prefill failure: this batch's consumers get
                # the typed crash error, then the WATCHDOG supervises the
                # rest — the donated cache may be gone, so the in-flight
                # slots must be failed and the decode state rebuilt
                crash = GeneratorCrashed(
                    f"prefill dispatch failed "
                    f"({type(exc).__name__}: {exc})")
                for req, _ in batch:
                    self._reject(req, crash)
                raise
            now = time.perf_counter()
            for (req, _), slot in zip(batch, slots, strict=True):
                req.slot = slot
                self._active[slot] = req
                # fused decode windows read this to bound on-device steps
                # so a window can't burn K steps for a slot the reaper is
                # about to cancel; the serving reaper stays authoritative
                self.gen.slots[slot].deadline_at = req.deadline_at
                self._admit_times.append(now)
                trace = (req.trace_ctx.trace_id
                         if req.trace_ctx is not None else None)
                self._events.emit(
                    "admit", model=self.name, slot=slot,
                    rid=req.rid,
                    priority=PRIORITIES[req.priority],
                    prompt_tokens=req.n_tokens,
                    queued_ms=round((now - req.enqueued_at) * 1e3, 2),
                    **({"trace": trace} if trace is not None else {}))
                if req.journey is not None:
                    # the admit mark closes the queue-wait segment; the
                    # radix split and any restore debt the admission
                    # charged ride along so the waterfall explains what
                    # the decode replica actually prefilled
                    extra: dict = {"slot": slot,
                                   "priority": PRIORITIES[req.priority]}
                    if req.full_prompt is not None:
                        extra["prefix_tokens"] = (len(req.full_prompt)
                                                  - len(req.prompt))
                    sched = getattr(self.gen, "scheduler", None)
                    if sched is not None and sched.restore_debt:
                        extra["restore_debt"] = sched.restore_debt
                    sp_shards = getattr(self.gen.slots[slot],
                                        "sp_shards", 0)
                    if sp_shards:
                        # this prompt prefilled sequence-parallel: the
                        # waterfall names the shard count that carried it
                        extra["sp_shards"] = sp_shards
                    req.journey.mark("admit", **extra)
                if req.full_prompt is not None and self.prefix_cache is not None:
                    # the hit is real only now: the slot borrowed the
                    # prefix pages and the suffix-only prefill happened
                    self.prefix_cache.commit_hit(req.prefix)
                if req.queue_span is not None:
                    req.queue_span.set_attribute("ml.slot", slot)
                    req.queue_span.end()
                if self._tracer is not None:
                    req.decode_span = self._tracer.start_span(
                        "ml.decode", parent=req.trace_ctx, activate=False,
                        attributes={"ml.model": self.name, "ml.slot": slot},
                    )
                if self._metrics is not None:
                    try:
                        self._metrics.record_histogram(
                            "app_llm_queue_seconds",
                            now - req.enqueued_at, model=self.name,
                        )
                        # per-class wait: the series an operator verifies
                        # priority admission (and aging) against
                        self._metrics.record_histogram(
                            "app_llm_priority_queue_seconds",
                            now - req.enqueued_at, model=self.name,
                            priority=PRIORITIES[req.priority],
                        )
                    except Exception:
                        pass

    def _validate(self, req) -> Any:
        """Shape-check the prompt on the serving thread so one bad request
        rejects cleanly instead of failing the whole admission wave. A
        prefixed request may carry an EMPTY suffix (the registered tail
        still prefills); the generator rejects a truly token-free one."""
        import numpy as np

        ids = np.asarray(req.prompt, np.int32).reshape(-1)
        n = len(ids)
        if (n == 0 and req.prefix is None) or n >= self.gen.max_seq:
            raise ValueError(
                f"prompt length {n} out of range (1..{self.gen.max_seq - 1})")
        return ids

    def _maybe_split_prefix(self, req, ids):
        """Admission-path radix lookup: longest-match the prompt against
        the framework prefix cache and split it into (registered prefix,
        suffix) so prefill covers only the suffix. Hot prefixes promote
        inside ``observe`` — the request crossing the threshold already
        reuses. Runs ONCE per request (``cache_seen``): a requeued request
        keeps its split, and the PrefixEvicted fallback keeps its decision
        to go uncached."""
        cache = self.prefix_cache
        if cache is None or req.prefix is not None or req.cache_seen:
            return ids
        req.cache_seen = True
        pid, reg_len = cache.observe(ids)
        if pid is None:
            return ids
        req.full_prompt = ids
        req.prefix = pid
        req.prompt = ids[reg_len:]
        return req.prompt

    def _emit(self, req: _Request, tokens: list[int]) -> None:
        """Push one BURST of tokens (the slot's share of a processed chunk)
        to the consumer — ONE loop wakeup per burst, not per token. At 64
        streams x chunk 16 the per-token version was ~38k
        ``call_soon_threadsafe`` wakeups/s on the event loop thread."""
        if self._fault is not None:
            self._fault("emit")  # chaos point: a poisoned token callback
        if req.journey is not None:
            # one mark per BURST, never per token: the first burst closes
            # the prefill segment (the TTFT boundary), later ones are
            # decode windows. The dispatch seq (this pass commits as
            # dispatches+1) and the rid tag on the dispatch record are
            # the two halves of the request↔dispatch pivot.
            name = "prefill" if req.first_token_at is None else "decode"
            rec = self.recorder
            # in-flight depth at emit time (0 = fully drained, 1 = the
            # lag-one pipeline, 2 = double-buffered, GOFR_ML_PIPELINE):
            # the waterfall shows overlapped dispatches honestly instead
            # of implying serial device time
            depth = len(self.gen._inflight)
            if rec is not None:
                rec.note_rid(req.rid)
                req.journey.mark(name, tokens=len(tokens), inflight=depth,
                                 dispatch=rec.dispatches + 1)
            else:
                req.journey.mark(name, tokens=len(tokens), inflight=depth)
        now = time.perf_counter()
        if (self._controller is not None and tokens
                and req.last_burst_at is not None):
            # live cadence per burst: waiting for stream FINISH would leave
            # the controller TPOT-blind (and decode unprotected) for the
            # whole lifetime of a long stream. Under speculation the burst
            # carries every VERIFIED token of the window (accepted drafts
            # + the bonus token), so verify tokens steer the controller
            # exactly like plain decode tokens — the SLO loop sees spec
            # speedups as lower TPOT, not as a blind spot
            self._controller.observe_tpot(
                (now - req.last_burst_at) / len(tokens))
        req.last_burst_at = now
        if req.first_token_at is None:
            req.first_token_at = now
            if self._controller is not None:
                self._controller.observe_ttft(
                    req.first_token_at - req.enqueued_at)
            if req.decode_span is not None:
                req.decode_span.add_event(
                    "first_token",
                    {"ttft_s": req.first_token_at - req.enqueued_at})
            if self._metrics is not None:
                try:
                    self._metrics.record_histogram(
                        "app_llm_ttft_seconds",
                        req.first_token_at - req.enqueued_at, model=self.name,
                    )
                except Exception:
                    pass
        if self._metrics is not None:
            try:
                self._metrics.add_counter(
                    "app_llm_tokens_total", len(tokens), model=self.name)
            except Exception:
                pass
        self._post(req, list(tokens))

    def _post(self, req: _Request, item) -> None:
        """Queue ``item`` (a burst of tokens, a finish marker, an error)
        for ``req``'s consumer. ``_flush_posts`` wakes each consumer loop
        ONCE for all that was posted since: after a dispatch's last burst,
        after the finished streams' markers, after a rejection. At 128
        streams a wakeup a stream was 128 writes to the loop's wake-up
        socket a dispatch, each of which gives up the interpreter's lock
        to the loop's thread; after a wave's one-step program the device
        stands idle until they are through and the next program is
        launched."""
        self._outbox.setdefault(req.loop, []).append((req.out_q, item))

    def _flush_posts(self) -> None:
        if not self._outbox:
            return
        outbox, self._outbox = self._outbox, {}
        for loop, items in outbox.items():
            try:
                loop.call_soon_threadsafe(_deliver, items)
            except RuntimeError:
                pass  # consumer loop itself already gone

    def _expire(self, req: _Request, where: str) -> None:
        """One request past its deadline: typed 504 to the consumer plus
        the counter the operator alarms on."""
        self._deadline_expired += 1
        self._events.emit("deadline", model=self.name, where=where,
                          rid=req.rid,
                          priority=PRIORITIES[req.priority])
        if self._metrics is not None:
            try:
                self._metrics.add_counter("app_llm_deadline_exceeded_total",
                                          1, model=self.name)
            except Exception:
                pass
        self._reject(req, DeadlineExceeded(
            f"request deadline exceeded {where}"))

    def _reap_cancelled(self) -> None:
        """Stop decoding for consumers that went away (client disconnect /
        stream abandoned) and requests past their deadline: either would
        otherwise burn decode steps to max_new_tokens, delaying every
        waiting request. Queued expirations reject here — before any
        prefill is paid; mid-decode expirations cancel the slot (pages
        free on release) and complete with ``DeadlineExceeded``."""
        now = time.perf_counter()
        # ONE queue scan for both conditions (this runs every serve-loop
        # pass); the removed items split by cause below
        for r in self._waiting.prune(
                lambda r: r.cancelled or (r.deadline_at is not None
                                          and now >= r.deadline_at)):
            if r.cancelled:
                r.finish_spans("ERROR", "cancelled before admission")
            else:
                self._expire(r, "while queued")
        for slot, req in self._active.items():
            if not self.gen.slots[slot].live:
                continue
            if req.cancelled:
                self.gen.slots[slot].live = False
            elif req.deadline_at is not None and now >= req.deadline_at:
                req.deadline_hit = True
                self.gen.slots[slot].live = False

    def _export_pool_gauges(self) -> None:
        """Pool pressure at :2121 — evictions (truncated streams) and
        prefix evictions (LRU-dropped system prompts) are the two signals
        an operator sizes n_pages by."""
        if self._metrics is None:
            return
        try:
            self._metrics.set_gauge("app_llm_active_slots",
                                    float(self.gen.n_live), model=self.name)
            self._metrics.set_gauge("app_llm_evictions",
                                    float(self.gen.evictions),
                                    model=self.name)
            if getattr(self.gen, "page_size", 0):
                self._metrics.set_gauge(
                    "app_llm_prefix_evictions",
                    float(getattr(self.gen, "prefix_evictions", 0)),
                    model=self.name)
                self._metrics.set_gauge("app_llm_free_pages",
                                        float(self.gen.free_pages),
                                        model=self.name)
                self._export_offload_metrics()
            sched = getattr(self.gen, "scheduler", None)
            if sched is not None:
                self._metrics.set_gauge("app_llm_token_budget",
                                        float(sched.budget),
                                        model=self.name)
                self._metrics.set_gauge("app_llm_prefill_share",
                                        float(sched.prefill_share),
                                        model=self.name)
            sp = getattr(self.gen, "sp_stats", None)
            sp = sp() if sp is not None else None
            if sp is not None:
                # sequence-parallel serving: the shard-count gauge plus
                # prefill/fallback counter deltas (watermark pattern)
                self._metrics.set_gauge("app_ml_sp_shards",
                                        float(sp["shards"]),
                                        model=self.name)
                if sp["prefills"] > self._sp_prefills_seen:
                    self._metrics.add_counter(
                        "app_ml_sp_prefills_total",
                        sp["prefills"] - self._sp_prefills_seen,
                        model=self.name)
                    self._sp_prefills_seen = sp["prefills"]
                if sp["fallbacks"] > self._sp_fallbacks_seen:
                    self._metrics.add_counter(
                        "app_ml_sp_fallbacks_total",
                        sp["fallbacks"] - self._sp_fallbacks_seen,
                        model=self.name)
                    self._sp_fallbacks_seen = sp["fallbacks"]
            disables = int(getattr(self.gen, "spec_disables", 0))
            if disables > self._spec_disables_seen:
                # adaptive speculation turned a slot OFF (accept rate
                # below GOFR_ML_SPEC_MIN_ACCEPT) — the alarm-able pair to
                # the app_llm_spec_accept histogram
                self._metrics.add_counter(
                    "app_llm_spec_disabled_total",
                    disables - self._spec_disables_seen, model=self.name)
                self._spec_disables_seen = disables
        except Exception:
            pass

    def _export_offload_metrics(self) -> None:
        """Host-tier visibility: spill/restore counter deltas + the bytes
        the tier currently holds. Each delta publishes independently so a
        missing metric (bare managers in tests) can't eat the others."""
        host = getattr(self.gen, "host_kv", None)
        if host is not None:
            try:
                self._metrics.set_gauge("app_ml_kv_offload_bytes",
                                        float(host.bytes_used),
                                        model=self.name)
            except Exception:
                pass
        spills = int(getattr(self.gen, "kv_spills", 0))
        if spills > self._kv_spills_seen:
            try:
                self._metrics.add_counter(
                    "app_ml_kv_offload_spills_total",
                    spills - self._kv_spills_seen, model=self.name)
                self._kv_spills_seen = spills
            except Exception:
                pass
        restores = int(getattr(self.gen, "kv_restores", 0))
        if restores > self._kv_restores_seen:
            try:
                self._metrics.add_counter(
                    "app_ml_kv_offload_restores_total",
                    restores - self._kv_restores_seen, model=self.name)
                self._kv_restores_seen = restores
            except Exception:
                pass

    def _finish_dead_slots(self) -> None:
        self._export_pool_gauges()
        for slot, req in list(self._active.items()):
            s = self.gen.slots[slot]
            if not s.live:
                self._ended_at = self.gen.settled  # see _take_arrivals
                if req.deadline_hit:
                    # cancelled mid-generation by its deadline: free the
                    # slot (pages with it) and complete with the typed
                    # 504 instead of a finish marker. The tokens it
                    # produced never ship as an answer — wasted.
                    self._note_goodput("deadline_cancelled", s.produced)
                    self.gen.release(slot)
                    del self._active[slot]
                    self._expire(req, "mid-generation")
                    continue
                if getattr(s, "evicted", False):
                    reason = "eviction"
                elif s.eos_hit:
                    reason = "stop"
                else:
                    reason = "length"
                if (self._metrics is not None
                        and getattr(self.gen, "spec_k", 0)
                        and s.spec_windows):
                    # per-stream draft acceptance rate in [0, 1]:
                    # accepted drafts / proposed drafts (VERDICT r4 #7)
                    rate = ((s.spec_emitted - s.spec_windows)
                            / (s.spec_windows * self.gen.spec_k))
                    try:
                        self._metrics.record_histogram(
                            "app_llm_spec_accept", rate, model=self.name)
                    except Exception:
                        pass
                produced = s.produced
                now = time.perf_counter()
                # (the SLO controller already sampled this stream's TPOT
                # per burst in _emit — a lifetime average here would
                # re-report stale slowness into a fresh window)
                if (self._metrics is not None and produced > 1
                        and req.first_token_at is not None):
                    # stream cadence AFTER the first token: the SLO pair to
                    # TTFT (a request is "fast" iff both are)
                    try:
                        self._metrics.record_histogram(
                            "app_llm_tpot_seconds",
                            (now - req.first_token_at) / (produced - 1),
                            model=self.name)
                    except Exception:
                        pass
                if req.decode_span is not None:
                    req.decode_span.set_attributes({
                        "ml.tokens": produced,
                        "ml.finish_reason": reason,
                    })
                if req.journey is not None:
                    # natural completion seals the journey here even for
                    # pool-owned ones — there is no reroute after a finish
                    req.journey.note(tokens=produced)
                    if getattr(self.gen, "spec_k", 0) and s.spec_windows:
                        req.journey.note(spec_windows=s.spec_windows,
                                         spec_emitted=s.spec_emitted)
                    self._finish_journey(req, reason)
                req.finish_spans()
                # goodput classification at the slot's fate decision: a
                # natural finish delivered every produced token; a
                # consumer that walked away mid-stream received nothing
                # it will use (the slot was cancelled, not completed)
                self._note_goodput(
                    "disconnected" if req.cancelled else "delivered",
                    produced)
                # all of the slot's tokens were streamed via the callback
                self.gen.release(slot)
                del self._active[slot]
                self.served += 1
                self._post(req, _Finish(reason))
        self._flush_posts()

    def check_admissible(self, prompt_ids, max_new_tokens: int = 1,
                         prefix: int | None = None) -> None:
        """Raise ValueError if this request can NEVER admit under the
        generator's static shape rules — prompt/suffix length vs max_seq
        and the prefill buckets, draft-model full-history ingestion, and
        a paged pool too small to ever cover the request. Transports call
        this BEFORE opening a response stream so un-admittable requests
        answer a clean 4xx instead of failing after headers are on the
        wire. Transient conditions (busy slots, recoverable pool
        pressure) pass — those requeue."""
        import numpy as np

        gen = self.gen
        ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        n = len(ids)
        if n == 0 or n >= gen.max_seq:
            raise ValueError(
                f"prompt length {n} out of range (1..{gen.max_seq - 1})")
        buckets = gen.prefill_buckets
        draft = (getattr(gen, "spec_k", 0)
                 and getattr(gen, "draft_params", None) is not None)
        if prefix is not None:
            info = getattr(gen, "_prefixes", {}).get(prefix)
            if info is None:
                return  # evicted: the PrefixEvicted retry path handles it
            n_suf = len(info["tail"]) + n
            if info["len"] + n_suf >= gen.max_seq:
                raise ValueError(
                    f"prefix {info['len']} + suffix {n_suf} exceeds "
                    f"max_seq")
            if n_suf > buckets[-1]:
                raise ValueError(
                    f"suffix length {n_suf} exceeds the largest prefill "
                    f"bucket {buckets[-1]}")
            if draft and info["len"] + n_suf > buckets[-1]:
                raise ValueError(
                    f"prefix+suffix length {info['len'] + n_suf} exceeds "
                    f"the largest prefill bucket {buckets[-1]} (the draft "
                    f"model must ingest the full history)")
            return
        chunked = getattr(gen, "prefill_chunk", 0) and n > gen.prefill_chunk
        if not chunked and n > buckets[-1]:
            # a cached shared prefix can still admit this prompt — only
            # the suffix prefills. Draft-model speculation can't (the
            # draft must ingest the full history), and a cold prompt
            # genuinely cannot prefill beyond the largest bucket.
            covered = (not draft and self.prefix_cache is not None
                       and self.prefix_cache.peek(ids)[0] is not None)
            if not covered:
                raise ValueError(
                    f"prompt length {n} exceeds the largest prefill bucket "
                    f"{buckets[-1]}")
        if chunked and draft and n > buckets[-1]:
            raise ValueError(
                f"prompt length {n} exceeds the largest prefill bucket "
                f"{buckets[-1]} (the draft model must ingest the full "
                f"history)")
        if getattr(gen, "page_size", 0):
            upto = min(n + 2 * gen.chunk, n + max_new_tokens, gen.max_seq)
            need = -(-upto // gen.page_size)
            if need > gen._pages_ever_free():
                raise ValueError(
                    f"request needs {need} pages but the pool can only "
                    f"ever free {gen._pages_ever_free()}")

    # -- async API ------------------------------------------------------------
    async def stream_chunks(self, prompt_ids, max_new_tokens: int = 64,
                            prefix: int | None = None,
                            info: dict | None = None,
                            priority: int | str | None = None,
                            deadline_s: float | None = None,
                            rid: str | None = None,
                            journey=None,
                            mode: str = "chunks") -> AsyncIterator[list[int]]:
        """Yield BURSTS of tokens — each list is the slot's share of one
        processed decode chunk (the first is ``[first_token]`` from the
        TTFT mini-chunk). The low-overhead surface for transports that can
        frame several tokens per message (gRPC streaming, SSE): one
        consumer wakeup and one wire frame per burst instead of per token.

        ``priority`` selects the admission class (``"high"`` / ``"normal"``
        / ``"low"`` or the class index; default normal): under slot
        contention higher classes admit first, with aging so lower classes
        can never starve. Unknown values raise ValueError before enqueue.

        ``deadline_s`` is the request's TTL (default from
        ``GOFR_ML_DEFAULT_DEADLINE_S``; 0 disables): past it the request
        is reaped wherever it sits — still queued (rejected before any
        prefill) or mid-decode (slot cancelled, pages freed) — with a
        typed ``DeadlineExceeded`` (HTTP 504 / gRPC DEADLINE_EXCEEDED).

        Pass ``info={}`` to receive ``info["finish_reason"]`` on completion:
        ``"stop"`` (eos), ``"length"`` (budget), or ``"eviction"`` (page
        pool dry — the answer was truncated mid-thought and must not be
        presented as a natural stop).

        ``rid``/``journey`` are the request-journey plumbing (a
        ``ReplicaPool`` front passes its own so the fleet hop and the
        core hop share ONE timeline); standalone callers leave them unset
        and the server records a journey itself when ``GOFR_ML_JOURNEY``
        enables them. ``mode`` labels the consumer surface
        (``chunks``/``stream``/``generate`` — the ``stream``/``generate``
        wrappers set it) in the traffic-capture record so a replayed
        bundle is honest about how the window was consumed.
        """
        if self._closed or self._draining:
            raise self._closed_error()
        prio = normalize_priority(priority)  # raises BEFORE enqueue
        ttl = self._default_deadline if deadline_s is None else deadline_s
        if not ttl >= 0:  # rejects NaN too (NaN >= 0 is False)
            raise ValueError(f"deadline_s must be >= 0, got {ttl}")
        loop = asyncio.get_running_loop()
        out_q: asyncio.Queue = asyncio.Queue()
        # capture the caller's span before the executor hop; the serving
        # thread parents ml.queue/ml.decode to it explicitly
        ctx = current_context()
        queue_span = None
        if self._tracer is not None:
            queue_span = self._tracer.start_span(
                "ml.queue", parent=ctx, activate=False,
                attributes={"ml.model": self.name},
            )
        cap_rec = None
        if rid is None:
            if self._capture is not None:
                # capture at the submit boundary, BEFORE any radix split
                # mutates the prompt: the bundle carries the full token
                # ids the caller sent. Pool cores never get here — their
                # front passed rid= after capturing the fleet request.
                rid = next_rid()
                cap_rec = self._capture.admit(
                    rid, model=self.name, tokens=prompt_ids,
                    max_new=max_new_tokens, priority=prio, deadline_s=ttl,
                    mode=mode, sampler=self._cap_sampler,
                    prefix=prefix is not None)
            else:
                rid = next_rid()
        owned = False
        if journey is None and self._journeys is not None:
            journey = self._journeys.start(Journey(
                rid, model=self.name,
                trace_id=ctx.trace_id if ctx is not None else None))
            owned = True
        req = _Request(prompt_ids, max_new_tokens, out_q, loop,
                       prefix=prefix, trace_ctx=ctx, queue_span=queue_span,
                       priority=prio, deadline_s=ttl, rid=rid,
                       journey=journey, journey_owned=owned)
        self._requests.put(req)
        if self._closed:
            # close() may have drained the queue before our put landed —
            # never park on a queue nobody reads (TOCTOU with close()).
            # If the flush DID see the request it only pushed an error
            # into out_q, which we're abandoning; mark cancelled so the
            # serving thread reaps it if it was somehow admitted.
            req.cancelled = True
            if cap_rec is not None:
                cap_rec.finish("error")
            if owned:
                self._finish_journey(req, "error", "server closed")
            raise self._closed_error()
        try:
            while True:
                item = await out_q.get()
                if item is _DONE:  # close-flush path: no slot state to read
                    return
                if isinstance(item, _Finish):
                    if info is not None:
                        info["finish_reason"] = item.reason
                    if cap_rec is not None:
                        # the digest↔rid crosslink: the capture record
                        # and the journey waterfall share the rid, and
                        # the journey's request summary names the digest
                        digest = cap_rec.finish(item.reason)
                        if journey is not None and digest is not None:
                            journey.note(output_digest=digest)
                    return
                if isinstance(item, Exception):
                    if cap_rec is not None:
                        cap_rec.finish(_abort_reason(item) or "error")
                    raise item
                if cap_rec is not None:
                    cap_rec.add_tokens(item)
                yield item
        finally:
            # consumer closed the stream (disconnect, break, cancellation):
            # flag it so the serving thread frees the slot instead of
            # decoding to max_new_tokens for nobody
            req.cancelled = True
            if cap_rec is not None and not cap_rec.done:
                cap_rec.finish("cancelled")
            if owned and journey is not None and not journey.done:
                # abandonment, not a serving failure (errors and natural
                # completions sealed the journey before we got here)
                self._finish_journey(req, "cancelled")

    async def stream(self, prompt_ids, max_new_tokens: int = 64,
                     prefix: int | None = None,
                     info: dict | None = None,
                     priority: int | str | None = None,
                     deadline_s: float | None = None) -> AsyncIterator[int]:
        """Yield tokens as the device produces them (token-at-a-time view
        of ``stream_chunks``)."""
        agen = self.stream_chunks(prompt_ids, max_new_tokens, prefix=prefix,
                                  info=info, priority=priority,
                                  deadline_s=deadline_s, mode="stream")
        try:
            async for burst in agen:
                for tok in burst:
                    yield tok
        finally:
            # close the inner generator NOW (its finally marks the request
            # cancelled); leaving it to GC delays slot reaping arbitrarily
            await agen.aclose()

    async def generate(self, prompt_ids, max_new_tokens: int = 64,
                       prefix: int | None = None,
                       info: dict | None = None,
                       priority: int | str | None = None,
                       deadline_s: float | None = None) -> list[int]:
        """Collect the full completion."""
        out: list[int] = []
        async for burst in self.stream_chunks(prompt_ids, max_new_tokens,
                                              prefix=prefix, info=info,
                                              priority=priority,
                                              deadline_s=deadline_s,
                                              mode="generate"):
            out.extend(burst)
        return out

    def queue_depth(self) -> int:
        """Requests waiting for a decode slot (sampled as
        ``app_ml_queue_depth{component="llm"}``)."""
        return len(self._waiting) + self._requests.qsize()

    def scheduler_snapshot(self) -> dict:
        """Live scheduler state for ``/debug/serving``: the token budget
        and realized chunk-size mix, the SLO controller's last percentiles
        vs targets, and per-priority ready-queue depth/age. Reads simple
        attributes only — safe from any thread."""
        out: dict = {"waiting": self._waiting.snapshot()}
        sched = getattr(self.gen, "scheduler", None)
        if sched is not None:
            out.update(sched.snapshot())
        else:
            out["budget"] = None  # fixed-chunk dispatch
        out["prefill_segments"] = getattr(self.gen,
                                          "prefill_segments_run", 0)
        if self._controller is not None:
            out["slo"] = self._controller.snapshot()
        return out

    # -- datasource contract --------------------------------------------------
    def health(self) -> str:
        """Serving state for the health plane: ``serving`` (healthy),
        ``recovering`` (a crash recovery is rebuilding the generator RIGHT
        NOW — a router should skip this replica until it finishes),
        ``degraded`` (the watchdog recovered a generator crash within the
        current restart window — still serving, but an operator should
        look), or ``dead`` (restart budget exhausted / recovery failed /
        serving thread gone: nothing will complete)."""
        if (self._state == "dead" or self._closed
                or not self._thread.is_alive()):
            return "dead"
        if self._state == "recovering":
            return "recovering"
        now = time.monotonic()
        with self._restart_lock:
            degraded = any(now - t <= self._restart_window
                           for t in self._restart_times)
        return "degraded" if degraded else "serving"

    def resilience_snapshot(self) -> dict:
        """The ``resilience`` block of ``/debug/serving``: state, restart
        budget + history, shed/deadline counters, queue bounds, and the
        armed fault config. Reads simple attributes only — safe from any
        thread."""
        with self._restart_lock:
            in_window = len(self._restart_times)
            recent = list(self._restart_history)
        return {
            "state": self.health(),
            "draining": self._draining,
            "closed_cleanly": self.closed_cleanly,
            "restarts": {
                "total": self._restarts_total,
                "in_window": in_window,
                "budget": self._max_restarts,
                "window_s": self._restart_window,
                "recent": recent,
            },
            "shed": dict(self._shed_counts),
            "deadline_expired": self._deadline_expired,
            "queue_bounds": {
                "max_requests": self._max_queue or None,
                "max_tokens": self._max_queued_tokens or None,
                "queued": len(self._waiting),
                "queued_tokens": self._waiting.tokens,
            },
            "default_deadline_s": self._default_deadline or None,
            "fault": fault_snapshot(self._fault),
        }

    def health_check(self) -> dict:
        state = self.health()
        status = {"serving": "UP", "degraded": "DEGRADED",
                  "recovering": "DEGRADED", "dead": "DOWN"}[state]
        return {
            "status": status,
            "details": {
                "model": self.name,
                "state": state,
                "slots": self.gen.batch_slots,
                "live": self.gen.n_live,
                "queued": len(self._waiting) + self._requests.qsize(),
                "served": self.served,
                "decode_steps": self.gen.steps,
                "restarts": self._restarts_total,
            },
        }

    def close(self, drain_s: float | None = None) -> None:
        """Shut the server down. With ``drain_s`` > 0 (default from
        ``GOFR_ML_DRAIN_S``; 0 = immediate) this is a GRACEFUL drain:
        admission stops first (new submissions fail fast with the typed
        closed error, queued requests stay parked), in-flight decode runs
        to completion up to the deadline, then the serving thread tears
        down and flushes whatever remains. Wired into app shutdown via
        ``MLDatasource.close`` so SIGTERM is a drain, not a drop."""
        if drain_s is None:
            drain_s = self._drain_default
        if drain_s > 0 and not self._closed and self._thread.is_alive():
            self._draining = True
            self._events.emit("drain", model=self.name,
                              drain_s=drain_s, in_flight=len(self._active),
                              queued=len(self._waiting))
            deadline = time.monotonic() + drain_s
            while time.monotonic() < deadline:
                if not self._active and self.gen.n_live == 0:
                    break  # every admitted request completed
                time.sleep(0.005)
            if self._logger is not None and self._active:
                try:
                    self._logger.warnf(
                        "llm %s drain deadline (%.1fs) hit with %d "
                        "request(s) still in flight", self.name, drain_s,
                        len(self._active))
                except Exception:
                    pass
        if not self._closed:
            self._closed = True
            self._requests.put(None)
            self._thread.join(timeout=5)
            # catch requests that raced past the serving thread's final
            # flush: wake their consumers instead of stranding them. Only
            # once the thread is really gone — if join timed out (stuck
            # compile/dispatch), flushing here would mutate _active/_waiting
            # under the live thread; its own finally-flush runs on exit.
            if self._thread.is_alive():
                # a wedged serving thread is an incident, not a clean
                # shutdown: say so (with where it's stuck) instead of
                # returning as if everything drained, and leave the
                # breadcrumb in the debug snapshot (closed_cleanly)
                self.closed_cleanly = False
                if self._logger is not None:
                    try:
                        self._logger.error(
                            "llm serving thread leaked on close",
                            model=self.name, thread=self._thread.name,
                            alive=True, state=self._state,
                            live_slots=self.gen.n_live,
                            queued=len(self._waiting)
                            + self._requests.qsize())
                    except Exception:
                        pass
            else:
                self._flush_on_close()

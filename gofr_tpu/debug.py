"""Serving debug + profiling endpoints.

The reference mounts net/http/pprof under APP_ENV=DEBUG
(pkg/gofr/http_server.go:65-72) so an operator can always answer "what is
the server doing right now?". The TPU-native equivalents here:

- ``GET /debug/serving`` — a JSON snapshot of the whole inference plane:
  per-engine step counts and compiled shape buckets, batcher backlog, LLM
  slot occupancy and KV-pool pressure, and in-process latency percentiles
  (TTFT, TPOT, device step) read from the same histograms Prometheus
  scrapes at :2121.
- ``GET /debug/profile?seconds=N`` — captures a ``jax.profiler`` trace
  (device + host timelines, viewable in XProf/TensorBoard) for N seconds
  and streams it back as a zip. One capture at a time: the profiler is a
  process-global singleton, so a second concurrent request answers 409
  instead of corrupting the first trace.
- ``GET /debug/events?since=<cursor>&model=…&kind=…`` — the serving
  flight recorder's fleet event log (flight_recorder.py): typed
  admission/routing/spill/shed/deadline/crash events with a monotonic
  cursor, so an operator (or a poller) replays exactly what the serving
  plane decided, in order, across every model and replica.
- ``GET /debug/crash`` / ``GET /debug/crash/<id>`` — crash forensics
  bundles the watchdog snapshots when a generator crashes or a replica
  dies: the triggering event, the preceding fleet events, the scheduler
  and pool state, and the in-flight slot table — the postmortem without a
  live repro.
- ``GET /debug/requests`` / ``GET /debug/requests/<rid>`` — the request
  journey tracer (ml/journey.py): per-request lifecycle timelines whose
  marks (route, ship/land, admit, prefill, decode, finish) sum to the
  request wall. The index answers with per-mark duration percentiles
  over the retained ring plus the failed/p99-slow exemplars; the rid
  route returns one request's waterfall.
- ``GET /debug/goodput`` — the serving-economics ledger (ml/goodput.py):
  every device-computed token classified as delivered or one of the
  wasted reasons (spec rejects, deadline/crash/disconnect losses,
  failover/restore/migration recomputes), per model and fleet-wide,
  with the goodput fraction and delivered tokens/s.
- ``GET /debug/programs`` — the jitted-program inventory
  (ml/programs.py): per-model rows for every compiled program (shapes,
  compile wall, persistent-XLA-cache provenance, lazy ``cost_analysis``
  flops/bytes; ``?cost=0`` skips the analysis) plus live per-device HBM.
- ``GET /debug/profile/auto`` / ``GET /debug/profile/auto/<id>`` — the
  anomaly-triggered auto-profiler's vault (flight_recorder.py): trace
  zips captured when a serving core's step time or phase shares
  regressed past their rolling baseline; the index lists triggers, the
  id route streams the zip.
- ``GET /debug/capture`` — the traffic-capture bundle (ml/capture.py,
  armed via ``GOFR_ML_CAPTURE``): the recorded request window as one
  length-prefixed binary download for ``python -m gofr_tpu.ml.replay``;
  ``?rid=`` exports a single request, unarmed answers a JSON
  ``enabled: false``.
"""

from __future__ import annotations

import asyncio
import math
import shutil
import tempfile
import time

from aiohttp import web

__all__ = ["register_debug_routes", "serving_snapshot"]

# histograms worth quoting percentiles for, keyed by their label sets:
# (name, labels) pairs resolved per registered model below
_LATENCY_HISTOGRAMS = (
    "app_tpu_step_seconds",
    "app_ml_queue_seconds",
    "app_llm_queue_seconds",
    "app_llm_ttft_seconds",
    "app_llm_tpot_seconds",
)
# queue wait per admission-priority class: label sets are (model, priority),
# so the per-model loop above can't reach them — resolved separately against
# the scheduler's own class list
_PRIORITY_HISTOGRAM = "app_llm_priority_queue_seconds"
_QUANTILES = (0.5, 0.95, 0.99)

# the jax profiler is process-global state: one capture at a time, ever —
# the lock lives in flight_recorder so the auto-profiler and this manual
# endpoint can never corrupt each other's trace
from .flight_recorder import PROFILE_LOCK as _profile_lock  # noqa: E402

MAX_PROFILE_SECONDS = 60.0


def _histogram_percentiles(manager, model_names) -> dict:
    """p50/p95/p99 per latency histogram per model, via Manager.percentile
    (bucket-boundary approximations — Prometheus does the real math
    server-side; these are for an operator's quick curl)."""
    out: dict = {}
    for name in _LATENCY_HISTOGRAMS:
        if not manager.has(name):
            continue
        for model in model_names:
            try:
                vals = {
                    f"p{int(q * 100)}": manager.percentile(name, q, model=model)
                    for q in _QUANTILES
                }
            except Exception:
                continue
            vals = {k: v for k, v in vals.items() if not math.isnan(v)}
            if vals:
                out.setdefault(name, {})[model] = vals
    if manager.has(_PRIORITY_HISTOGRAM) and model_names:
        # the scheduler's class list is the single source of truth for the
        # label values; imported lazily — pulling in gofr_tpu.ml at module
        # scope would cost every app jax's import time at startup
        from .ml.scheduler import PRIORITIES
        for model in model_names:
            for prio in PRIORITIES:
                try:
                    vals = {
                        f"p{int(q * 100)}": manager.percentile(
                            _PRIORITY_HISTOGRAM, q, model=model,
                            priority=prio)
                        for q in _QUANTILES
                    }
                except Exception:
                    continue
                vals = {k: v for k, v in vals.items() if not math.isnan(v)}
                if vals:
                    out.setdefault(_PRIORITY_HISTOGRAM, {}).setdefault(
                        model, {})[prio] = vals
    return out


def serving_snapshot(container) -> dict:
    """Structured state of the inference plane (the /debug/serving body)."""
    # the runtime fingerprint — the SAME dict a capture bundle's header
    # snapshots (jax/backend/device kind+count, armed GOFR_ML_* knobs)
    from .ml.capture import runtime_fingerprint

    snap: dict = {"ts": time.time(), "runtime": runtime_fingerprint()}
    ml = getattr(container, "ml", None)
    if ml is not None and hasattr(ml, "serving_snapshot"):
        snap.update(ml.serving_snapshot())
        names = list(snap.get("models", {})) + list(snap.get("llms", {}))
    else:
        snap.update({"models": {}, "llms": {}})
        names = []
    manager = container.metrics_manager
    run = getattr(manager, "run_samplers", None)
    if run is not None:
        run()  # queue depths / HBM gauges current, not stale
    snap["percentiles"] = _histogram_percentiles(manager, names)
    return snap


def _run_profile_capture(trace_dir: str, seconds: float) -> None:
    """Blocking capture, run off the event loop. Kept as a module-level
    seam so tests can monkeypatch it where ``jax.profiler`` has no
    backend to trace; the body is the auto-profiler's capture (ONE
    start/sleep/stop implementation for both profiler paths)."""
    from .flight_recorder import _capture_profile_trace

    _capture_profile_trace(trace_dir, seconds)


def _zip_dir(root: str) -> bytes:
    from .flight_recorder import zip_dir_bytes

    data, _truncated = zip_dir_bytes(root)  # manual capture: uncapped
    return data


def register_debug_routes(app, aio_app: web.Application) -> None:
    """Mount /debug/serving and /debug/profile on the HTTP server. Always
    on (like /metrics): they answer from in-process state, and they sit
    behind whatever auth middleware the app enabled."""

    async def serving_handler(_: web.Request) -> web.Response:
        return web.json_response({"data": serving_snapshot(app.container)})

    async def profile_handler(request: web.Request) -> web.Response:
        try:
            seconds = float(request.query.get("seconds", "2"))
        except ValueError:
            return web.json_response(
                {"error": {"message": "seconds must be a number"}}, status=400)
        if not 0 < seconds <= MAX_PROFILE_SECONDS:
            return web.json_response(
                {"error": {"message":
                           f"seconds must be in (0, {MAX_PROFILE_SECONDS:g}]"}},
                status=400)
        if not _profile_lock.acquire(blocking=False):
            return web.json_response(
                {"error": {"message": "a profile capture is already running"}},
                status=409)
        try:
            trace_dir = tempfile.mkdtemp(prefix="gofr-profile-")
            loop = asyncio.get_running_loop()
            capture = loop.run_in_executor(
                None, _run_profile_capture, trace_dir, seconds)
        except BaseException:
            _profile_lock.release()
            raise
        # the lock must outlive THIS handler: a client disconnect cancels the
        # coroutine, but the capture thread keeps running — and the profiler
        # is process-global, so the next capture must keep seeing 409 until
        # this one actually stops. Release from the executor future instead
        # of a finally here.
        capture.add_done_callback(lambda _: _profile_lock.release())
        try:
            await asyncio.shield(capture)
            body = _zip_dir(trace_dir)
        except asyncio.CancelledError:
            capture.add_done_callback(
                lambda _: shutil.rmtree(trace_dir, ignore_errors=True))
            raise
        except Exception as exc:
            shutil.rmtree(trace_dir, ignore_errors=True)
            app.logger.errorf("profile capture failed: %s", exc)
            return web.json_response(
                {"error": {"message": f"profile capture failed: {exc}"}},
                status=503)
        shutil.rmtree(trace_dir, ignore_errors=True)
        return web.Response(
            body=body,
            content_type="application/zip",
            headers={"Content-Disposition":
                     'attachment; filename="jax-trace.zip"'},
        )

    async def events_handler(request: web.Request) -> web.Response:
        # lazy import: flight_recorder is stdlib-only, but going through
        # the gofr_tpu.ml package at module scope would cost every app
        # jax's import time at startup
        from .flight_recorder import event_log

        try:
            since = int(request.query.get("since", "0"))
            limit = int(request.query.get("limit", "256"))
        except ValueError:
            return web.json_response(
                {"error": {"message": "since/limit must be integers"}},
                status=400)
        if limit < 1:
            return web.json_response(
                {"error": {"message": "limit must be >= 1"}}, status=400)
        # kind= is multi-value: repeatable (?kind=a&kind=b) and/or
        # comma-separated (?kind=a,b) — one incident query can follow a
        # request across admit/route/shed without N polls
        kinds = [k for raw in request.query.getall("kind", [])
                 for k in raw.split(",") if k]
        return web.json_response({"data": event_log().query(
            since=since, model=request.query.get("model") or None,
            kind=tuple(kinds) or None,
            rid=request.query.get("rid") or None, limit=limit)})

    async def requests_handler(_: web.Request) -> web.Response:
        from .ml.journey import journey_log

        log = journey_log()
        if log is None:
            return web.json_response(
                {"data": {"enabled": False,
                          "reason": "GOFR_ML_JOURNEY=0"}})
        data = log.snapshot()
        data["enabled"] = True
        return web.json_response({"data": data})

    async def request_handler(request: web.Request) -> web.Response:
        from .ml.journey import journey_log

        log = journey_log()
        rid = request.match_info["rid"]
        journey = log.get(rid) if log is not None else None
        if journey is None:
            return web.json_response(
                {"error": {"message": f"unknown request id {rid!r}"
                           + (" (journeys disabled: GOFR_ML_JOURNEY=0)"
                              if log is None else "")}},
                status=404)
        return web.json_response({"data": journey.snapshot()})

    async def goodput_handler(_: web.Request) -> web.Response:
        from .ml.goodput import goodput_ledger

        ledger = goodput_ledger()
        if ledger is None:
            return web.json_response(
                {"data": {"enabled": False,
                          "reason": "GOFR_ML_GOODPUT=0"}})
        data = ledger.snapshot()
        data["enabled"] = True
        return web.json_response({"data": data})

    async def programs_handler(request: web.Request) -> web.Response:
        ml = getattr(app.container, "ml", None)
        if ml is None or not hasattr(ml, "programs_snapshot"):
            return web.json_response(
                {"data": {"models": {}}})
        cost = request.query.get("cost", "1") != "0"
        # cost analysis re-lowers each program once (cached after) —
        # debug-endpoint work; keep it off the event loop
        loop = asyncio.get_running_loop()
        data = await loop.run_in_executor(
            None, lambda: ml.programs_snapshot(cost=cost))
        return web.json_response({"data": data})

    async def autoprofile_list_handler(_: web.Request) -> web.Response:
        from .flight_recorder import autoprof_enabled, profile_vault

        return web.json_response({"data": {
            "enabled": autoprof_enabled(),
            "captures": profile_vault().list(),
        }})

    async def autoprofile_handler(request: web.Request) -> web.Response:
        from .flight_recorder import profile_vault

        profile_id = request.match_info["profile_id"]
        bundle = profile_vault().get(profile_id)
        if bundle is None:
            return web.json_response(
                {"error": {"message":
                           f"unknown profile id {profile_id!r}"}},
                status=404)
        return web.Response(
            body=bundle["data"],
            content_type="application/zip",
            headers={"Content-Disposition":
                     f'attachment; filename="{profile_id}.zip"'},
        )

    async def capture_handler(request: web.Request) -> web.Response:
        from .ml.capture import traffic_capture

        cap = traffic_capture()
        if cap is None:
            return web.json_response(
                {"data": {"enabled": False,
                          "reason": "GOFR_ML_CAPTURE unset or 0"}})
        rid = request.query.get("rid") or None
        if rid is not None and cap.get(rid) is None:
            return web.json_response(
                {"error": {"message": f"unknown request id {rid!r}"}},
                status=404)
        # encode() walks the bounded ring and packs token arrays — debug
        # work, kept off the event loop like the programs snapshot
        loop = asyncio.get_running_loop()
        body = await loop.run_in_executor(None,
                                          lambda: cap.encode(rid=rid))
        name = f"capture-{rid}.gfrb" if rid is not None else "capture.gfrb"
        return web.Response(
            body=body,
            content_type="application/octet-stream",
            headers={"Content-Disposition":
                     f'attachment; filename="{name}"'},
        )

    async def crash_list_handler(_: web.Request) -> web.Response:
        from .flight_recorder import crash_vault

        return web.json_response(
            {"data": {"crashes": crash_vault().list()}})

    async def crash_handler(request: web.Request) -> web.Response:
        from .flight_recorder import crash_vault

        crash_id = request.match_info["crash_id"]
        bundle = crash_vault().get(crash_id)
        if bundle is None:
            return web.json_response(
                {"error": {"message": f"unknown crash id {crash_id!r}"}},
                status=404)
        return web.json_response({"data": bundle})

    aio_app.router.add_get("/debug/serving", serving_handler)
    aio_app.router.add_get("/debug/profile", profile_handler)
    # /profile/auto must register BEFORE aiohttp ever sees a bare
    # /debug/profile/{...}; these are literal paths, so order is only
    # cosmetic — kept adjacent for readability
    aio_app.router.add_get("/debug/profile/auto", autoprofile_list_handler)
    aio_app.router.add_get("/debug/profile/auto/{profile_id}",
                           autoprofile_handler)
    aio_app.router.add_get("/debug/goodput", goodput_handler)
    aio_app.router.add_get("/debug/capture", capture_handler)
    aio_app.router.add_get("/debug/programs", programs_handler)
    aio_app.router.add_get("/debug/events", events_handler)
    aio_app.router.add_get("/debug/crash", crash_list_handler)
    aio_app.router.add_get("/debug/crash/{crash_id}", crash_handler)
    aio_app.router.add_get("/debug/requests", requests_handler)
    aio_app.router.add_get("/debug/requests/{rid}", request_handler)

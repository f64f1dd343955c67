"""Serving flight recorder: stall attribution, fleet events, crash forensics.

The serving plane grew a scheduler (scheduler.py), a tiered KV cache
(kv_offload.py), a watchdog (llm.py) and a replica router (replica.py) —
and with them, failure and latency stories that span several components: a
routed, spilled, rerouted request used to show up as four disconnected
counters. This module is the shared memory those components write into, so
one curl can answer "where did the step time go?" and "what happened right
before the crash?":

- ``DispatchRecorder`` — per-dispatch **stall attribution**. The serving
  thread wraps its work in ``with rec.phase(name)`` (queue pop, scheduler
  decide, batch assemble, program launch, async-D2H issue, device wait,
  emit): a span with a start and an end on ``perf_counter`` that is also
  a ``gofr.serve.<phase>`` annotation in a ``jax.profiler`` capture.
  Every device dispatch commits one record (when, what program, how many
  rows, its spans) into the process-global ``dispatch_log()``, the
  unattributed remainder recorded honestly as ``other`` — the phases of
  a record always sum to its wall time. Rolling per-phase shares (over
  the recorder's newest records) feed the ``llms.<name>.stalls`` block of
  ``/debug/serving`` and the ``app_llm_dispatch_phase_seconds{phase=…}``
  histogram; ``top_stall`` names the top *host-side* phase so ROADMAP-3c
  work knows what to kill first. ``GOFR_ML_FLIGHT_RECORDER=0`` disables
  recording entirely (the instrumented sites guard on ``is not None``).
- ``EventLog`` — the **fleet event log**: one process-global bounded ring
  of typed serving events (admit, route, failover, spill, restore, shed,
  deadline, crash, recover, dead, drain, scale and canary
  promote/rollback) written by ``LLMServer``,
  ``ReplicaPool``, ``RadixPrefixCache`` and ``HostKVStore``, and read by
  ``GET /debug/events?since=<cursor>&model=…``. Appends are O(1) under a
  tiny lock; the ring (``GOFR_ML_EVENT_RING``, default 2048) bounds
  memory, and the monotonic ``seq`` cursor lets a poller resume without
  missing or re-reading events that are still in the ring.
- ``CrashVault`` — **crash forensics**: when the watchdog trips (or a
  replica dies), the server snapshots the triggering event, the last N
  fleet events, the scheduler/queue state and the in-flight slot table
  into an in-memory bundle served at ``GET /debug/crash/<id>`` — the
  postmortem survives the recovery, so reading it never needs a live
  repro.
- ``AutoProfiler`` + ``ProfileVault`` — the **anomaly-triggered
  auto-profiler**: a rolling baseline over the DispatchRecorder's
  step wall and phase shares; when a regression trips (step p50 past
  ``GOFR_ML_AUTOPROF_MULT`` × baseline, or a host phase's share jumping
  by more than 25 points) it captures a bounded ``jax.profiler`` trace
  on a background thread into an 8-deep vault served at
  ``GET /debug/profile/auto`` — the trace of the slowdown exists by the
  time a human reads the alert, instead of asking them to reproduce it.
  Cooldown (``GOFR_ML_AUTOPROF_COOLDOWN_S``) bounds capture frequency;
  ``GOFR_ML_AUTOPROF=0`` disables under the same is-not-None
  zero-overhead contract as the recorder itself.

The per-REQUEST axis of the same story — "where did this request's
TTFT/TPOT budget go, across the fleet?" — lives in the sibling journey
tracer (``ml/journey.py``): dispatch records carry the rids they served
and journey marks carry the dispatch seq, so forensics pivot both ways.

Everything here is host-side stdlib — jax is imported lazily (a capture,
a phase's annotation), so the debug endpoints import this module without
paying the ml package's startup cost.
"""

from __future__ import annotations

import collections
import contextlib
import io
import itertools
import os
import shutil
import tempfile
import threading
import time
import zipfile

__all__ = ["PHASES", "DispatchRecorder", "DispatchLog", "EventLog",
           "CrashVault", "AutoProfiler", "ProfileVault", "PROFILE_LOCK",
           "phase", "dispatch_log", "event_log", "crash_vault",
           "profile_vault",
           "recorder_enabled", "autoprof_enabled", "zip_dir_bytes"]

# the jax profiler is process-global state: ONE capture at a time, ever —
# shared by the manual /debug/profile endpoint and the auto-profiler, so
# the two can never corrupt each other's trace
PROFILE_LOCK = threading.Lock()

# the dispatch-phase taxonomy (the label set of
# app_llm_dispatch_phase_seconds; docs/tpu/observability.md says what each
# phase covers). ``route`` is recorded by the replica pool's router,
# everything else by one LLMServer serving thread; ``other`` is the honest
# remainder: wall time of a pass no instrumented site claimed (host
# bookkeeping loops, GC, OS scheduling).
PHASES = ("queue_pop", "decide", "assemble", "sp_prefill", "launch",
          "d2h_issue", "device_wait", "emit", "route", "ship", "land",
          "other")
# phases that burn HOST time; ``device_wait`` is the one phase where the
# host is merely blocked on device compute, so it never names a stall
_HOST_PHASES = tuple(p for p in PHASES if p != "device_wait")


def recorder_enabled() -> bool:
    """``GOFR_ML_FLIGHT_RECORDER`` (default on): 0 disables the dispatch
    recorder (what it costs when on: PERF.md §6, PR 27)."""
    return os.environ.get("GOFR_ML_FLIGHT_RECORDER", "1").strip() != "0"


_NO_PHASE = contextlib.nullcontext()  # the one no-op context, recorder off
# a pass that launched no decode program (the tail flush) commits as this
_NO_LAUNCH = ("flush", 0, 0)
_TraceAnnotation = None  # jax.profiler's, imported at the first phase


def phase(rec: "DispatchRecorder | None", name: str, **meta):
    """``rec.phase(name, **meta)``, or the shared no-op context where the
    recorder is off (``GOFR_ML_FLIGHT_RECORDER=0``: nothing is constructed)."""
    return _NO_PHASE if rec is None else rec.phase(name, **meta)


class _Span:
    """One phase of the current pass, open between ``with`` and its end;
    in a profiler capture an event on the serving thread's host line."""

    __slots__ = ("rec", "name", "t0", "t1", "inner_s", "ann")

    def __init__(self, rec: "DispatchRecorder", name: str, meta: dict):
        global _TraceAnnotation
        if _TraceAnnotation is None:
            from jax.profiler import TraceAnnotation as _TraceAnnotation
        self.rec, self.name, self.inner_s = rec, name, 0.0
        self.ann = _TraceAnnotation("gofr.serve." + name, **meta)

    def __enter__(self) -> "_Span":
        self.ann.__enter__()
        self.t0 = self.t1 = time.perf_counter()
        self.rec._open.append(self)
        self.rec._spans.append(self)
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        rec, took = self.rec, self.t1 - self.t0
        rec._open.pop()
        if rec._open:
            rec._open[-1].inner_s += took
        # self time: the interval less what its child spans cover, so a
        # record's phases sum to (never past) its wall
        rec.note(self.name, max(0.0, took - self.inner_s))


class DispatchRecorder:
    """Per-dispatch phase breakdown for one serving core.

    The serving thread wraps its work in ``with rec.phase(name)`` and
    calls ``commit()`` once per device dispatch; ``reset()`` discards a
    pure idle pass (an idle server's poll wait is not a stall of any
    dispatch). Records go to ``dispatch_log()``; ``snapshot()`` and
    ``tail()`` read this recorder's newest ``ring`` of them, any thread.
    """

    _ids = itertools.count(1)

    def __init__(self, *, model: str = "llm", metrics=None,
                 ring: int = 256) -> None:
        self.model = model
        self._metrics = metrics
        self._rolling = ring
        self._key = next(self._ids)  # this recorder's records in the log
        # guards the lifetime totals only — note() and phase() are
        # serving-thread-private and take no lock at all
        self._lock = threading.Lock()
        self._pending: dict[str, float] = {}
        self._spans: list[_Span] = []  # this pass's, in order of start
        self._open: list[_Span] = []   # the nesting stack
        self._pending_rids: list[str] = []  # rids served this pass
        # the decode programs this pass launched: the last one's kind,
        # their steps, their rows x steps (see phase)
        self._pending_launch = _NO_LAUNCH
        # the prefill programs this pass launched: their launch metadata
        self._pending_prefills: list[dict] = []
        # (planned K, realized steps, windows settled), see note_window
        self._pending_window: tuple[int, int, int] | None = None
        self._pending_overlap = 0  # see note_overlap
        # host-idle estimate state, see note_settle
        self._pending_busy = 0.0
        self._pending_settled = 0
        self._exec_ema: float | None = None  # device s per planned step
        self._anchor: float | None = None  # pass start (perf_counter)
        self.dispatches = 0
        self.totals = dict.fromkeys(PHASES, 0.0)  # lifetime seconds
        # optional per-commit observer (the auto-profiler's feed): called
        # with (wall_s, phases) after each committed record. None costs
        # one attribute test — the GOFR_ML_AUTOPROF=0 contract.
        self.observer = None

    @property
    def pending_device_work(self) -> bool:
        """True when the current pass actually touched the device (a
        dispatch, a blocking read-back, or token emission) — the gate for
        the serve loop's tail-flush commit, so idle passes that merely
        glanced at an empty queue never pollute the dispatch log."""
        return any(k in self._pending
                   for k in ("launch", "d2h_issue", "device_wait", "emit",
                             "ship", "land"))

    def phase(self, name: str, **meta) -> _Span:
        """``with rec.phase(name):`` stamps one span of the current pass:
        start and end on ``perf_counter``, its self time added as
        ``note()`` would, and a ``gofr.serve.<name>`` profiler annotation
        (``meta`` its metadata). Spans nest; serving-thread only.

        The ``launch`` of a decode program says what runs, ``kind=``
        (``chunk``, ``mini``, ``window``, ``spec``, ``specwin``),
        ``steps=`` and ``rows=`` (the rows producing tokens): they go on
        the record and, with the ``seq`` the pass will commit under, on
        the annotation. The ``launch`` of a whole-prompt prefill program
        says ``kind="prefill"``, ``rows=``, ``seq=`` (here the program's
        sequence length) and ``real_tokens=`` (the prompt's): the record
        lists them under ``prefills``, one entry a program."""
        if "steps" in meta:
            steps, rows = int(meta["steps"]), int(meta["rows"])
            _, steps0, row_steps0 = self._pending_launch
            self._pending_launch = (meta["kind"], steps0 + steps,
                                    row_steps0 + rows * steps)
            meta.update(seq=self.dispatches + 1, steps=steps, rows=rows)
        elif meta.get("kind") == "prefill":
            self._pending_prefills.append(meta)
        return _Span(self, name, meta)

    def note(self, phase: str, seconds: float) -> None:
        """Attribute ``seconds`` of the current pass to ``phase``: the
        primitive under ``phase()``. Serving-thread only, like every
        ``note_*``; one dict update, no lock."""
        self._pending[phase] = self._pending.get(phase, 0.0) + seconds

    def note_rid(self, rid: str) -> None:
        """Tag the current pass with a request id it served: forensics
        pivot dispatch→requests (journeys carry the other direction)."""
        self._pending_rids.append(rid)

    def note_window(self, k: int, realized: int) -> None:
        """Tag the current pass with a fused decode window it drained:
        ``k`` planned device steps, ``realized`` steps the early-exit
        masks actually ran. A pass can settle MORE than one window (the
        double-buffered pipeline drains both at a barrier), so calls
        accumulate into the committed record."""
        if self._pending_window is None:
            self._pending_window = (int(k), int(realized), 1)
        else:
            k0, r0, n0 = self._pending_window
            self._pending_window = (k0 + int(k), r0 + int(realized), n0 + 1)

    def note_overlap(self, depth: int) -> None:
        """Tag the current pass with the in-flight depth its dispatch
        launched on top of (1 = the classic lag-one pipeline, 2 =
        double-buffered under GOFR_ML_PIPELINE); the record keeps the max."""
        if depth > self._pending_overlap:
            self._pending_overlap = depth

    def note_settle(self, span_s: float, depth0: int, steps: int,
                    wait_s: float) -> None:
        """One in-flight dispatch settled: ``span_s`` seconds from its
        launch to now, ``depth0`` dispatches already outstanding when it
        launched, ``steps`` planned device positions, ``wait_s`` the
        blocking read-back tail just measured. Feeds the host-idle
        estimate: a settle that actually BLOCKED on a dispatch launched
        onto an empty device pins the execution time exactly (span =
        device run time), calibrating an EMA of device seconds per
        planned step; every settle then credits min(span, max(wait,
        ema*steps)) estimated device-busy seconds to the current pass."""
        if wait_s > 1e-6 and depth0 == 0:
            per = span_s / max(1, steps)
            self._exec_ema = (per if self._exec_ema is None
                              else 0.8 * self._exec_ema + 0.2 * per)
        est = (wait_s if self._exec_ema is None
               else max(wait_s, self._exec_ema * max(1, steps)))
        self._pending_busy += min(span_s, est)
        self._pending_settled += 1

    def reset(self) -> None:
        """Drop the current pass unrecorded (idle poll: no dispatch to
        attribute the wait to) and re-anchor the wall clock."""
        self._pending.clear()
        self._spans.clear()
        self._pending_rids.clear()
        self._pending_launch = _NO_LAUNCH
        self._pending_prefills = []
        self._pending_window = None
        self._pending_overlap = 0
        self._pending_busy = 0.0
        self._pending_settled = 0
        self._anchor = time.perf_counter()

    def commit(self) -> None:
        """Close one dispatch record: when the pass ran (``t0``, ``t1``),
        its spans, what it launched, and the phases noted since the last
        commit/reset plus the unattributed remainder as ``other``, so a
        record's phases always sum to its wall time."""
        now = time.perf_counter()
        attributed = sum(self._pending.values())
        wall = (now - self._anchor if self._anchor is not None
                else attributed)
        phases = dict(self._pending)
        phases["other"] = max(0.0, wall - attributed)
        # rows: the mean over the pass's decode steps, so that records sum
        # to rows x steps whether a pass launched one program or several
        kind, steps, row_steps = self._pending_launch
        rows = row_steps / steps if steps else 0
        if rows == int(rows):
            rows = int(rows)
        rec = {"model": self.model, "t0": now - wall, "t1": now,
               "wall_s": wall, "kind": kind, "steps": steps, "rows": rows,
               "phases": phases,
               "spans": [(s.name, s.t0, s.t1) for s in self._spans]}
        if self._pending_rids:
            # stable de-dup (a slot may burst twice in one pass): the
            # record names every request this dispatch served
            rec["rids"] = list(dict.fromkeys(self._pending_rids))
        if self._pending_prefills:
            rec["prefills"] = self._pending_prefills
        if self._pending_window is not None:
            k, realized, n = self._pending_window
            rec["window"] = {"k": k, "realized": realized, "n": n}
        if self._pending_overlap:
            rec["overlap"] = self._pending_overlap
        if self._pending_settled:
            # the host-idle estimate's numerator, clipped at wall: a span
            # begun in an earlier pass never claims more than this record
            rec["busy_s"] = min(self._pending_busy, wall)
        with self._lock:
            self.dispatches += 1
            rec["seq"] = self.dispatches  # the journey marks' pivot key
            for name, v in phases.items():
                self.totals[name] = self.totals.get(name, 0.0) + v
        _DISPATCHES.append(self._key, rec)
        self.reset()
        self._anchor = now
        obs = self.observer
        if obs is not None:
            try:
                obs(wall, phases)
            except Exception:
                pass  # a broken observer must never fail a dispatch
        m = self._metrics
        if m is not None:
            try:
                for name, v in phases.items():
                    if v > 0.0:
                        m.record_histogram("app_llm_dispatch_phase_seconds",
                                           v, model=self.model, phase=name)
            except Exception:
                pass  # bare managers in tests: recording stays optional

    def tail(self, n: int = 16) -> list[dict]:
        """The newest ``n`` raw dispatch records — crash bundles carry
        these so a postmortem can pivot the victims' journeys onto the
        exact dispatches that ran them. Safe from any thread."""
        records = _DISPATCHES.records(self._key, min(n, self._rolling))
        return [{**r, "wall_s": round(r["wall_s"], 6),
                 "phases": {k: round(v, 6)
                            for k, v in r["phases"].items()}}
                for r in records]

    def snapshot(self) -> dict:
        """The ``stalls`` block of ``/debug/serving``: rolling per-phase
        seconds and share-of-wall over this recorder's newest records,
        the top host-side phase by share, and how much of the wall the
        instrumented phases (all but ``other``) explained."""
        records = _DISPATCHES.records(self._key, self._rolling)
        with self._lock:
            dispatches = self.dispatches
            totals = {name: round(v, 6)
                      for name, v in self.totals.items() if v > 0.0}
        wall = sum(r["wall_s"] for r in records)
        sums: dict[str, float] = {}
        for r in records:
            for name, v in r["phases"].items():
                sums[name] = sums.get(name, 0.0) + v
        phases = {
            name: {"s": round(v, 6),
                   "share": round(v / wall, 4) if wall > 0 else 0.0}
            for name, v in sorted(sums.items(), key=lambda kv: -kv[1])
        }
        host = {n: v for n, v in sums.items() if n in _HOST_PHASES}
        top = max(host, key=host.get) if host and wall > 0 else None
        attributed = sum(v for n, v in sums.items() if n != "other")
        # fused-window dim over the ring: how many dispatches were window
        # launches, the planned K vs what the early-exit masks realized —
        # named decode_window because "window" above is the ROLLING ring
        # window of this snapshot, a different thing entirely
        win_recs = [r["window"] for r in records if "window" in r]
        decode_window = None
        if win_recs:
            planned = sum(w["k"] for w in win_recs)
            realized = sum(w["realized"] for w in win_recs)
            n_windows = sum(w.get("n", 1) for w in win_recs)
            decode_window = {
                "windows": n_windows,
                "mean_k": round(planned / n_windows, 2),
                "mean_realized": round(realized / n_windows, 2),
                "realized_share": (round(realized / planned, 4)
                                   if planned else None),
            }
        # host-idle estimate: the settles' estimated device-busy seconds
        # against the wall. An ESTIMATE on the host's clock: prefill
        # dispatches aren't credited, so it reads high on admission-heavy
        # windows; the pipeline A/B compares like against like. The
        # device's own idle share comes from a profiler trace only
        busy = sum(r.get("busy_s", 0.0) for r in records)
        overlapped = sum(1 for r in records if r.get("overlap", 0) >= 2)
        return {
            "dispatches": dispatches,
            "window": {
                "records": len(records),
                "wall_s": round(wall, 6),
                "per_dispatch_ms": (round(wall / len(records) * 1e3, 3)
                                    if records else None),
                "phases": phases,
            },
            "top_stall": top,
            "decode_window": decode_window,
            "host_idle_estimate": (round(max(0.0, 1.0 - busy / wall), 4)
                                   if wall > 0 and busy > 0.0 else None),
            "overlapped_dispatches": overlapped,
            "attributed_share": (round(attributed / wall, 4)
                                 if wall > 0 else None),
            # lifetime per-phase seconds: the ring answers "what's slow
            # NOW", this answers "where has the wall gone since boot"
            "totals_s": totals,
        }


class EventLog:
    """Bounded ring of typed serving events with a monotonic cursor.

    The event-kind vocabulary is documented in
    docs/tpu/observability.md (the fleet narration table): admission
    and routing, replica lifecycle, KV movement, elastic scaling,
    canary promotion — and the federation membership kinds
    (``peer_up`` / ``peer_suspect`` / ``peer_dead`` / ``host_join`` /
    ``host_leave``) that narrate the cross-host fleet (federation.py).
    """

    def __init__(self, capacity: int | None = None) -> None:
        if capacity is None:
            raw = os.environ.get("GOFR_ML_EVENT_RING", "").strip()
            try:
                capacity = int(raw) if raw else 2048
            except ValueError:
                capacity = 2048
        self._buf: collections.deque[dict] = collections.deque(
            maxlen=max(16, capacity))
        self._lock = threading.Lock()
        self._seq = 0
        # events silently overwritten by ring churn: consumers polling
        # with since= need to know their cursor gapped (the ``dropped``
        # field of /debug/events + app_ml_events_dropped_total)
        self.dropped = 0

    @property
    def cursor(self) -> int:
        """Seq of the newest event (pass it back as ``since=``)."""
        with self._lock:
            return self._seq

    def emit(self, kind: str, model: str | None = None, **data) -> dict:
        """Append one event; returns the stored record (its ``seq`` is
        the cursor callers quote, e.g. a crash bundle's trigger)."""
        with self._lock:
            self._seq += 1
            rec = {"seq": self._seq, "ts": round(time.time(), 6),
                   "kind": kind, "model": model, **data}
            if len(self._buf) == self._buf.maxlen:
                self.dropped += 1  # the append below overwrites the oldest
            self._buf.append(rec)
            return rec

    @staticmethod
    def _model_match(ev_model: str | None, want: str) -> bool:
        # "chat" also matches its replica cores "chat/0", "chat/1", …
        return (ev_model == want
                or (ev_model is not None and ev_model.startswith(want + "/")))

    def query(self, since: int = 0, *, model: str | None = None,
              kind=None, rid: str | None = None,
              limit: int = 256) -> dict:
        """Events with ``seq > since`` (oldest first), optionally filtered
        by model (a pool name matches its replica cores too), kind (one
        name or any collection of names — the multi-value ``kind=`` of
        /debug/events), and rid (the request-journey id stamped on
        admit/shed/deadline/route/failover/kv_ship/kv_land events).
        ``cursor`` is what the next poll passes as ``since=``: past the
        whole ring normally, or the last returned event when ``limit``
        truncated the page (so pagination never skips events).
        ``dropped`` counts events the ring has overwritten since boot —
        a consumer whose poll cadence lost to churn sees it move."""
        with self._lock:
            events = [e for e in self._buf if e["seq"] > since]
            cursor = self._seq
            dropped = self.dropped
        if model is not None:
            events = [e for e in events
                      if self._model_match(e.get("model"), model)]
        if kind is not None:
            kinds = {kind} if isinstance(kind, str) else set(kind)
            events = [e for e in events if e["kind"] in kinds]
        if rid is not None:
            events = [e for e in events if e.get("rid") == rid]
        truncated = len(events) > max(1, limit)
        if truncated:
            events = events[:max(1, limit)]
            cursor = events[-1]["seq"]
        return {"cursor": cursor, "truncated": truncated,
                "dropped": dropped, "events": events}

    def tail(self, n: int = 128) -> list[dict]:
        """Newest ``n`` events, oldest first (crash-bundle context)."""
        with self._lock:
            return list(self._buf)[-max(0, n):]


class DispatchLog:
    """The process's committed dispatch records, and no live server needed
    to read them: one bounded ring a recorder behind one lock, so a busy
    replica never pushes a quiet one's records out. 2,048 records are two
    minutes at the fastest dispatch rate measured; the rings of the newest
    16 recorders stay (an elastic fleet's retired replicas roll off)."""

    def __init__(self, per_recorder: int = 2048, recorders: int = 16) -> None:
        self._rings: dict[int, collections.deque[dict]] = {}
        self._per_recorder, self._recorders = per_recorder, recorders
        self._lock = threading.Lock()

    def append(self, key: int, rec: dict) -> None:
        with self._lock:
            ring = self._rings.get(key)
            if ring is None:
                while len(self._rings) >= self._recorders:
                    del self._rings[next(iter(self._rings))]  # the oldest
                ring = self._rings[key] = collections.deque(
                    maxlen=self._per_recorder)
            ring.append(rec)

    def records(self, key: int | None = None,
                last: int | None = None) -> list[dict]:
        """Every recorder's records, each recorder's oldest first; or one
        recorder's (``key``) newest ``last``, oldest first."""
        with self._lock:
            if key is None:
                return [r for ring in self._rings.values() for r in ring]
            newest = itertools.islice(reversed(self._rings.get(key, ())), last)
            return list(newest)[::-1]


class CrashVault:
    """Bounded in-memory store of crash bundles, keyed by id."""

    def __init__(self, capacity: int = 8) -> None:
        self._bundles: collections.OrderedDict[str, dict] = \
            collections.OrderedDict()
        self._capacity = max(1, capacity)
        self._lock = threading.Lock()
        self._n = 0

    def capture(self, *, model: str, trigger: dict, state: dict,
                events: list[dict], capture: dict | None = None) -> str:
        """Store one bundle; returns its id (``/debug/crash/<id>``).
        Oldest bundles roll off past the capacity — postmortems read the
        bundle soon after the incident, not weeks later. ``capture`` is
        the traffic-capture tail (ml/capture.py export, present only
        when ``GOFR_ML_CAPTURE`` is armed): it lands under
        ``state.capture`` so a saved crash body feeds
        ``python -m gofr_tpu.ml.replay`` directly."""
        with self._lock:
            self._n += 1
            # replica core names carry a slash ("chat/0") that would split
            # the URL path — flatten it for the id, keep it in the body
            crash_id = f"{model.replace('/', '-')}-{self._n}"
            if capture is not None:
                state = {**state, "capture": capture}
            self._bundles[crash_id] = {
                "id": crash_id,
                "at": round(time.time(), 6),
                "model": model,
                "trigger": trigger,
                "state": state,
                "events": events,
            }
            while len(self._bundles) > self._capacity:
                self._bundles.popitem(last=False)
            return crash_id

    def get(self, crash_id: str) -> dict | None:
        with self._lock:
            return self._bundles.get(crash_id)

    def list(self) -> list[dict]:
        """Summaries, oldest first (full bundles via ``get``)."""
        with self._lock:
            return [{"id": b["id"], "at": b["at"], "model": b["model"],
                     "error": b["trigger"].get("error")}
                    for b in self._bundles.values()]


def autoprof_enabled() -> bool:
    """``GOFR_ML_AUTOPROF`` (default on): 0 disables the auto-profiler —
    the recorder's ``observer`` stays ``None`` and commits do zero extra
    work (same contract as ``GOFR_ML_FLIGHT_RECORDER``)."""
    return os.environ.get("GOFR_ML_AUTOPROF", "").strip() != "0"


def _env_float(name: str, default: float, *, minimum: float,
               maximum: float = float("inf")) -> float:
    """Loudly-validated float env knob (the PR-6 drain pattern): a
    malformed threshold must fail the boot, not silently profile never
    (or constantly)."""
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        value = float(raw)
    except ValueError:
        raise ValueError(f"{name} must be a number, got {raw!r}") from None
    if not minimum <= value <= maximum:  # NaN fails both compares
        raise ValueError(
            f"{name} must be in [{minimum:g}, {maximum:g}], got {raw!r}")
    return value


def zip_dir_bytes(root: str, max_bytes: int | None = None) -> tuple[bytes, bool]:
    """Zip a directory tree into memory, stopping once the archive would
    exceed ``max_bytes`` (profiler traces can be large; a bounded vault
    must never eat the heap). Returns ``(data, truncated)``."""
    buf = io.BytesIO()
    truncated = False
    with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as zf:
        for base, _, files in os.walk(root):
            for fname in sorted(files):
                full = os.path.join(base, fname)
                if max_bytes is not None:
                    try:
                        size = os.path.getsize(full)
                    except OSError:
                        continue
                    # guard BEFORE deflating: one giant .xplane.pb must
                    # not blow the heap the cap exists to bound (deflate
                    # compresses, so raw size is a conservative bound)
                    if buf.tell() + size > max_bytes:
                        truncated = True
                        continue
                zf.write(full, os.path.relpath(full, root))
    return buf.getvalue(), truncated


def _capture_profile_trace(trace_dir: str, seconds: float) -> None:
    """Blocking jax.profiler capture (device + host timelines), run on
    the auto-profiler's background thread. Module-level so tests can
    monkeypatch it where jax has no backend worth tracing — the same
    seam as ``debug._run_profile_capture``."""
    import jax

    jax.profiler.start_trace(trace_dir)
    try:
        time.sleep(seconds)
    finally:
        jax.profiler.stop_trace()


class ProfileVault:
    """Bounded in-memory store of auto-captured profile bundles, keyed
    by id — the CrashVault pattern applied to ``jax.profiler`` zips."""

    def __init__(self, capacity: int = 8) -> None:
        self._bundles: collections.OrderedDict[str, dict] = \
            collections.OrderedDict()
        self._capacity = max(1, capacity)
        self._lock = threading.Lock()
        self._n = 0

    def capture(self, *, model: str, trigger: dict, data: bytes,
                truncated: bool = False) -> str:
        with self._lock:
            self._n += 1
            profile_id = f"{model.replace('/', '-')}-{self._n}"
            self._bundles[profile_id] = {
                "id": profile_id,
                "at": round(time.time(), 6),
                "model": model,
                "trigger": trigger,
                "bytes": len(data),
                "truncated": truncated,
                "data": data,
            }
            while len(self._bundles) > self._capacity:
                self._bundles.popitem(last=False)
            return profile_id

    def get(self, profile_id: str) -> dict | None:
        with self._lock:
            return self._bundles.get(profile_id)

    def list(self) -> list[dict]:
        """Summaries (no trace bytes), oldest first."""
        with self._lock:
            return [{k: v for k, v in b.items() if k != "data"}
                    for b in self._bundles.values()]


class AutoProfiler:
    """Anomaly-triggered profiling over one serving core's dispatches.

    Installed as the core's ``DispatchRecorder.observer``: every commit
    feeds ``observe(wall_s, phases)``. Dispatches accumulate in a short
    window; when it fills, its step-wall p50 and host-phase shares are
    compared against a rolling baseline of earlier windows. A regression
    — p50 ≥ ``multiplier`` × baseline p50, or a host phase's share of
    wall jumping by more than ``share_jump`` — spawns ONE background
    capture (``jax.profiler``, ``capture_s`` seconds, zipped and
    size-capped into the process-global :class:`ProfileVault`), emits a
    ``profile`` fleet event, and starts the cooldown. Everything on the
    serving thread is deque appends and, once per window, two small
    sorts — the capture itself never runs there.
    """

    def __init__(self, *, model: str = "llm", vault: "ProfileVault | None"
                 = None, events: "EventLog | None" = None,
                 multiplier: float | None = None,
                 cooldown_s: float | None = None,
                 capture_s: float | None = None,
                 share_jump: float = 0.25,
                 window: int = 16, baseline: int = 128,
                 min_baseline: int = 64,
                 max_bytes: int = 32 * 1024 * 1024,
                 capture_fn=None) -> None:
        self.model = model
        self._vault = vault if vault is not None else profile_vault()
        self._events = events if events is not None else event_log()
        self.multiplier = (_env_float("GOFR_ML_AUTOPROF_MULT", 2.0,
                                      minimum=1.01)
                           if multiplier is None else float(multiplier))
        self.cooldown_s = (_env_float("GOFR_ML_AUTOPROF_COOLDOWN_S", 120.0,
                                      minimum=0.0)
                           if cooldown_s is None else float(cooldown_s))
        self.capture_s = (_env_float("GOFR_ML_AUTOPROF_SECONDS", 1.0,
                                     minimum=0.05, maximum=30.0)
                          if capture_s is None else float(capture_s))
        self.share_jump = float(share_jump)
        self._win: list[tuple[float, dict]] = []
        self._win_n = max(4, int(window))
        # baseline of (wall, phases) records from PAST windows only — the
        # window under judgment never pollutes its own reference. The
        # serving thread extends it; /debug/serving snapshots read it —
        # the lock keeps a concurrent extend from crashing the iteration
        # (the PR-9 role-controller deque lesson)
        self._base_lock = threading.Lock()
        self._base: collections.deque[tuple[float, dict]] = \
            collections.deque(maxlen=max(self._win_n * 2, int(baseline)))
        self._min_baseline = max(self._win_n, int(min_baseline))
        self._max_bytes = int(max_bytes)
        self._capture_fn = (capture_fn if capture_fn is not None
                            else _capture_profile_trace)
        self._cooldown_until = 0.0
        self.dispatches = 0
        self.captures = 0
        self.failures = 0
        self.skipped_busy = 0  # trigger lost the profiler lock (manual
        # capture in flight): counted, cooldown still consumed
        self.last_trigger: dict | None = None

    # -- serving-thread side -------------------------------------------------
    def observe(self, wall_s: float, phases: dict) -> None:
        self.dispatches += 1
        self._win.append((wall_s, phases))
        if len(self._win) < self._win_n:
            return
        window, self._win = self._win, []
        with self._base_lock:
            base = list(self._base)
        trigger = self._judge(window, base) if len(base) >= \
            self._min_baseline else None
        with self._base_lock:
            self._base.extend(window)
        if trigger is not None:
            self._trigger(trigger)

    @staticmethod
    def _p50(walls: list[float]) -> float:
        ordered = sorted(walls)
        return ordered[len(ordered) // 2]

    @staticmethod
    def _shares(records) -> dict[str, float]:
        wall = sum(w for w, _ in records)
        if wall <= 0:
            return {}
        sums: dict[str, float] = {}
        for _, phases in records:
            for name, v in phases.items():
                sums[name] = sums.get(name, 0.0) + v
        return {name: v / wall for name, v in sums.items()
                if name in _HOST_PHASES}

    def _judge(self, window, base) -> dict | None:
        """Compare the just-filled window against a baseline copy; a
        dict describing the regression, or None."""
        now = time.monotonic()
        if now < self._cooldown_until:
            return None
        base_p50 = self._p50([w for w, _ in base])
        win_p50 = self._p50([w for w, _ in window])
        if base_p50 > 0 and win_p50 >= self.multiplier * base_p50:
            return {"reason": "step_ms_p50",
                    "step_ms": round(win_p50 * 1e3, 3),
                    "baseline_ms": round(base_p50 * 1e3, 3),
                    "multiplier": self.multiplier}
        base_shares = self._shares(base)
        for name, share in self._shares(window).items():
            ref = base_shares.get(name, 0.0)
            if share - ref > self.share_jump:
                return {"reason": "phase_share", "phase": name,
                        "share": round(share, 4),
                        "baseline_share": round(ref, 4),
                        "jump_points": self.share_jump}
        return None

    def _trigger(self, trigger: dict) -> None:
        """Start ONE bounded background capture; the cooldown begins now
        (capture time included), so a sustained regression produces one
        trace per cooldown window, not a trace storm."""
        self._cooldown_until = (time.monotonic() + self.cooldown_s
                                + self.capture_s)
        self.last_trigger = {**trigger, "at": round(time.time(), 3)}
        if not PROFILE_LOCK.acquire(blocking=False):
            # a manual /debug/profile capture (or another core's auto
            # capture) owns the process-global profiler right now
            self.skipped_busy += 1
            return
        try:
            t = threading.Thread(target=self._capture, args=(trigger,),
                                 daemon=True,
                                 name=f"gofr-autoprof-{self.model}")
            t.start()
        except BaseException:
            # a failed thread start (resource pressure — exactly when
            # regressions fire) must not leak the process-global
            # profiler lock: the manual endpoint would 409 forever
            PROFILE_LOCK.release()
            self.failures += 1

    # -- background capture thread ------------------------------------------
    def _capture(self, trigger: dict) -> None:
        try:
            trace_dir = tempfile.mkdtemp(prefix="gofr-autoprof-")
            try:
                self._capture_fn(trace_dir, self.capture_s)
                data, truncated = zip_dir_bytes(trace_dir, self._max_bytes)
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)
            profile_id = self._vault.capture(
                model=self.model, trigger=dict(self.last_trigger or trigger),
                data=data, truncated=truncated)
            self.captures += 1
            self._events.emit("profile", model=self.model,
                              profile_id=profile_id,
                              bytes=len(data), **trigger)
        except Exception:
            self.failures += 1
        finally:
            PROFILE_LOCK.release()

    def snapshot(self) -> dict:
        """The ``autoprof`` block of ``/debug/serving``. Safe from any
        thread (baseline copied under its lock)."""
        with self._base_lock:
            base = [w for w, _ in self._base]
        return {
            "dispatches": self.dispatches,
            "captures": self.captures,
            "failures": self.failures,
            "skipped_busy": self.skipped_busy,
            "multiplier": self.multiplier,
            "cooldown_s": self.cooldown_s,
            "capture_s": self.capture_s,
            "baseline_ms": (round(self._p50(base) * 1e3, 3)
                            if len(base) >= self._min_baseline else None),
            "cooling_down": time.monotonic() < self._cooldown_until,
            "last_trigger": self.last_trigger,
        }


# the process-global instances every serving component shares — ONE fleet
# event stream, ONE dispatch log, ONE crash vault, and ONE profile vault
# per process, like the metrics registry
_EVENTS = EventLog()
_DISPATCHES = DispatchLog()
_CRASHES = CrashVault()
_PROFILES = ProfileVault()


def event_log() -> EventLog:
    return _EVENTS


def dispatch_log() -> DispatchLog:
    return _DISPATCHES


def crash_vault() -> CrashVault:
    return _CRASHES


def profile_vault() -> ProfileVault:
    return _PROFILES

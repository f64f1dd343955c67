"""Program & compile telemetry: the jitted-program inventory.

Warmup cost and ladder bloat were folklore until now: the Generator
pre-jits a whole family of programs (the decode chunk ladder — plain AND
spec-window — the prefill buckets, the segment program, the paged
gather/scatter ops) and the Engine compiles one executable per batch
bucket, but nobody could answer "how many programs exist, what did each
compile cost, and did the persistent XLA cache actually serve the
restart?". This module is the shared recording machinery:

- ``ProgramLog`` — a per-owner (Generator / Engine / PjrtExecutor)
  inventory of jitted programs: one row per program with its arg shapes,
  the compile wall seconds (measured at the owner's warmup/first-use
  dispatch), the true backend-compile seconds and persistent-cache
  provenance (from jax's monitoring events, attributed via
  ``watch_compiles``), and — lazily, on the first ``/debug/programs``
  read — XLA ``cost_analysis()`` flops / bytes-accessed for the
  program's HLO.
- ``watch_compiles()`` — a thread-local attribution window over jax's
  monitoring stream (``/jax/core/compile/backend_compile_duration``,
  ``/jax/compilation_cache/cache_hits|cache_misses``): whatever jax
  compiles on this thread inside the ``with`` block is charged to the
  program being recorded, so "compiled fresh" vs "served from the
  persistent cache" (``scheduler.maybe_enable_compilation_cache``) vs "already in
  the in-process jit cache" becomes a per-row fact instead of folklore.

Outside a window the same listeners count a **late compile**: whatever
jax compiles after warm-up, on the serving thread or any other, adds to a
process-wide count and emits one ``compile`` event into the fleet event
log (seconds, persistent-cache hit or miss, thread name), so
``/debug/events?kind=compile`` answers which dispatch recompiled.

Aggregates export as ``app_ml_compile_seconds_total`` /
``app_ml_compile_cache_hits_total`` counters and the ``app_ml_programs``
gauge (the sampler pass publishes deltas per model); the full inventory
is served at ``GET /debug/programs``.

jax is imported lazily (listener installation and cost analysis only) —
importing this module costs stdlib only.
"""

from __future__ import annotations

import contextlib
import threading
import time

__all__ = ["ProgramLog", "watch_compiles", "abstractify", "late_compiles"]

# thread-local compile-attribution window (one level deep: program
# compiles never nest across our record sites)
_local = threading.local()
_install_lock = threading.Lock()
_installed = False

_COMPILE_DURATION_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


# compiles no watch_compiles window claimed, process-wide
_late = {"compiles": 0, "seconds": 0.0}


def late_compiles() -> dict:
    """Count and seconds of the compiles that ran outside every
    ``watch_compiles`` window since the listeners were installed."""
    with _install_lock:
        return dict(_late)


def _note_late(secs: float, cache: str) -> None:
    from ..flight_recorder import event_log

    with _install_lock:
        _late["compiles"] += 1
        _late["seconds"] += secs
    event_log().emit("compile", seconds=round(secs, 6), cache=cache,
                     thread=threading.current_thread().name)


def _ensure_listeners() -> bool:
    """Install the process-wide jax monitoring listeners once. Outside a
    ``watch_compiles`` window they cost one thread-local getattr per
    event, and a compile there is a late one (``_note_late``), counted
    once at its ``backend_compile_duration``: jax times the whole
    compile-or-load under that event, and a load from the persistent
    cache says ``cache_hits`` on the same thread just before it. False
    when jax's monitoring API is unavailable."""
    global _installed
    with _install_lock:
        if _installed:
            return True
        try:
            import jax.monitoring as mon

            def on_duration(name: str, secs: float, **kw) -> None:
                if name != _COMPILE_DURATION_EVENT:
                    return
                acc = getattr(_local, "acc", None)
                if acc is None:
                    hit = _local.__dict__.pop("late_hit", False)
                    _note_late(secs, "hit" if hit else "miss")
                else:
                    acc["backend_compile_s"] += secs
                    acc["compiles"] += 1

            def on_event(name: str, **kw) -> None:
                acc = getattr(_local, "acc", None)
                if acc is None:
                    if name == _CACHE_HIT_EVENT:
                        _local.late_hit = True  # read by on_duration
                elif name == _CACHE_HIT_EVENT:
                    acc["cache_hits"] += 1
                elif name == _CACHE_MISS_EVENT:
                    acc["cache_misses"] += 1

            mon.register_event_duration_secs_listener(on_duration)
            mon.register_event_listener(on_event)
        except Exception:
            return False
        _installed = True
        return True


@contextlib.contextmanager
def watch_compiles():
    """Attribute jax compile activity on THIS thread to one accumulator:
    ``{"backend_compile_s", "compiles", "cache_hits", "cache_misses"}``.
    Yields the accumulator; read it after the block."""
    ok = _ensure_listeners()
    acc = {"backend_compile_s": 0.0, "compiles": 0,
           "cache_hits": 0, "cache_misses": 0,
           "monitored": ok}
    prev = getattr(_local, "acc", None)
    _local.acc = acc
    try:
        yield acc
    finally:
        _local.acc = prev


def abstractify(args: tuple):
    """Shape/dtype skeleton of a call's args (captured BEFORE the call —
    donated buffers are deleted after it), good enough to re-``lower``
    the jitted program for cost analysis without touching data."""
    import jax

    def leaf(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
        return x

    return jax.tree.map(leaf, args)


def _provenance(acc: dict | None) -> str:
    """Compile provenance of one recorded program: ``persistent_cache``
    (the on-disk XLA cache served it), ``compiled`` (a fresh backend
    compile ran), ``cached`` (jax's in-process executable cache — e.g. a
    re-warm after recover), or ``unknown`` (monitoring unavailable)."""
    if acc is None or not acc.get("monitored"):
        return "unknown"
    if acc["cache_hits"] > 0 and acc["cache_misses"] == 0:
        return "persistent_cache"
    if acc["compiles"] > 0:
        return "compiled"
    return "cached"


class ProgramLog:
    """One owner's jitted-program inventory. ``record`` dedupes by
    entry name (a recover()'s re-warm of an already-recorded program
    only bumps ``warm_count`` — the first compile is the fact worth
    keeping); ``snapshot`` is safe from any thread and computes XLA
    cost analysis lazily, caching it on the row."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._entries: dict[str, dict] = {}
        self.compile_s_total = 0.0
        self.backend_compile_s_total = 0.0
        self.cache_hits_total = 0

    def __contains__(self, name: str) -> bool:
        with self._lock:
            return name in self._entries

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def record(self, name: str, *, wall_s: float, acc: dict | None = None,
               shapes=None, fn=None, abstract=None, kind: str = "jit",
               **extra) -> None:
        """Add one program row. ``wall_s`` is the owner's measured
        first-dispatch wall (trace + compile + one execute); ``acc`` a
        ``watch_compiles`` accumulator for true backend seconds and
        cache provenance; ``fn``/``abstract`` enable lazy cost
        analysis."""
        with self._lock:
            row = self._entries.get(name)
            if row is not None:
                row["warm_count"] = row.get("warm_count", 1) + 1
                return
            row = {
                "name": name,
                "kind": kind,
                "wall_s": round(float(wall_s), 6),
                "cache": _provenance(acc),
                "warm_count": 1,
                "at": round(time.time(), 3),
            }
            if shapes is not None:
                row["shapes"] = shapes
            if acc is not None and acc.get("monitored"):
                row["backend_compile_s"] = round(acc["backend_compile_s"], 6)
                self.backend_compile_s_total += acc["backend_compile_s"]
                self.cache_hits_total += acc["cache_hits"]
            row.update(extra)
            if fn is not None and abstract is not None:
                # held for lazy cost analysis only; never serialized
                row["_cost_ref"] = (fn, abstract)
            self._entries[name] = row
            self.compile_s_total += float(wall_s)

    def _cost(self, row: dict) -> None:
        """XLA cost analysis of one program's HLO, computed on demand
        (a re-lower, no re-compile) and cached on the row. None when
        the program cannot be re-lowered (mesh-closured tracing, native
        executables). The slow lowering runs OUTSIDE the lock; the row
        mutation happens under it, so a concurrent snapshot never sees
        the dict change mid-iteration (two racing readers may both pay
        the lowering — wasted work, never a crash)."""
        with self._lock:
            ref = row.get("_cost_ref")
        if ref is None:
            return
        fn, abstract = ref
        try:
            analysis = fn.lower(*abstract).cost_analysis()
            cost = {
                "flops": analysis.get("flops"),
                "bytes_accessed": analysis.get("bytes accessed"),
            }
            cost = {k: v for k, v in cost.items() if v is not None}
        except Exception:
            cost = None
        with self._lock:
            row["cost"] = cost or None
            row.pop("_cost_ref", None)

    def snapshot(self, cost: bool = False) -> list[dict]:
        """JSON-safe rows, oldest first. ``cost=True`` computes (and
        caches) the per-program flops / bytes-accessed — debug-endpoint
        work, never hot-path work. Safe against concurrent snapshots:
        every row read/copy happens under the log's lock."""
        with self._lock:
            rows = list(self._entries.values())
        out = []
        for row in rows:
            if cost:
                self._cost(row)
            with self._lock:
                out.append({k: v for k, v in row.items()
                            if not k.startswith("_")})
        return out

    def totals(self) -> dict:
        with self._lock:
            return {
                "programs": len(self._entries),
                "compile_s": round(self.compile_s_total, 6),
                "backend_compile_s": round(self.backend_compile_s_total, 6),
                "cache_hits": self.cache_hits_total,
                # process-wide, not this owner's: a compile after warm-up
                # belongs to no recorded program
                "late_compiles": _late["compiles"],
            }

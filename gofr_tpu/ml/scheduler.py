"""Adaptive token-budget scheduling for the continuous-batching serving path.

Iteration-level scheduling (Orca, OSDI '22) and stall-free token-budget
batching (Sarathi-Serve, OSDI '24) applied to this stack's shapes: every
device dispatch gets ONE token budget shared by decode and chunked prefill.

- ``TokenBudgetScheduler`` is the per-dispatch planner a ``Generator``
  consults: pick the smallest pre-jitted decode chunk (a power-of-two
  ladder) that covers the live decodable slots within the budget, and hand
  the remainder to segmented prefill — several segments per dispatch when
  decode is light, a bounded slice when decode is saturated. Stall-free by
  construction: a decodable batch always dispatches at least a 1-step
  chunk, and prefill always advances at least one segment, so neither side
  can starve the other beyond one budget's worth of work.
- ``SLOController`` closes the loop the PR-1 telemetry opened: it compares
  observed TTFT / TPOT percentiles against the operator's targets
  (``GOFR_ML_TTFT_TARGET_MS`` / ``GOFR_ML_TPOT_TARGET_MS``) and steers the
  budget fraction reserved for prefill — TTFT over target admits prefill
  faster (additive increase), TPOT over target protects decode
  (multiplicative backoff).
- ``AgingPriorityQueue`` replaces strict-FIFO admission with weighted
  priority classes (``high`` / ``normal`` / ``low``) plus aging: a waiting
  request's effective priority improves with time, so a saturated
  high-priority stream can never starve low-priority traffic forever.

Everything here is host-side policy — no jax imports on the hot path, and
all mutation happens on the serving thread that owns the Generator.

Greedy outputs are unaffected by any decision made here, and sampling keys
fold the ABSOLUTE step counter (generate.py chunk_fn), so re-chunking a
given step sequence draws the same tokens. Under temperature>0 with
CONCURRENT traffic the interleave can shift a request's admission step and
therefore its draws — same distribution, different sample; greedy decode
(the serving default) is bit-identical in all cases.
"""

from __future__ import annotations

import collections
import os
import time

__all__ = [
    "PRIORITIES", "normalize_priority", "TokenBudgetScheduler",
    "SLOController", "AgingPriorityQueue", "maybe_enable_compilation_cache",
    "retry_after_s",
]


def retry_after_s(admit_times, backlog: int) -> float:
    """Retry-After from the observed drain rate: admissions per second
    over the recent admission-timestamp window, scaled by the ``backlog``
    ahead of a retry. Conservative 1 s floor before any drain was
    observed; clamped to [0.5, 300] s. The ONE computation behind both
    the single-server and the replica-pool 429s — an instance's window
    holds its own admissions, a fleet front's the aggregate."""
    depth = backlog + 1
    rate = 0.0
    if len(admit_times) >= 2:
        span = admit_times[-1] - admit_times[0]
        if span > 0:
            rate = (len(admit_times) - 1) / span
    if rate <= 0:
        return 1.0
    return min(max(depth / rate, 0.5), 300.0)

# priority classes, best first; index == class number
PRIORITIES = ("high", "normal", "low")
_PRIORITY_BY_NAME = {name: i for i, name in enumerate(PRIORITIES)}
DEFAULT_PRIORITY = _PRIORITY_BY_NAME["normal"]


def normalize_priority(priority) -> int:
    """Map a caller-facing priority (class name, int, or None) onto a class
    index. Raises ValueError on unknown values so transports can answer a
    clean 400 instead of silently demoting a typo to 'normal'."""
    if priority is None:
        return DEFAULT_PRIORITY
    if isinstance(priority, str):
        try:
            return _PRIORITY_BY_NAME[priority.strip().lower()]
        except KeyError:
            raise ValueError(
                f"unknown priority {priority!r} (one of {PRIORITIES})"
            ) from None
    # ints only (bool is an int subclass; floats would silently truncate
    # — 0.9 must not become 'high'), and ValueError not TypeError so
    # transports map it to a 400
    if isinstance(priority, bool) or not isinstance(priority, int):
        raise ValueError(
            f"priority must be a class name or int, got "
            f"{type(priority).__name__}")
    if not 0 <= priority < len(PRIORITIES):
        raise ValueError(
            f"priority {priority} out of range (0..{len(PRIORITIES) - 1})")
    return priority


# where the persistent compilation cache lives when the environment names
# no place for it: one fixed path inside the checkout. The path is part of
# the cache key, so a directory that moves between runs never hits.
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def maybe_enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache so a restarted server
    loads the chunk-fn ladder and prefill buckets from disk instead of
    recompiling them. Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax's
    own handling of it stands and no directory is set here; otherwise the
    cache goes to ``DEFAULT_COMPILATION_CACHE_DIR``. Returns the directory
    in effect. Safe to call repeatedly."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as _cc

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILATION_CACHE_DIR)
    # serving programs are small but numerous: the default min-compile-time
    # threshold (1 s) would skip exactly the ladder entries restarts want
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # jax decides cache-or-not lazily at the FIRST compile and then sticks
    # with that decision; a Generator is always built after the model's own
    # param/cache compiles, so drop the memoized state and let the next
    # compile re-read the cache dir
    _cc.reset_cache()
    return jax.config.jax_compilation_cache_dir


class TokenBudgetScheduler:
    """Per-dispatch planner: one token budget split between decode and
    chunked prefill.

    ``plan(n_decodable, prefill_pending, unit_tokens=1)`` returns
    ``(chunk_size, n_segments)``: the ladder entry to dispatch and how
    many prefill segments may run before it. ``unit_tokens`` is the
    device cost of ONE ladder step per decodable row — 1 for plain
    decode, ``K+1`` for a speculative verify window (draft + verify
    positions all sweep the weights), so spec windows are charged
    honestly against the same budget. Invariants:

    - chunk_size is the LARGEST ladder entry whose total decode tokens
      (``size * n_decodable * unit_tokens``) fit the decode share of the
      budget — i.e. the smallest program count for the work, never
      beyond ``chunk``.
    - with prefill pending, ``max(prefill_chunk, share * budget)`` tokens
      are reserved for prefill first; the decode chunk shrinks down the
      ladder instead of delaying prefill a full chunk.
    - both sides always make progress: chunk_size >= 1 whenever anything
      is decodable, n_segments >= 1 whenever prefill is pending. Total
      planned work stays within one budget (plus those two floors), which
      is the stall-free bound.
    """

    def __init__(self, budget: int, ladder, prefill_chunk: int = 0, *,
                 slots: int | None = None, prefill_share: float = 0.5,
                 min_share: float = 0.05, max_share: float = 0.75) -> None:
        if budget <= 0:
            raise ValueError("token budget must be positive")
        self.budget = int(budget)
        self.ladder = tuple(sorted(int(c) for c in ladder))
        if not self.ladder:
            raise ValueError("chunk ladder is empty")
        self.prefill_chunk = int(prefill_chunk)
        self.slots = slots  # batch size hint for the decode-light test
        self.prefill_share = float(prefill_share)
        self.min_share = float(min_share)
        self.max_share = float(max_share)
        # observability: dispatch counts per chunk size (segments run are
        # the Generator's prefill_segments_run — one counter, one owner).
        # TTFT mini-chunks are counted apart: they are admission-driven,
        # not ladder picks, and would read as 1-step collapse otherwise.
        self.dispatches: collections.Counter = collections.Counter()
        self.mini_dispatches = 0
        self.last_chunk = self.ladder[-1]
        self.last_segments = 0
        # device cost of one ladder step per row in the LAST plan: 1 for
        # plain decode, K+1 when the dispatch was a spec verify window
        self.last_unit = 1
        # KV-restore charging (generate.Generator.restore_prefix): a
        # host->device prefix restore rides the device queue like prefill
        # work; its token count lands here as DEBT that upcoming plans pay
        # off before budgeting decode+prefill, so restores interleave with
        # decode instead of stacking on top of a full dispatch.
        self.restore_debt = 0
        self.restores_charged = 0
        self.sp_charges = 0  # sequence-parallel prefill waves charged
        # fused-decode-window generators flip this on: ladder entries are
        # then WINDOW sizes (K device steps per dispatch), so plan() picks
        # windows through the same c*rows*unit <= budget arithmetic —
        # display-only here, the math is unchanged by construction
        self.window_mode = False

    def charge_sp(self, tokens: int) -> None:
        """Charge one sequence-parallel prefill wave. The caller passes
        tokens/shards — each sp shard swept only its slice of the
        prompt, so the debt upcoming plans repay is the PER-DEVICE
        device time, not the full prompt's (charging the full prompt
        would make the scheduler throttle decode as if the prefill had
        cost shards× what it did). Rides the restore-debt ledger: same
        repayment cap, same stall-free floor."""
        self.restore_debt = min(self.restore_debt + max(0, int(tokens)),
                                4 * self.budget)
        self.sp_charges += 1

    def charge_restore(self, tokens: int) -> None:
        """Debit ``tokens`` of restore DMA/scatter work against upcoming
        dispatch budgets. Capped at a few budgets so a restore burst
        throttles the next dispatches, never starves decode indefinitely
        (plan() additionally repays at most half a budget per dispatch)."""
        self.restore_debt = min(self.restore_debt + max(0, int(tokens)),
                                4 * self.budget)
        self.restores_charged += 1

    def set_share(self, share: float) -> float:
        self.prefill_share = min(self.max_share,
                                 max(self.min_share, float(share)))
        return self.prefill_share

    def plan(self, n_decodable: int, prefill_pending: bool,
             unit_tokens: int = 1) -> tuple[int, int]:
        unit = max(1, int(unit_tokens))
        self.last_unit = unit
        budget = self.budget
        if self.restore_debt:
            # pay down restore debt first — at most half a budget per
            # dispatch, so decode keeps at least the ladder floor's cadence
            paid = min(self.restore_debt, budget // 2)
            self.restore_debt -= paid
            budget -= paid
        if prefill_pending and self.prefill_chunk:
            # share-based reserve (flooring it at a full segment would
            # zero the decode budget whenever prefill_chunk ~ budget),
            # with a decode FLOOR of half the fixed chunk per live row:
            # stall-freeness cuts both ways — however hard the controller
            # leans toward prefill, live streams keep at least half their
            # fixed-path cadence, so a misdirected share ratchet can
            # never collapse decode to 1-step dispatches
            floor = (self.ladder[-1] // 2) * max(1, n_decodable) * unit
            decode_budget = max(budget - int(budget * self.prefill_share),
                                min(floor, budget))
        else:
            decode_budget = budget
        rows = max(1, n_decodable)
        size = self.ladder[0]
        for c in self.ladder:
            if c * rows * unit <= decode_budget:
                size = c
        if not (prefill_pending and self.prefill_chunk):
            self.last_segments = 0
            return size, 0
        # segment batching is for a LIGHT batch (few live consumers to
        # delay) or an explicit controller bias toward prefill; a
        # saturated batch gets the stall-free minimum of one segment so
        # live streams keep their cadence
        light = (self.slots is None
                 or n_decodable <= max(1, self.slots // 4)
                 or self.prefill_share > 0.6)
        spare = budget - size * n_decodable * unit
        segments = max(1, spare // self.prefill_chunk if light else 1)
        self.last_segments = segments
        return size, segments

    def note_dispatch(self, chunk_size: int) -> None:
        self.last_chunk = chunk_size
        self.dispatches[chunk_size] += 1

    def snapshot(self) -> dict:
        # dict(Counter) is atomic under the GIL; sorting the copy keeps
        # this safe to call from the debug endpoint while the serving
        # thread keeps dispatching
        dispatches = dict(self.dispatches)
        return {
            "budget": self.budget,
            "plans": "windows" if self.window_mode else "chunks",
            "prefill_share": round(self.prefill_share, 4),
            "ladder": list(self.ladder),
            "last_chunk": self.last_chunk,
            "dispatches": {str(k): v
                           for k, v in sorted(dispatches.items())},
            "mini_dispatches": self.mini_dispatches,
            "last_segments": self.last_segments,
            "last_unit": self.last_unit,
            "restore_debt": self.restore_debt,
            "restores_charged": self.restores_charged,
            "sp_charges": self.sp_charges,
        }


class SLOController:
    """Closed-loop steering of the prefill share from observed latency.

    Runs entirely on the serving thread: the LLMServer feeds it TTFT /
    TPOT samples as they are measured and calls ``maybe_update`` once per
    serve-loop pass; at most every ``interval_s`` it compares window p95s
    against the targets and nudges ``scheduler.prefill_share``:

    - TPOT above target → decode is being squeezed → multiplicative
      backoff of the prefill share (fast protection of live streams).
    - else TTFT above target → queued prompts are waiting too long →
      additive increase of the prefill share.
    - both within target → drift slowly back toward the neutral share so
      a past incident doesn't pin the split forever.
    """

    def __init__(self, scheduler: TokenBudgetScheduler, *,
                 ttft_target_s: float = 0.2, tpot_target_s: float = 0.05,
                 interval_s: float = 0.5, window: int = 64,
                 neutral_share: float = 0.5) -> None:
        self.scheduler = scheduler
        self.ttft_target_s = float(ttft_target_s)
        self.tpot_target_s = float(tpot_target_s)
        self.interval_s = float(interval_s)
        self.neutral_share = float(neutral_share)
        self._ttft: collections.deque = collections.deque(maxlen=window)
        self._tpot: collections.deque = collections.deque(maxlen=window)
        self._last_update = 0.0
        self.updates = 0
        self.last_ttft_p95 = float("nan")
        self.last_tpot_p95 = float("nan")

    @classmethod
    def from_env(cls, scheduler: TokenBudgetScheduler) -> "SLOController":
        """Targets from ``GOFR_ML_TTFT_TARGET_MS`` / ``GOFR_ML_TPOT_TARGET_MS``
        (defaults 200 / 50 ms)."""
        ttft_ms = float(os.environ.get("GOFR_ML_TTFT_TARGET_MS", "200"))
        tpot_ms = float(os.environ.get("GOFR_ML_TPOT_TARGET_MS", "50"))
        return cls(scheduler, ttft_target_s=ttft_ms / 1e3,
                   tpot_target_s=tpot_ms / 1e3)

    def observe_ttft(self, seconds: float) -> None:
        self._ttft.append(seconds)

    def observe_tpot(self, seconds: float) -> None:
        self._tpot.append(seconds)

    @staticmethod
    def _p95(samples) -> float:
        if not samples:
            return float("nan")
        ordered = sorted(samples)
        return ordered[min(len(ordered) - 1, int(0.95 * len(ordered)))]

    def maybe_update(self, now: float | None = None) -> bool:
        now = time.monotonic() if now is None else now
        if now - self._last_update < self.interval_s:
            return False
        self._last_update = now
        ttft_p95 = self._p95(self._ttft)
        tpot_p95 = self._p95(self._tpot)
        self.last_ttft_p95, self.last_tpot_p95 = ttft_p95, tpot_p95
        # fresh window per interval: without this, one past burst of slow
        # TTFTs keeps ratcheting the share up every 0.5 s long after the
        # burst cleared (and TPOT could never out-vote it)
        self._ttft.clear()
        self._tpot.clear()
        sched = self.scheduler
        if tpot_p95 == tpot_p95 and tpot_p95 > self.tpot_target_s:
            sched.set_share(sched.prefill_share * 0.7)
        elif ttft_p95 == ttft_p95 and ttft_p95 > self.ttft_target_s:
            sched.set_share(sched.prefill_share + 0.1)
        else:
            sched.set_share(sched.prefill_share
                            + (self.neutral_share - sched.prefill_share)
                            * 0.1)
        self.updates += 1
        return True

    def snapshot(self) -> dict:
        def _ms(v: float):
            return None if v != v else round(v * 1e3, 2)

        return {
            "ttft_target_ms": self.ttft_target_s * 1e3,
            "tpot_target_ms": self.tpot_target_s * 1e3,
            "ttft_p95_ms": _ms(self.last_ttft_p95),
            "tpot_p95_ms": _ms(self.last_tpot_p95),
            "updates": self.updates,
        }


class AgingPriorityQueue:
    """Weighted ready queues with aging — the admission order policy.

    One FIFO deque per priority class. ``pop`` compares the HEAD of each
    class by effective priority ``class - waited / aging_s``: a request
    ages one full class per ``aging_s`` seconds waited, so a 'low' request
    outranks fresh 'high' traffic after ``2 * aging_s`` — starvation-free
    without giving up strict ordering on short horizons. FIFO order within
    a class is preserved, and ``push_front`` keeps the requeue-at-front
    semantics paged admission failures rely on (the retried request stays
    at the head of ITS class).

    Items must expose ``priority`` (class index) and ``enqueued_at``
    (``time.perf_counter`` seconds). Serving-thread-only, like the list it
    replaced.
    """

    def __init__(self, aging_s: float = 2.0) -> None:
        self.aging_s = max(1e-6, float(aging_s))
        self._queues: tuple[collections.deque, ...] = tuple(
            collections.deque() for _ in PRIORITIES)
        # queued prompt tokens (items' ``n_tokens``), maintained across
        # push/pop/prune — the load-shedding bound GOFR_ML_MAX_QUEUED_TOKENS
        # is enforced against this sum, so it must never drift
        self.tokens = 0

    def __len__(self) -> int:
        return sum(len(q) for q in self._queues)

    def __iter__(self):
        for q in self._queues:
            yield from q

    def push(self, item) -> None:
        self._queues[item.priority].append(item)
        self.tokens += getattr(item, "n_tokens", 0)

    def push_front(self, item) -> None:
        self._queues[item.priority].appendleft(item)
        self.tokens += getattr(item, "n_tokens", 0)

    def pop(self, now: float | None = None):
        """Next request to admit, or None when empty."""
        now = time.perf_counter() if now is None else now
        best_class = None
        best_eff = None
        for cls, q in enumerate(self._queues):
            if not q:
                continue
            eff = cls - (now - q[0].enqueued_at) / self.aging_s
            if best_eff is None or eff < best_eff:
                best_eff, best_class = eff, cls
        if best_class is None:
            return None
        item = self._queues[best_class].popleft()
        self.tokens -= getattr(item, "n_tokens", 0)
        return item

    def shed_lowest(self, worse_than: int | None = None):
        """Remove and return the shed victim under overload: the NEWEST
        request of the lowest-priority non-empty class (the oldest of a
        class is closest to admission and has the most wait invested —
        shedding it would waste that). With ``worse_than`` set, only
        classes strictly worse than that index are candidates (high-
        priority admission may preempt queued low-priority work, never
        peers); returns None when no such victim exists."""
        floor = -1 if worse_than is None else int(worse_than)
        for cls in range(len(self._queues) - 1, floor, -1):
            q = self._queues[cls]
            if q:
                item = q.pop()
                self.tokens -= getattr(item, "n_tokens", 0)
                return item
        return None

    def prune(self, predicate) -> list:
        """Remove and return every item matching ``predicate`` (cancelled
        consumers), preserving order among the kept."""
        removed: list = []
        for q in self._queues:
            kept = []
            for item in q:
                if predicate(item):
                    removed.append(item)
                else:
                    kept.append(item)
            if len(kept) != len(q):
                q.clear()
                q.extend(kept)
        for item in removed:
            self.tokens -= getattr(item, "n_tokens", 0)
        return removed

    def drain(self) -> list:
        """Remove and return everything (close-flush path)."""
        out: list = []
        for q in self._queues:
            out.extend(q)
            q.clear()
        self.tokens = 0
        return out

    def snapshot(self, now: float | None = None) -> dict:
        now = time.perf_counter() if now is None else now
        out = {}
        for name, q in zip(PRIORITIES, self._queues, strict=True):
            try:
                oldest = round(now - q[0].enqueued_at, 4)
            except IndexError:
                # raced the serving thread's popleft — the debug endpoint
                # reads this from the event-loop thread
                oldest = 0.0
            out[name] = {"depth": len(q), "oldest_wait_s": oldest}
        return out

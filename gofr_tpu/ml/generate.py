"""Continuous-batching token generation.

The serving heart of BASELINE.md config #4 (Llama streaming, TP=8):
a decode loop that keeps the MXU busy with a fixed-shape batch while
requests of different lengths join and leave — the TPU-native analogue of
the reference's per-request goroutine model (handler.go:77-97), redesigned
because SPMD compute wants ONE static-shaped program, not one thread per
request.

Design:
- ``Generator`` holds a fixed batch of slots; the jitted step always runs
  the full batch — free slots decode garbage that is simply ignored (a
  slot's share of one matmul is cheaper than a recompile).
- the decode loop is DEVICE-RESIDENT: sampling is fused into the jitted
  step, the KV cache is donated (no copy per step), ``chunk`` tokens are
  produced per dispatch via ``lax.scan``, and sampled tokens come back to
  the host through an async-copy pipeline one dispatch deep — host-side
  bookkeeping (callbacks, EOS, slot lifecycle) lags one chunk behind the
  device and never stalls it. A device→host sync per step would put the
  host's round trip into every token, regardless of chip speed.
- prefill runs per-request on padded shape buckets, then the sequence's
  KV rows are scattered into its slot.
"""

from __future__ import annotations

import collections
import functools
import logging
import os
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..flight_recorder import phase
from .kv_offload import HostKVStore
from .programs import ProgramLog, abstractify, watch_compiles
from .scheduler import TokenBudgetScheduler, maybe_enable_compilation_cache

__all__ = ["Sampler", "sample_logits", "greedy", "Generator",
           "PagePoolExhausted", "PrefixEvicted", "spec_k_from_env",
           "decode_window_from_env", "DecodeWindowUnsupported"]

_log = logging.getLogger("gofr_tpu.ml.generate")


def _chunk_ladder(chunk: int) -> tuple[int, ...]:
    """Power-of-two dispatch sizes up to ``chunk`` (always including 1 and
    ``chunk`` itself): the pre-jitted decode programs the budget scheduler
    picks from. 16 -> (1, 2, 4, 8, 16); 3 -> (1, 2, 3)."""
    ladder = [1]
    while ladder[-1] * 2 < chunk:
        ladder.append(ladder[-1] * 2)
    if chunk > 1:
        ladder.append(chunk)
    return tuple(ladder)


def _prefill_ladder(cap: int) -> tuple[int, ...]:
    """Sequence lengths of the dense layout's one-row prefill programs:
    128, 256, 512, 768, 1024, then powers of two, as far as ``cap`` (the
    longest whole prompt), and ``cap`` itself. A prompt takes the smallest
    entry that holds it. Every entry below ``cap`` is 128 or a multiple of
    256, which ``ops.flash_attention`` needs for its kernel (384 would
    fall to the XLA path). 2048 -> (128, 256, 512, 768, 1024, 2048);
    512 -> (128, 256, 512); 64 -> (64,)."""
    ladder = [n for n in (128, 256, 512, 768) if n < cap]
    n = 1024
    while n < cap:
        ladder.append(n)
        n *= 2
    return (*ladder, cap)


def _env_int(name: str, default: int, *, minimum: int = 0) -> int:
    """Loudly-validated integer env knob (the PR-6 drain/replicas
    pattern): malformed or out-of-range values fail at construction
    instead of silently serving with a default."""
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        value = int(raw)
    except ValueError:
        raise ValueError(
            f"{name} must be an integer, got {raw!r}") from None
    if value < minimum:
        raise ValueError(f"{name} must be >= {minimum}, got {value}")
    return value


def _env_fraction(name: str, default: float) -> float:
    """Loudly-validated [0, 1] float env knob — rejects malformed values,
    negatives, values over 1, and NaN."""
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        value = float(raw)
    except ValueError:
        raise ValueError(
            f"{name} must be a number in [0, 1], got {raw!r}") from None
    if not 0.0 <= value <= 1.0:  # NaN fails both compares
        raise ValueError(f"{name} must be in [0, 1], got {raw!r}")
    return value


def spec_k_from_env(default: int = 0) -> int:
    """``GOFR_ML_SPEC_K`` with loud validation — the ONE parse behind the
    Generator's env default and the examples' LLM_SPEC_K fallback chain,
    so a malformed value fails the boot with the knob's name instead of
    a bare int() traceback."""
    return _env_int("GOFR_ML_SPEC_K", default)


# the K "auto" resolves to: big enough that a window amortizes the
# ~tens-of-ms host round-trip per launch, small enough that early-exit
# waste past a short answer stays a fraction of the window
_WINDOW_AUTO = 32


def decode_window_from_env(default: int = 0) -> int:
    """``GOFR_ML_DECODE_WINDOW`` — the fused-decode-window size K (one
    jitted program runs up to K sampling steps; the host intervenes only
    at admission/completion boundaries). Accepts ``0``/``off`` (today's
    single-step dispatch, the default), ``auto`` (a tuned power of two),
    or an explicit power-of-two K. Malformed, negative, or
    non-power-of-two values fail loudly at construction with the knob's
    name — a silently-clamped window would misreport every launch-share
    number the mode exists to collapse."""
    raw = os.environ.get("GOFR_ML_DECODE_WINDOW", "").strip().lower()
    if not raw:
        return default
    if raw in ("0", "off"):
        return 0
    if raw == "auto":
        return _WINDOW_AUTO
    try:
        value = int(raw)
    except ValueError:
        raise ValueError(
            f"GOFR_ML_DECODE_WINDOW must be an integer, 'auto', or "
            f"'off', got {raw!r}") from None
    if value < 1 or value & (value - 1):
        raise ValueError(
            f"GOFR_ML_DECODE_WINDOW must be a power of two >= 1 "
            f"(or 0/off/auto), got {value}")
    return value


def pipeline_from_env(default: int = 0) -> int:
    """``GOFR_ML_PIPELINE`` — double-buffered dispatch: ``1``/``on``
    keeps TWO decode dispatches in flight across serve passes (window
    N+1 launches before the host blocks on N, so N's settle/emit host
    work overlaps N+1's device compute), ``0``/``off``/unset keeps the
    classic lag-one pipeline. Malformed values fail loudly at
    construction with the knob's name — a silently-ignored arm would
    quietly benchmark the wrong serving loop."""
    raw = os.environ.get("GOFR_ML_PIPELINE", "").strip().lower()
    if not raw:
        return default
    if raw in ("0", "off"):
        return 0
    if raw in ("1", "on"):
        return 1
    raise ValueError(
        f"GOFR_ML_PIPELINE must be 0/off or 1/on, got {raw!r}")


class DecodeWindowUnsupported(ValueError):
    """Fused decode windows require the paged KV cache: the on-device
    early-exit loop freezes a finished row by holding its page-table
    ``len`` in place, and the dense decode path has no such per-row
    write routing (int4 KV already rejects dense for the same reason).
    Construct the Generator with ``page_size > 0`` or leave
    ``GOFR_ML_DECODE_WINDOW`` unset."""


class PagePoolExhausted(RuntimeError):
    """Paged-KV admission failed for lack of free pages — transient
    back-pressure (pages free as slots finish), not a bad request; the
    serving layer requeues instead of erroring the client."""


class PrefixEvicted(RuntimeError):
    """The registered prefix this request references was LRU-evicted under
    pool pressure. Callers re-register (or retry with the full prompt) —
    the suffix-only ids they hold are meaningless without the prefix."""


class Sampler:
    """Static sampling config (hashable: safe as a jit static arg)."""

    def __init__(self, temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0) -> None:
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)

    def __hash__(self) -> int:
        return hash((self.temperature, self.top_k, self.top_p))

    def __eq__(self, other) -> bool:
        return (isinstance(other, Sampler)
                and (self.temperature, self.top_k, self.top_p)
                == (other.temperature, other.top_k, other.top_p))


def greedy() -> Sampler:
    return Sampler()


def _sample_impl(logits: jnp.ndarray, key, sampler: Sampler) -> jnp.ndarray:
    """logits [B, V] -> token ids [B]. Traced inside the decode step."""
    if sampler.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / sampler.temperature
    if sampler.top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -sampler.top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    if sampler.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest set of tokens whose mass exceeds top_p
        cutoff_idx = jnp.sum(cum < sampler.top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None], axis=-1)
        logits = jnp.where(logits < cutoff, -1e30, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("sampler",))
def sample_logits(logits: jnp.ndarray, key, sampler: Sampler) -> jnp.ndarray:
    return _sample_impl(logits, key, sampler)


class _Slot:
    __slots__ = ("live", "tokens", "max_new", "produced", "prompt_len",
                 "eos_hit", "evicted", "callback", "spec_windows",
                 "spec_emitted", "spec_disabled", "spec_cooldown_left",
                 "spec_recent_w", "spec_recent_e", "hist", "sp_shards",
                 "deadline_at")

    def __init__(self) -> None:
        self.live = False
        self.tokens: list[int] = []
        self.max_new = 0
        self.produced = 0
        self.prompt_len = 0
        self.eos_hit = False
        # absolute time.monotonic() deadline the serving layer stamps at
        # slot binding (None outside a served request): the fused decode
        # window derives a per-slot step bound from it so a window never
        # burns K steps for a request its deadline will reap mid-window
        self.deadline_at: float | None = None
        # shard count of the sequence-parallel prefill that admitted
        # this slot (0 = the single-device path) — journey marks and the
        # sp debug block read it
        self.sp_shards = 0
        # per-stream draft efficiency (spec mode): windows seen / tokens
        # emitted — the serving layer exports the acceptance rate
        self.spec_windows = 0
        self.spec_emitted = 0
        # adaptive speculation (GOFR_ML_SPEC_MIN_ACCEPT): a slot whose
        # rolling accept rate drops below the floor degrades to plain
        # decode (1 token/window) and re-probes after a cooldown —
        # adversarial streams stop wasting the verify budget, losslessly
        self.spec_disabled = False
        self.spec_cooldown_left = 0
        self.spec_recent_w = 0   # windows in the current judging window
        self.spec_recent_e = 0   # tokens emitted in it
        # host mirror of the slot's FULL token history (prompt + emitted),
        # kept only when the all-disabled plain-ladder fallback is armed:
        # it re-seeds the device drafting row when speculation re-probes
        self.hist: list[int] = []
        # a dry page pool truncated this slot: it finished with the tokens
        # it had, NOT at eos/max_new — serving layers must not report it
        # as a natural "stop" (ADVICE r4 #4)
        self.evicted = False
        self.callback = None


def _jit(name: str, fn, **jit_kwargs):
    """``jax.jit`` under a stable name: a profiler trace's programs
    (``jit_<name>``) and jax's compile log say what each one is. Prefill
    programs hold ``prefill`` and decode programs ``chunk_fn``, the
    patterns the benchmark's trace readers find them by."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kwargs)


def _model_of(cfg):
    """The model module that ``cfg`` belongs to, by its type: the one
    place the serving loop chooses between families. The dense slot
    layout takes ``init_cache``, ``prefill_into`` and ``decode_step`` from
    it; every other layout is the llama block's."""
    from ..models import jamba, llama, qwen3_next

    if isinstance(cfg, qwen3_next.Qwen3NextConfig):
        return qwen3_next
    if isinstance(cfg, jamba.JambaConfig):
        return jamba
    return llama


class Generator:
    """Continuous-batching decode loop over a fixed slot batch.

    Synchronous core (the asyncio serving layer drives it from a thread via
    the Engine pattern). Usage:

        gen = Generator(params, cfg, batch_slots=8, max_seq=2048)
        out = gen.generate(prompt_ids, max_new_tokens=64)   # single request
        # or: slot = gen.add_request(ids, n, cb); gen.step() in a loop

    Admission is one prompt a prefill program, whatever the size of the
    wave: in the dense layout (the default) on a ladder of lengths fixed
    by ``max_seq`` (``_prefill_ladder``; ``gen.prefill_buckets`` holds
    it), in the paged and SP layouts on the ``prefill_buckets`` given.
    ``warmup()`` builds every one of them. ``prefill_tokens_real`` and
    ``prefill_tokens_padded`` count what the dense programs were sent and
    what they computed.
    """

    def __init__(self, params: Any, cfg, *, batch_slots: int = 8,
                 max_seq: int = 2048, sampler: Sampler | None = None,
                 eos_id: int | None = None, prefill_buckets=(128, 512, 2048),
                 seed: int = 0, mesh=None, chunk: int = 1,
                 shard_cache: bool = False, spec_k: int | None = None,
                 spec_ngram: int = 3, spec_min_accept: float | None = None,
                 spec_cooldown: int | None = None, page_size: int = 0,
                 n_pages: int | None = None, draft_params: Any = None,
                 draft_cfg: Any = None, prefill_chunk: int = 0,
                 token_budget: int | None = None,
                 host_kv: Any = None, sp: Any = None,
                 decode_window: int | None = None,
                 pipeline: int | None = None) -> None:
        import contextlib

        from ..models import llama

        model = self._m = _model_of(cfg)
        self._mesh_ctx = (lambda: mesh) if mesh is not None else contextlib.nullcontext
        self.params = params
        self.cfg = cfg
        self.batch_slots = batch_slots
        self.max_seq = max_seq
        self.sampler = sampler or greedy()
        self.eos_id = eos_id
        # an int, or a collection (Llama-3 instruct stops on several ids)
        if eos_id is None:
            self._eos = frozenset()
        elif isinstance(eos_id, (list, tuple, set, frozenset)):
            self._eos = frozenset(int(e) for e in eos_id)
        else:
            self._eos = frozenset((int(eos_id),))
        # vector form for the batched burst apply (np.isin in _apply_burst)
        self._eos_arr = (np.fromiter(self._eos, np.int64, len(self._eos))
                         if self._eos else None)
        self.chunk = chunk
        # -- fused decode windows (GOFR_ML_DECODE_WINDOW) ------------------
        # decode_window: None -> env (0 = off, the byte-identical
        # single-step path). Window mode re-points ``chunk`` at K so the
        # WHOLE existing dispatch machinery composes unchanged: the
        # pre-jitted ladder entries become window sizes, the token-budget
        # scheduler's plan() charges K tokens/slot through the same
        # ladder-entry * unit_tokens contract speculation uses, and
        # _grow_pages' pipeline margin covers K steps per dispatch.
        if decode_window is None:
            decode_window = decode_window_from_env(0)
        self.decode_window = int(decode_window)
        if self.decode_window < 0 or (
                self.decode_window and
                self.decode_window & (self.decode_window - 1)):
            raise ValueError(
                f"decode_window must be 0 or a power of two, got "
                f"{self.decode_window}")
        if self.decode_window:
            if not page_size:
                raise DecodeWindowUnsupported(
                    "fused decode windows (GOFR_ML_DECODE_WINDOW="
                    f"{self.decode_window}) require the paged KV cache — "
                    "set page_size > 0")
            self.chunk = self.decode_window
            # window-mode-only state (is-not-None contract: none of this
            # exists when the knob is off)
            self.windows = 0                  # fused windows processed
            self.window_steps_planned = 0     # sum of dispatched K
            self.window_steps_realized = 0    # device steps actually run
            self.window_overshoot = 0         # tokens computed past a
            #                                   slot's EOS/budget (ledger)
            self._step_ema: float | None = None  # s per planned step
            self._last_dispatch: tuple | None = None
        # -- double-buffered dispatch (GOFR_ML_PIPELINE) -------------------
        # pipeline: None -> env (0 = off, the classic lag-one pipeline
        # and the byte-identical default). Armed, step() settles down to
        # TWO outstanding dispatches instead of one: fused windows feed
        # next-tokens back on-device, so window N+1 never needs N's
        # drained results — N's settle/emit host work overlaps N+1's
        # device compute. Admission stays a boundary-only concern:
        # _admit_waiting's drain barrier flushes BOTH windows before a
        # slot is reused, and prefill dispatches (they mutate the page
        # table) never ride the in-flight queue at depth.
        if pipeline is None:
            pipeline = pipeline_from_env()
        self.pipeline = 1 if pipeline else 0
        if self.pipeline:
            # pipeline-only state (is-not-None contract: none of this
            # exists when the knob is off)
            self.pipeline_windows = 0    # passes that ended double-buffered
            self.pipeline_overshoot = 0  # tokens computed for slots
            #                              already dead at settle (ledger)
        # -- speculation knobs (parsed EARLY: the auto token budget below
        # charges verify windows at K+1 tokens per slot) -----------------
        # spec_k: None -> env GOFR_ML_SPEC_K (0 = off); malformed or
        # negative values fail loudly at construction (_env_int).
        if spec_k is None:
            spec_k = _env_int("GOFR_ML_SPEC_K", 0)
        self.spec_k = int(spec_k)
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {self.spec_k}")
        # per-slot adaptive speculation: below this rolling accept rate a
        # slot stops speculating (0 = never auto-disable) and re-probes
        # after spec_cooldown windows
        self.spec_min_accept = (
            _env_fraction("GOFR_ML_SPEC_MIN_ACCEPT", 0.0)
            if spec_min_accept is None else float(spec_min_accept))
        if not 0.0 <= self.spec_min_accept <= 1.0:
            raise ValueError(
                f"spec_min_accept must be in [0, 1], got "
                f"{self.spec_min_accept}")
        self.spec_cooldown = (_env_int("GOFR_ML_SPEC_COOLDOWN", 32,
                                       minimum=1)
                              if spec_cooldown is None
                              else int(spec_cooldown))
        if self.spec_cooldown < 1:
            raise ValueError(
                f"spec_cooldown must be >= 1, got {self.spec_cooldown}")
        self._spec_probe_min = 8  # windows judged before a disable verdict
        self.spec_disables = 0    # slots auto-disabled (lifetime)
        self.spec_reprobes = 0    # cooldown expiries re-arming a slot
        self._plain_armed = False  # set in _init_spec (lookup mode only)
        self._spec_rows_stale = False  # device history lags the mirror
        if (model is llama and getattr(cfg, "kv_bits", 16) == 4
                and not page_size):
            raise ValueError(
                "kv_bits=4 (int4 KV) requires the paged cache — set "
                "page_size > 0")
        self.prefill_buckets = tuple(
            b for b in sorted(prefill_buckets) if b <= max_seq
        ) or (max_seq,)
        # -- sequence-parallel serving plan (ml/sp_serving.py) ------------
        # sp=None consults GOFR_ML_SP; unset/off resolves to None and
        # constructs NO SP machinery — the single-device serving path
        # stays byte-identical. A resolved plan may bring its own sp
        # mesh (built over the visible devices) and is validated loudly
        # HERE: shard bounds, bucket/max_seq divisibility, Ulysses head
        # divisibility, and mode conflicts all reject at construction.
        from .sp_serving import SPConfig
        from .sp_serving import resolve as _resolve_sp

        if model is not llama:
            # another family serves from the dense slot layout alone; what
            # else was asked for, here or in the environment, is refused
            # with what it would take
            asked = {"page_size": page_size,
                     "sp": sp or (sp is None
                                  and SPConfig.from_env() is not None),
                     "spec_k": self.spec_k or draft_params is not None,
                     "kv_bits": getattr(cfg, "kv_bits", 16) != 16,
                     "prefill_chunk": prefill_chunk,
                     "mesh": mesh is not None or shard_cache}
            for what, on in asked.items():
                if on:
                    raise ValueError(
                        f"{type(cfg).__name__} is not served with {what} "
                        f"yet: {model.UNSUPPORTED[what]}")
        self._sp = _resolve_sp(
            sp, cfg=cfg, mesh=mesh, prefill_buckets=self.prefill_buckets,
            max_seq=max_seq, page_size=int(page_size), spec_k=self.spec_k,
            shard_cache=shard_cache)
        if not page_size and self._sp is None:
            # the dense layout prefills every prompt in a one-row program
            # of its own, on a ladder of lengths that follows from max_seq
            # alone (the ``prefill_buckets`` argument is the paged and SP
            # layouts'). Segmented prefill takes every prompt past the
            # chunk, so no longer whole prompt exists — but for a draft
            # model, which ingests a segmented prompt's history whole.
            whole = (min(int(prefill_chunk), max_seq)
                     if prefill_chunk and draft_params is None else max_seq)
            self.prefill_buckets = _prefill_ladder(whole)
        if self._sp is not None:
            mesh = self._sp.mesh
            self._mesh_ctx = lambda: mesh
            self.sp_prefills = 0    # prompts prefilled sequence-parallel
            self.sp_fallbacks = 0   # SP failures served single-device
            self.sp_tokens = 0      # prompt tokens through the SP path
        self.mesh = mesh
        # A mesh-less generator lives on the one device its params are
        # committed to (a one-chip replica, replica.py). Everything it
        # allocates eagerly — cache, token row, page table — is born there:
        # an uncommitted array is allocated on the default device (chip 0)
        # and only follows the params at the first jitted call, so N
        # replicas would all allocate through chip 0. None (params on
        # several devices, or not yet on any) leaves jax's default.
        self._device = None
        if mesh is None:
            devs = {d for leaf in jax.tree.leaves(params)
                    if isinstance(leaf, jax.Array) for d in leaf.devices()}
            if len(devs) == 1:
                self._device = devs.pop()
        self._repl = None  # replicated sharding for host-visible outputs
        self.page_size = int(page_size)
        # prefill_chunk > 0: prompts longer than this are prefilled in
        # segments interleaved with decode chunks (llama.prefill_segment_
        # into) so one long prefill can't stall every live stream — the
        # TTFT-jitter fix (VERDICT r4 #2). Composes with the paged pool,
        # int8 caches, and speculation (the draft model still needs the
        # full history inside the largest prefill bucket; check_admissible
        # rejects prompts beyond that).
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk:
            if max_seq % self.prefill_chunk:
                # the dense segment program writes a fixed C-wide window; a
                # final window crossing capacity would CLAMP its start and
                # silently overwrite earlier prefilled rows (the paged
                # program routes overflow to scratch, but one rule is
                # simpler than two)
                raise ValueError(
                    f"max_seq {max_seq} must be a multiple of "
                    f"prefill_chunk {self.prefill_chunk}")
        self._chunked: dict[int, dict] = {}   # slot -> chunked-prefill state
        # round-robin across slots; deque: the hot path pops the head every
        # interleaved segment (list.pop(0) is O(n) and this runs per chunk)
        self._chunked_order: collections.deque[int] = collections.deque()
        self.evictions = 0  # slots truncated because the page pool ran dry
        if self.page_size:
            # Block-paged KV cache (llama.init_paged_cache): a shared page
            # pool + host-owned page tables instead of a dense [B, S_max]
            # rectangle per slot. HBM holds ACTUAL tokens, not worst case,
            # so the same memory serves more concurrent long-context
            # slots. n_pages defaults to the dense-equivalent so the
            # operator dials capacity down explicitly.
            if shard_cache or (
                    self._sp is None and mesh is not None
                    and getattr(cfg, "sequence_parallel", False)):
                raise ValueError(
                    "page_size composes with sequence parallelism only "
                    "through the serving plan (GOFR_ML_SP / sp=) — not "
                    "shard_cache or a bare cfg.attn_impl mesh")
            for b in (*self.prefill_buckets, max_seq):
                # max_seq included: it is the prefill-bucket fallback, and
                # a non-multiple would silently drop trailing prompt rows
                if b % self.page_size:
                    raise ValueError(
                        f"prefill bucket/max_seq {b} not a multiple of "
                        f"page_size")
            self._p_max = -(-max_seq // self.page_size)
            self.n_pages = n_pages or (1 + batch_slots * self._p_max)
            if self._sp is not None and self.n_pages % self._sp.shards:
                # striped pool: each device owns n_pages/shards pages —
                # round UP so the operator's capacity ask stays a floor
                self.n_pages += self._sp.shards - (
                    self.n_pages % self._sp.shards)
            self._shard_cache = False
            self._reset_cache_storage()
            # shared-prefix bookkeeping: per-slot count of BORROWED pages
            # (never freed back by this slot) and the owning prefix id
            self._prefixes: dict[int, dict] = {}
            self._next_prefix = 1
            self._prefix_clock = 0   # LRU stamp for prefix eviction
            self.prefix_evictions = 0
            # Host spill tier (kv_offload.py): evicting an idle prefix
            # copies its pages device→host instead of discarding, so the
            # next hit restores them with a DMA instead of a prefill.
            # ``host_kv`` None -> env GOFR_ML_KV_HOST_BUDGET_MB (0/unset
            # = tier off, today's discard behavior); False disables
            # explicitly; a HostKVStore instance is used as-is.
            if host_kv is None:
                host_kv = HostKVStore.from_env()
            # identity check, not truthiness: an EMPTY store is falsy
            # (len 0) but very much enabled
            self.host_kv = host_kv if host_kv is not False else None
            self.kv_spills = 0            # prefixes copied device->host
            self.kv_restores = 0          # prefixes copied host->device
            self.kv_restore_fallbacks = 0  # restores lost to pool pressure
            self.prefix_prefills = 0      # prefix KV builds actually paid

            cache_keys = tuple(k for k in self.cache if k != "len")

            def gather_pages(cache, pages):
                """Copy ``pages`` ([n_pg] int32) out of the pool — a fresh
                device buffer, so the pool pages are reusable the moment
                this dispatches (the D2H copy streams from the copy)."""
                return {k: jnp.take(cache[k], pages, axis=1)
                        for k in cache_keys}

            def scatter_pages(cache, pages, slabs):
                out = {k: cache[k].at[:, pages].set(slabs[k])
                       for k in cache_keys}
                out["len"] = cache["len"]
                return out

            self._gather_pages = jax.jit(gather_pages)
            # donate the pool: in-place page writes, no cache copy
            self._scatter_pages = jax.jit(scatter_pages,
                                          donate_argnums=(0,))
        elif shard_cache:
            # Multi-controller serving (ml/multihost.py): slots shard over
            # dp, kv heads over tp (matching SHARDING_RULES so decode never
            # reshards), and every array the host reads is explicitly
            # replicated. The cache is created INSIDE jit with out_shardings
            # — an eagerly-created array would be process-local and cannot
            # feed a global SPMD program.
            if mesh is None:
                raise ValueError("shard_cache requires a mesh")
            from ..parallel import NamedSharding
            from ..parallel import P as _P

            self._shard_cache = True
            self._repl = NamedSharding(mesh, _P())
            self._reset_cache_storage()
        else:
            self._shard_cache = False
            self._reset_cache_storage()
        self.slots = [_Slot() for _ in range(batch_slots)]
        # two independent streams: decode keys fold the step counter,
        # prefill keys fold a request counter — no collisions between the
        # two or between back-to-back add_request calls. Keys live as HOST
        # numpy values: under multi-controller an eagerly-created device key
        # would be process-local; a host value is replicated by contract
        # (every rank derives the identical key from the shared seed).
        root = jax.random.PRNGKey(seed)
        self._base_key = np.asarray(jax.random.fold_in(root, 0))
        self._prefill_key = np.asarray(jax.random.fold_in(root, 1))
        self._n_requests = 0
        self._tok_dev = self._repl_zeros((batch_slots,))  # device-resident
        self._inflight: collections.deque = collections.deque()  # [chunk, B] arrays
        self._pending_first: collections.deque = collections.deque()  # (slot, dev scalar)
        self.steps = 0
        self.settled = 0  # dispatches read back (_pop_process): the serving
        # loop reads its queue where this moved, after a wait on the device
        self.restarts = 0  # successful crash recoveries (recover())
        # chaos hook (testutil/faults.py): the serving layer installs a
        # FaultInjector here when GOFR_ML_FAULT is set; every instrumented
        # dispatch site guards with ``is not None`` so the disabled path
        # costs one attribute test, nothing else
        self.fault = None
        # flight recorder (gofr_tpu/flight_recorder.py): the serving layer
        # installs a DispatchRecorder here so step()/drain() can stamp the
        # decide/launch/device_wait/emit phases (``with phase(rec, …)``:
        # the shared no-op context while it is None)
        self.recorder = None
        # called once a processed dispatch, after the last of its
        # callbacks (``_fire_bursts``): the serving layer hands every
        # stream's burst to its consumers' loop there, in one wakeup
        self.after_bursts = None
        # goodput ledger handle (ml/goodput.py): the serving layer installs
        # a model-bound ModelGoodput here so the spec verify path and the
        # restore-fallback path can classify device tokens; same
        # is-not-None contract as the recorder (GOFR_ML_GOODPUT=0)
        self.goodput = None
        # program & compile telemetry (ml/programs.py): one row per jitted
        # program, recorded at warmup / first paged-op use — the
        # /debug/programs inventory the serving layer labels with its
        # model name
        self.programs = ProgramLog()
        # async-prefetch failures (satellite: the bare except around
        # copy_to_host_async must be observable — a broken prefetch path
        # degrades every dispatch silently otherwise)
        self.prefetch_errors = 0
        self._prefetch_warned = False
        self.prefill_segments_run = 0  # chunked-prefill segments dispatched
        # prompt tokens sent to, and rows x seq computed by, the dense
        # whole-prompt prefill programs: their ratio is the pad share
        self.prefill_tokens_real = 0
        self.prefill_tokens_padded = 0
        # a family with per-slot state that has no length axis sweeps every
        # slot's row of it each decode step, whoever holds the slot: rows
        # read and written, and those of them that decoded a request
        self.state_rows_swept = 0
        self.state_rows_live = 0

        sampler_cfg = self.sampler
        host_visible = self._host_visible
        sp_plan = self._sp
        # THE decode step of this (model, layout, SP plan), chosen here and
        # nowhere else: ``decode_one(params, tok, cache, table)``. The
        # dense layout takes its model's ``decode_step`` and no table —
        # under a dense SP plan traced with the plan's config clone
        # (attn_impl set), so the step attends the S-sharded cache through
        # sp_decode_attention; the page pool routes through the page
        # table, and a pool striped across the sp mesh through the
        # cross-device combine of ``sp_paged_decode_step``.
        if not self.page_size:
            decode_cfg = sp_plan.sp_cfg if sp_plan is not None else cfg

            def decode_one(params, tok, cache, table):
                return model.decode_step(params, tok, cache, decode_cfg,
                                         mesh=mesh)
        elif sp_plan is not None:
            def decode_one(params, tok, cache, table):
                return llama.sp_paged_decode_step(params, tok, cache, table,
                                                  cfg, mesh)
        else:
            def decode_one(params, tok, cache, table):
                return llama.paged_decode_step(params, tok, cache, table,
                                               cfg)

        def make_chunk_fn(n_chunk: int):
            def chunk_fn(params, tok, cache, step0, base_key, table=None):
                """``n_chunk`` fused decode+sample steps. Returns
                [n_chunk+1, B] tokens: row 0 is the INPUT token row (how
                newly-admitted slots' first sampled tokens reach the host — a
                separate per-admission transfer would cost a synchronous
                D2H of its own; this way firsts ride the chunk fetch that
                happens anyway), rows 1..n_chunk are this chunk's
                samples; plus the final carry. The paged layouts pass their
                page table (constant across the chunk — growth
                pre-allocates); the dense layout passes none."""
                tok_in = tok

                def body(carry, j):
                    tok, cache = carry
                    logits, cache = decode_one(params, tok, cache, table)
                    key = jax.random.fold_in(base_key, step0 + j)
                    nxt = _sample_impl(logits, key, sampler_cfg)
                    return (nxt, cache), nxt

                (tok, cache), toks = jax.lax.scan(
                    body, (tok, cache), jnp.arange(n_chunk)
                )
                block = jnp.concatenate([tok_in[None], toks], axis=0)
                return host_visible(block), host_visible(tok), cache

            # donate the cache AND the input token row: in-place KV update
            # on device, no copy per step, and the token-row buffer is
            # reused across dispatches instead of reallocated (part of the
            # dispatch-launch fusion — fewer allocator round-trips per
            # program). The page table (last arg, paged mode) is NOT
            # donated: it is a device-cached host upload reused until the
            # table actually changes (_table_device). The trace readers
            # find decode programs by these two names.
            return _jit("paged_chunk_fn" if self.page_size else "chunk_fn",
                        chunk_fn, donate_argnums=(1, 2))

        # EOS membership as a host constant the jitted window programs
        # embed — the device-side mirror of _apply_burst's np.isin, so the
        # on-device early exit and the host truncation agree exactly
        eos_const = (np.asarray(sorted(self._eos), np.int32)
                     if self._eos else None)

        def is_eos_dev(t):
            """Elementwise EOS membership for any-shaped int32 tokens."""
            if eos_const is None:
                return jnp.zeros(t.shape, bool)
            return jnp.any(t[..., None] == eos_const, axis=-1)

        def make_window_fn(n_win: int):
            """One FUSED decode window: up to ``n_win`` sampling steps in
            ONE jitted program (paged cache only). Per-slot early-exit
            masks — EOS, the remaining ``max_new``/capacity budget, the
            deadline step bound — freeze finished rows on device (their
            token and page-table ``len`` stop advancing), and a whole-batch
            ``lax.cond`` skips the model sweep entirely once every row is
            frozen. The host drains ONE async D2H per window instead of
            one per chunk dispatch: this is the launch-share collapse the
            flight recorder measures.

            Signature: (params, tok, cache, step0, base_key, active0 [B]
            bool, step_cap [B] int32, table) -> (block [n_win+1, B] with
            row 0 the input-token ride-along, n_out [B] tokens emitted per
            row, realized scalar steps actually run, carry tok, cache)."""
            def window_fn(params, tok, cache, step0, base_key, active0,
                          step_cap, table):
                tok_in = tok
                # pre-mask: a row whose input token is already EOS (a
                # first token the host hasn't folded in yet) or whose
                # step budget is zero must not emit anything
                active0 = active0 & ~is_eos_dev(tok) & (step_cap > 0)

                def run(carry, j):
                    tok, cache0, active, n_out, realized = carry
                    logits, cache2 = decode_one(params, tok, cache0, table)
                    key = jax.random.fold_in(base_key, step0 + j)
                    nxt = _sample_impl(logits, key, sampler_cfg)
                    # freeze finished rows: token and len stop advancing
                    # (the KV row their garbage step wrote sits past their
                    # final len and is never attended)
                    nxt = jnp.where(active, nxt, tok)
                    cache2 = {**cache2,
                              "len": jnp.where(active, cache2["len"],
                                               cache0["len"])}
                    n_out = n_out + active.astype(jnp.int32)
                    active = active & ~is_eos_dev(nxt) & (n_out < step_cap)
                    return (nxt, cache2, active, n_out, realized + 1), nxt

                def body(carry, j):
                    # whole-batch early exit: once every row is frozen the
                    # remaining scan iterations skip the model sweep
                    return jax.lax.cond(
                        jnp.any(carry[2]), run,
                        lambda c, _j: (c, c[0]), carry, j)

                carry0 = (tok, cache, active0,
                          jnp.zeros(tok.shape, jnp.int32), jnp.int32(0))
                (tok, cache, _act, n_out, realized), toks = jax.lax.scan(
                    body, carry0, jnp.arange(n_win))
                block = jnp.concatenate([tok_in[None], toks], axis=0)
                return block, n_out, realized, tok, cache

            # same donation contract as the chunk ladder: cache + token
            # row in place, the page table reused un-donated
            return jax.jit(window_fn, donate_argnums=(1, 2))

        self._is_eos_dev = is_eos_dev  # _init_spec's windowed fns reuse it

        # Pre-jitted chunk ladder: one decode program per power-of-two size
        # up to `chunk`. The fixed path only ever uses `chunk` and the
        # 1-step TTFT mini-chunk; the token-budget scheduler picks the
        # ladder entry that fills the per-dispatch budget given live slots.
        # Window mode swaps the entry factory: ladder entries ARE window
        # sizes and every program carries the early-exit machinery.
        self._chunk_ladder = _chunk_ladder(self.chunk)
        make_decode_fn = (make_window_fn if self.decode_window
                          else make_chunk_fn)
        self._chunk_fns = {n: make_decode_fn(n) for n in self._chunk_ladder}
        # the PLAIN decode ladder survives _init_spec's spec-window ladder:
        # when adaptive speculation has disabled every decodable slot
        # (lookup mode), step() degrades the whole dispatch to these —
        # full budget efficiency instead of paying K+1 verify positions
        # per always-rejected draft
        self._plain_fns = self._chunk_fns
        self._chunk_fn = self._chunk_fns[self.chunk]
        # TTFT path: a 1-step mini-chunk dispatched while first tokens are
        # pending, so a new request's first token reaches the host ~one full
        # chunk earlier instead of waiting out `chunk` decode steps.
        self._mini_chunk_fn = self._chunk_fns[1]
        # Adaptive token budget: None -> env GOFR_ML_TOKEN_BUDGET
        # ("auto"/unset picks a default; "0" disables). 0/negative ->
        # fixed-chunk dispatch. The auto budget guarantees two invariants
        # at the neutral 0.5 split: the decode share stays >= chunk *
        # batch_slots (budget >= 2 * chunk * slots, so the steady-state
        # dispatch never shrinks below the fixed path's while a prompt
        # prefills), and a light batch can still fit two prefill segments
        # in the remainder (budget >= decode cost + 2 * prefill_chunk) —
        # a budget equal to the decode cost alone would make the
        # scheduler strictly pay overhead without buying prefill progress.
        # Under speculation one ladder step costs K+1 device positions per
        # row (plan() charges unit_tokens=K+1), so the auto budget scales
        # by the same factor — the steady-state window count matches the
        # plain path's chunk count instead of collapsing the ladder.
        per_step = (self.spec_k + 1) if self.spec_k else 1
        if token_budget is None:
            raw = os.environ.get("GOFR_ML_TOKEN_BUDGET", "auto")
            token_budget = (max(2 * self.chunk * batch_slots * per_step,
                                self.chunk * batch_slots * per_step
                                + 2 * self.prefill_chunk)
                            if raw.strip().lower() in ("", "auto")
                            else int(raw))
        self.scheduler = (
            TokenBudgetScheduler(token_budget, self._chunk_ladder,
                                 self.prefill_chunk, slots=batch_slots)
            if token_budget > 0 else None)
        if self.scheduler is not None and self.decode_window:
            # same budget arithmetic, honest labeling: plan() picks ladder
            # entries that are now WINDOW sizes (K steps/slot per entry)
            self.scheduler.window_mode = True

        def post_prefill(tok_dev, logits, prefill_key, n_req, slot):
            """Sample the first token and park it in the device-resident
            token row — ONE program with traced (n_req, slot). An eager
            ``fold_in(key, python_int)`` + ``.at[int].set(int)`` here
            compiled a fresh trivial executable per request (per counter
            value and even per sampled token value): a compile on every
            admission, far dearer than the prefill itself."""
            key = jax.random.fold_in(prefill_key, n_req)
            first = _sample_impl(logits, key, sampler_cfg)[0]
            return host_visible(tok_dev.at[slot].set(first))

        self._post_prefill = jax.jit(post_prefill, donate_argnums=(0,))
        if "moe_counts" in self.cache:
            self._copy_counts = _jit("copy_counts", lambda a: jnp.copy(a))
            # what ``pool_stats()`` reads until the first dispatch: zeros
            self._counts_dev = np.zeros(self.cache["moe_counts"].shape,
                                        np.uint32)
        if self.page_size:
            ps = self.page_size
            self._prefill_paged = _jit(
                "paged_prefill",
                lambda p, t, l, c, row, slot: llama.paged_prefill_into(
                    p, t, l, cfg, c, row, slot, ps),
                donate_argnums=(3,),
            )

            def make_suffix_prefill(name: str, set_len: bool):
                def f(p, t, l, c, row, start, slot):
                    logits, c2 = llama.paged_suffix_prefill(
                        p, t, l, cfg, c, row, start, ps)
                    if set_len:  # a slot admission; prefix builds skip it
                        c2 = {**c2,
                              "len": c2["len"].at[slot].set(start + l[0])}
                    return logits, c2
                return _jit(name, f, donate_argnums=(3,))

            self._suffix_prefill = make_suffix_prefill("suffix_prefill", True)
            self._prefix_prefill = make_suffix_prefill("prefix_prefill",
                                                       False)
        self._prefill_into = _jit(
            "prefill_into",
            lambda p, t, l, c, slot: model.prefill_into(p, t, l, cfg, c, slot,
                                                        mesh=mesh),
            donate_argnums=(3,),
        )
        if self._sp is not None:
            # the sequence-parallel prefill family: same landing scatter
            # as the single-device programs, the forward traced with the
            # sp config clone so attention shards the prompt over the
            # mesh (ring/ulysses). Prompts under min_tokens never touch
            # these — the dual-path threshold routes them to the plain
            # programs above.
            sp_cfg = self._sp.sp_cfg
            if self.page_size:
                ps = self.page_size

                def make_sp_paged(name: str, set_len: bool):
                    def f(p, t, l, c, row, slot):
                        return llama.paged_prefill_into(
                            p, t, l, sp_cfg, c, row, slot, ps, mesh=mesh,
                            set_len=set_len)
                    return _jit(name, f, donate_argnums=(3,))

                self._sp_prefill_paged = make_sp_paged("sp_paged_prefill",
                                                       True)
                # prefix builds (register_prefix / the disagg ship path)
                # fill pages without admitting a slot
                self._sp_prefix_paged = make_sp_paged("sp_prefix_prefill",
                                                      False)
            else:
                self._sp_prefill_into = _jit(
                    "sp_prefill_into",
                    lambda p, t, l, c, slot: llama.prefill_into(
                        p, t, l, sp_cfg, c, slot, mesh=mesh),
                    donate_argnums=(3,))
        if self.prefill_chunk:
            if self.page_size:
                ps = self.page_size

                def paged_segment_prefill(p, t, l, c, row, start, slot,
                                          new_len):
                    logits, c2 = llama.paged_suffix_prefill(
                        p, t, l, cfg, c, row, start, ps)
                    return logits, {**c2, "len":
                                    c2["len"].at[slot].set(new_len)}

                self._segment_prefill_paged = jax.jit(paged_segment_prefill,
                                                      donate_argnums=(3,))
            else:
                self._segment_prefill = _jit(
                    "segment_prefill",
                    lambda p, t, l, c, slot, start, new_len:
                    llama.prefill_segment_into(p, t, l, cfg, c, slot, start,
                                               new_len, mesh=mesh),
                    donate_argnums=(3,),
                )

        # -- speculative decoding (device-resident prompt lookup) ----------
        # (self.spec_k was parsed and validated at the top of __init__)
        self.spec_ngram = int(spec_ngram)
        self._tokens_dev = None
        # draft-model speculation: a small shared-vocab model proposes the
        # K draft tokens instead of prompt lookup (VERDICT r4 #7) — its own
        # dense fp cache rides the jitted window as donated state
        if (draft_params is None) != (draft_cfg is None):
            raise ValueError("draft_params and draft_cfg come together")
        if draft_params is not None and not self.spec_k:
            raise ValueError("a draft model requires spec_k > 0")
        if draft_cfg is not None and draft_cfg.vocab_size != cfg.vocab_size:
            raise ValueError("draft and target must share the vocabulary")
        if draft_cfg is not None and getattr(draft_cfg, "kv_quant", False):
            raise ValueError("the draft model uses the fp cache")
        self.draft_params = draft_params
        self.draft_cfg = draft_cfg
        self._draft_cache: Any = {}  # empty pytree when no draft model
        # draft efficiency: emitted / windows - 1 == avg accepted per window
        self.spec_windows = 0
        self.spec_emitted = 0
        if self.spec_k > 0:
            self._init_spec()

    def _init_spec(self) -> None:
        """Speculative decoding INSIDE the continuous-batching loop:
        prompt-lookup drafting, the K+1-token verify window
        (llama.decode_window), acceptance, and per-slot history all live in
        the jitted chunk program. ml/speculate.py's single-stream loop pays
        a host round-trip per window (drafts from host history, acceptance
        on host), which would erase the speedup; device-resident
        speculation preserves the one-dispatch-deep async pipeline, so it
        composes with continuous batching for free. Greedy verify is
        LOSSLESS: every emitted token is the verifier's own argmax chain —
        a bad draft costs speed, never correctness. One "window" replaces
        one decode step and emits 1..K+1 tokens for the same weight sweep
        out of HBM."""
        from ..models import llama  # speculation is the llama block's

        cfg = self.cfg
        mesh = self.mesh
        if self.sampler.temperature > 0:
            raise ValueError("speculative decode is greedy-only")
        K = self.spec_k
        hist_cap = self.max_seq + K + 2
        self._hist_cap = hist_cap
        B = self.batch_slots
        self._tokens_dev = self._repl_zeros((B, hist_cap))
        host_visible = self._host_visible
        draft_params, draft_cfg = self.draft_params, self.draft_cfg
        if draft_params is not None:
            # the draft's dense fp cache: sized past max_seq so the K+1
            # draft steps of the last window never clip
            with jax.default_device(self._device):
                self._draft_cache = llama.init_cache(draft_cfg, B,
                                                     self.max_seq + K + 2)

        ngrams = tuple(range(max(1, self.spec_ngram), 0, -1))

        def draft_row(td_row, h):
            """Longest-trailing-n-gram lookup over one row's history
            (td_row [hist_cap], h = history length): find the most recent
            earlier occurrence of the trailing n-gram and copy the K tokens
            that followed it. All masked integer compares — O(hist_cap)
            VPU work, invisible next to the layer matmuls."""
            idx = jnp.arange(hist_cap)
            candidates = []
            for n in ngrams:
                pat = jax.lax.dynamic_slice(
                    td_row, (jnp.maximum(h - n, 0),), (n,))
                # follow token must exist INSIDE history; this also
                # excludes the trailing pattern matching itself
                m = (idx + n) <= (h - 1)
                for i in range(n):
                    m &= jnp.take(td_row, idx + i, mode="clip") == pat[i]
                candidates.append((jnp.max(jnp.where(m, idx, -1)), n))
            start = jnp.int32(-1)
            npick = jnp.int32(0)
            for j, n in candidates:  # longest n with a match wins
                take = (start < 0) & (j >= 0)
                start = jnp.where(take, j, start)
                npick = jnp.where(take, jnp.int32(n), npick)
            # no match: draft a repeat of the last token (cheap, usually
            # rejected — the window still emits its one verified token)
            src = jnp.where(start >= 0, start + npick, h - 1)
            return jax.lax.dynamic_slice(td_row, (src,), (K,))

        # the verify window of this layout, chosen once: ``verify(params,
        # window, cache, table) -> (logits, cache, capacity)``; ``len`` is
        # capped at ``capacity`` (a row's virtual pages, or the dense row).
        # Paged mode routes window writes/reads through the page table.
        if self.page_size:
            page_s = self.page_size

            def verify(params, window, cache, table):
                logits, cache = llama.paged_decode_window(
                    params, window, cache, table, cfg)
                return logits, cache, table.shape[1] * page_s
        else:
            def verify(params, window, cache, table):
                logits, cache = llama.decode_window(
                    params, window, cache, cfg, mesh=mesh)
                return logits, cache, cache["k"].shape[2]

        def draft_one(tok, dcache):
            return llama.decode_step(draft_params, tok, dcache, draft_cfg)

        def run_draft_model(tok, dcache):
            """Propose K tokens with the draft model: K sequential greedy
            draft steps (the window input token first), plus one extra
            step writing d_K's KV row — a fully-accepted window needs that
            row in place before the next round. Returns ([B, K] drafts,
            updated draft cache). ~2K+1 small-model sweeps per window; the
            target's single big sweep still dominates."""
            def dstep(carry, _):
                t, dc = carry
                dlogits, dc = draft_one(t, dc)
                nxt = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
                return (nxt, dc), nxt

            (last, dcache), drafts = jax.lax.scan(
                dstep, (tok, dcache), None, length=K)
            _, dcache = draft_one(last, dcache)
            return jnp.moveaxis(drafts, 0, 1), dcache

        windowed = bool(self.decode_window)
        is_eos_dev = self._is_eos_dev

        def make_spec_chunk_fn(n_windows: int):
            def spec_window_fn(params, tok, cache, tokens_dev, draft_cache,
                               spec_on, active0, step_cap, table):
                """Fused-window speculation: spec verify windows ARE the
                K-step windows. Each scan iteration drafts, verifies, and
                accepts exactly like ``spec_chunk_fn`` below, but per-slot
                early-exit masks fold into the accept path: a frozen row
                (EOS emitted, step budget spent) emits nothing and stops
                advancing, and a whole-batch ``lax.cond`` skips the sweep
                once every row froze. Capping a row's emit count below
                n_acc+1 is LOSSLESS — the capped prefix is the verifier's
                own greedy chain. Returns (row0, emits [W, B, K+1], counts
                [W, B], realized scalar windows actually run, carry tok,
                cache, tokens_dev, draft_cache)."""
                tok_in = tok
                ar = jnp.arange(K + 1)[None, :]
                rows = jnp.arange(B)
                active0 = active0 & ~is_eos_dev(tok) & (step_cap > 0)

                def run(carry):
                    tok, cache, td, dcache, active, n_out, realized = carry
                    h = cache["len"] + 1  # [B] history length
                    if draft_params is not None:
                        draft, dcache = run_draft_model(tok, dcache)
                    else:
                        draft = jax.vmap(draft_row)(td, h)       # [B, K]
                    window = jnp.concatenate([tok[:, None], draft], axis=1)
                    logits, cache, S_max = verify(params, window, cache,
                                                  table)
                    greedy_t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    match = (draft == greedy_t[:, :K]).astype(jnp.int32)
                    n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                    n_acc = jnp.where(spec_on & active, n_acc, 0)
                    g_last = jnp.take_along_axis(greedy_t, n_acc[:, None], 1)
                    draft_pad = jnp.concatenate(
                        [draft, jnp.zeros((B, 1), jnp.int32)], axis=1)
                    emit = jnp.where(
                        ar < n_acc[:, None], draft_pad,
                        jnp.where(ar == n_acc[:, None], g_last, 0))
                    # the early-exit fold: frozen rows emit nothing;
                    # active rows cap at their remaining step budget
                    # (>= 1 by the active mask, so the verified next
                    # token always lands)
                    n_emit = jnp.where(
                        active,
                        jnp.minimum(n_acc + 1,
                                    jnp.maximum(step_cap - n_out, 0)),
                        0)
                    emit = jnp.where(ar < n_emit[:, None], emit, 0)
                    new_len = jnp.minimum(cache["len"] + n_emit, S_max)
                    cache = {**cache, "len": new_len}
                    if draft_params is not None:
                        d_S = dcache["k"].shape[2]
                        dcache = {**dcache,
                                  "len": jnp.minimum(new_len, d_S)}
                    widx = jnp.where(ar < n_emit[:, None],
                                     h[:, None] + ar, hist_cap)
                    td = td.at[rows[:, None], widx].set(emit, mode="drop")
                    # carry token = the LAST token this row emitted (its
                    # next window continues the verified chain even when
                    # the budget cap truncated the accepted prefix);
                    # frozen rows keep their token
                    last = jnp.take_along_axis(
                        emit, jnp.maximum(n_emit - 1, 0)[:, None], 1)[:, 0]
                    tok = jnp.where(n_emit > 0, last, tok)
                    n_out = n_out + n_emit
                    hit = jnp.any((ar < n_emit[:, None]) & is_eos_dev(emit),
                                  axis=1)
                    active = active & ~hit & (n_out < step_cap)
                    return ((tok, cache, td, dcache, active, n_out,
                             realized + 1), (emit, n_emit))

                def body(carry, _):
                    def skip(c):
                        return c, (jnp.zeros((B, K + 1), jnp.int32),
                                   jnp.zeros((B,), jnp.int32))
                    return jax.lax.cond(jnp.any(carry[4]), run, skip, carry)

                carry0 = (tok, cache, tokens_dev, draft_cache, active0,
                          jnp.zeros((B,), jnp.int32), jnp.int32(0))
                (tok, cache, tokens_dev, draft_cache, _act, _n_out,
                 realized), (emits, counts) = jax.lax.scan(
                    body, carry0, None, length=n_windows)
                return (host_visible(tok_in), host_visible(emits),
                        host_visible(counts), host_visible(realized),
                        host_visible(tok), cache, tokens_dev, draft_cache)

            def spec_chunk_fn(params, tok, cache, tokens_dev, draft_cache,
                              spec_on, table=None):
                """``n_windows`` draft→verify→accept rounds. Returns
                (input token row [B] — the firsts ride-along, as in the
                plain chunk — emitted candidates [W, B, K+1], emit counts
                [W, B], final carry tok, cache, tokens_dev, draft_cache).
                Drafts come from the draft model when one is configured,
                else prompt lookup; ``draft_cache`` is the empty pytree in
                lookup mode. ``spec_on`` [B] bool masks ADAPTIVE per-slot
                disable: a masked row accepts nothing, so it emits exactly
                its verified next token per window — plain greedy decode
                at window cadence, bit-identical (the window's position-0
                logits depend only on the prefix + input token)."""
                tok_in = tok
                ar = jnp.arange(K + 1)[None, :]
                rows = jnp.arange(B)

                def body(carry, _):
                    tok, cache, td, dcache = carry
                    h = cache["len"] + 1  # [B] history length
                    if draft_params is not None:
                        draft, dcache = run_draft_model(tok, dcache)
                    else:
                        draft = jax.vmap(draft_row)(td, h)       # [B, K]
                    window = jnp.concatenate([tok[:, None], draft], axis=1)
                    logits, cache, S_max = verify(params, window, cache,
                                                  table)
                    greedy_t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    match = (draft == greedy_t[:, :K]).astype(jnp.int32)
                    n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                    # adaptively-disabled rows accept nothing: their one
                    # emitted token is the verifier's own argmax — plain
                    # decode, losslessly
                    n_acc = jnp.where(spec_on, n_acc, 0)
                    g_last = jnp.take_along_axis(greedy_t, n_acc[:, None], 1)
                    draft_pad = jnp.concatenate(
                        [draft, jnp.zeros((B, 1), jnp.int32)], axis=1)
                    # emitted: accepted draft prefix + the verifier's own
                    # next token at position n_acc
                    emit = jnp.where(
                        ar < n_acc[:, None], draft_pad,
                        jnp.where(ar == n_acc[:, None], g_last, 0))
                    n_emit = n_acc + 1
                    new_len = jnp.minimum(cache["len"] + n_emit, S_max)
                    cache = {**cache, "len": new_len}
                    if draft_params is not None:
                        # the draft fed tok,d1..dK itself, so rows for every
                        # accepted token exist — roll its len back to the
                        # target's (rejected rows are overwritten next round)
                        d_S = dcache["k"].shape[2]
                        dcache = {**dcache,
                                  "len": jnp.minimum(new_len, d_S)}
                    # append emitted tokens to history; rejected positions
                    # route to hist_cap and drop
                    widx = jnp.where(ar < n_emit[:, None],
                                     h[:, None] + ar, hist_cap)
                    td = td.at[rows[:, None], widx].set(emit, mode="drop")
                    return (g_last[:, 0], cache, td, dcache), (emit, n_emit)

                carry0 = (tok, cache, tokens_dev, draft_cache)
                (tok, cache, tokens_dev, draft_cache), (emits, counts) = \
                    jax.lax.scan(body, carry0, None, length=n_windows)
                return (host_visible(tok_in), host_visible(emits),
                        host_visible(counts), host_visible(tok), cache,
                        tokens_dev, draft_cache)

            # donate tok + cache + history + draft cache (the token row
            # rides its buffer across dispatches, like the plain ladder)
            return jax.jit(spec_window_fn if windowed else spec_chunk_fn,
                           donate_argnums=(1, 2, 3, 4))

        # spec mode replaces the PRIMARY ladder (the plain one survives in
        # self._plain_fns for the all-disabled fallback): entries are
        # verify WINDOWS (each emits 1..K+1 tokens); the budget scheduler
        # charges them at K+1 tokens per decodable row (plan(unit_tokens)),
        # which keeps the decode/prefill split honest about device time
        self._chunk_fns = {n: make_spec_chunk_fn(n)
                           for n in self._chunk_ladder}
        self._chunk_fn = self._chunk_fns[self.chunk]
        self._mini_chunk_fn = self._chunk_fns[1]
        # the all-disabled plain-ladder fallback needs two things a draft
        # model can't give: drafting state that survives plain dispatches
        # (prompt-lookup history does, via the host mirror + row re-seed;
        # a draft model's own KV cache does not) and an auto-disable floor
        # actually set. Draft mode still disables per slot via the mask.
        self._plain_armed = (self.spec_min_accept > 0
                             and draft_params is None)

        def reseed_hist(rows):
            """Replace the device drafting history wholesale from the
            host mirror — the plain→spec transition repair. ONE upload
            for the whole batch: per-slot row writes would pay one
            program launch per live slot at every re-probe transition."""
            return host_visible(jnp.asarray(rows))

        self._reseed_hist = jax.jit(reseed_hist)

        def spec_post_prefill(tok_dev, tokens_dev, logits, prompt, lens,
                              slot):
            """Greedy first token + write prompt and first token into the
            slot's history row (device drafting needs the full history)."""
            length = lens[0]
            first = jnp.argmax(logits[0]).astype(jnp.int32)
            tok_dev = host_visible(tok_dev.at[slot].set(first))
            bucket = prompt.shape[1]
            arb = jnp.arange(bucket)
            cur = jax.lax.dynamic_slice(tokens_dev, (slot, jnp.int32(0)),
                                        (1, bucket))
            row = jnp.where(arb[None, :] < length, prompt, cur)
            tokens_dev = jax.lax.dynamic_update_slice(
                tokens_dev, row, (slot, jnp.int32(0)))
            tokens_dev = tokens_dev.at[slot, length].set(first)
            return tok_dev, host_visible(tokens_dev)

        self._spec_post_prefill = jax.jit(spec_post_prefill,
                                          donate_argnums=(0, 1))

        def spec_prefix_post(tok_dev, tokens_dev, logits, row, length,
                             slot):
            """Prefixed admission under speculation: the slot's history
            row is the FULL prefix+suffix (drafting context), written
            whole — one [hist_cap] int32 transfer — plus the greedy first
            token at position ``length``."""
            first = jnp.argmax(logits[0]).astype(jnp.int32)
            tok_dev = host_visible(tok_dev.at[slot].set(first))
            row = row.at[length].set(first)
            tokens_dev = jax.lax.dynamic_update_slice(
                tokens_dev, row[None], (slot, jnp.int32(0)))
            return tok_dev, host_visible(tokens_dev)

        self._spec_prefix_post = jax.jit(spec_prefix_post,
                                         donate_argnums=(0, 1))

        if draft_params is not None:
            # the draft must ingest every admitted prompt too: its cache
            # rows are the drafting context (same buckets as the target
            # prefill, so warmup compiles both together)
            self._draft_prefill_into = _jit(
                "draft_prefill_into",
                lambda p, t, l, c, s: llama.prefill_into(
                    p, t, l, draft_cfg, c, s),
                donate_argnums=(3,))

    def _after_prefill(self, logits, tokens, lens, slot) -> None:
        """Route one prompt's prefill logits into first-token state — spec
        mode also records prompt + first into the history row. One site,
        shared by warmup and admission so compiled shapes always stay
        warm."""
        if self.spec_k:
            self._tok_dev, self._tokens_dev = self._spec_post_prefill(
                self._tok_dev, self._tokens_dev, logits, tokens, lens, slot)
            if self.draft_params is not None:
                _, self._draft_cache = self._draft_prefill_into(
                    self.draft_params, tokens, lens, self._draft_cache,
                    slot)
        else:
            self._tok_dev = self._post_prefill(
                self._tok_dev, logits, self._prefill_key,
                np.uint32(self._n_requests), slot)

    # -- paged-pool bookkeeping (page_size > 0) ------------------------------
    def _pop_free_page(self) -> int | None:
        """One page off the free pool, or None when dry. Striped (SP)
        mode round-robins across the per-device stacks so a slot's
        consecutive virtual pages land on different shards — the page
        striping that spreads one long context across every HBM."""
        if self._free_dev is None:
            return self._free_pages.pop() if self._free_pages else None
        n = len(self._free_dev)
        for i in range(n):
            d = (self._stripe_rr + i) % n
            if self._free_dev[d]:
                self._stripe_rr = (d + 1) % n
                return self._free_dev[d].pop()
        return None

    def _return_pages(self, pages) -> None:
        """Give pages back to the pool (their owning device's stack in
        striped mode — a page's shard is fixed by its id)."""
        if self._free_dev is None:
            self._free_pages.extend(pages)
            return
        p_loc = self.n_pages // len(self._free_dev)
        for pg in pages:
            self._free_dev[pg // p_loc].append(pg)

    def _n_free_pages(self) -> int:
        if self._free_dev is None:
            return len(self._free_pages)
        return sum(len(stack) for stack in self._free_dev)

    def _alloc_pages_to(self, slot: int, upto_len: int) -> bool:
        """Grow the slot's page list to cover ``upto_len`` virtual
        positions (in order — virtual offsets stay contiguous). False when
        the pool ran dry; the caller picks the policy."""
        need = min(-(-upto_len // self.page_size), self._p_max)
        pages = self._slot_pages[slot]
        while len(pages) < need:
            pg = self._pop_free_page()
            if pg is None:
                return False
            pages.append(pg)
            self._table[slot, len(pages) - 1] = pg
            self._table_dirty = True
        return True

    def _pages_ever_free(self) -> int:
        """Pool pages that could EVER be free: everything except the
        scratch page and pages held by registered prefixes. A request
        needing more than this can never admit — reject it instead of
        requeueing forever."""
        held = sum(len(i["pages"]) for i in self._prefixes.values()
                   if i["refs"] > 0)  # idle prefixes are reclaimable cache
        return (self.n_pages - 1) - held

    def _free_slot_pages(self, slot: int) -> None:
        shared = self._slot_shared[slot] if self.page_size else 0
        self._return_pages(self._slot_pages[slot][shared:])
        if shared:
            pid = self._slot_prefix[slot]
            if pid in self._prefixes:
                self._prefixes[pid]["refs"] -= 1
            self._slot_shared[slot] = 0
            self._slot_prefix[slot] = None
        self._slot_pages[slot] = []
        self._table[slot, :] = 0
        self._table_dirty = True

    def _grow_pages(self) -> None:
        """Pre-allocate pages for the upcoming dispatch: host bookkeeping
        lags one chunk, so cover produced + a pipeline margin. A dry pool
        TRUNCATES the growing slot — it finishes early with the tokens it
        has (counted in ``evictions``) rather than corrupting neighbors."""
        per_dispatch = (self.spec_k + 1) if self.spec_k else 1
        margin = self.chunk * (len(self._inflight) + 2) * per_dispatch
        for i, s in enumerate(self.slots):
            if not s.live:
                continue
            est = min(s.prompt_len + s.produced + margin,
                      s.prompt_len + s.max_new,  # never past its budget
                      self.max_seq)
            if not self._alloc_pages_to(i, est):
                # idle prefix pages are reclaimable cache — spend them
                # before truncating a live stream
                need = -(-est // self.page_size) - len(self._slot_pages[i])
                self._reclaim_prefix_pages(max(need, 1))
                if self._alloc_pages_to(i, est):
                    continue
                s.live = False
                s.evicted = True  # distinguishable from eos/length finishes
                self.evictions += 1

    def _table_device(self):
        """The device-resident page table for the next chunk dispatch,
        re-uploaded only when the host copy changed — before this, every
        paged launch re-staged the [B, P_max] table H2D (part of the
        PR-7-measured ~59% launch share). Under a mesh the host array is
        passed through unchanged (a device_put here would COMMIT it to
        one device and fight GSPMD's placement)."""
        if self.mesh is not None:
            return self._table
        if self._table_dirty or self._table_dev is None:
            self._table_dev = jax.device_put(self._table, self._device)
            self._table_dirty = False
        return self._table_dev

    @property
    def free_pages(self) -> int:
        return self._n_free_pages() if self.page_size else 0

    def pool_stats(self) -> dict:
        """KV/slot occupancy snapshot for gauges and /debug/serving — the
        numbers an operator sizes batch_slots and n_pages by."""
        out = {
            "slots": self.batch_slots,
            "live": self.n_live,
            "decode_steps": self.steps,
            "evictions": self.evictions,
            "chunked_prefills": len(self._chunked),
            "prefill_segments": self.prefill_segments_run,
            "prefill_tokens_real": self.prefill_tokens_real,
            "prefill_tokens_padded": self.prefill_tokens_padded,
            "prefetch_errors": self.prefetch_errors,
            "restarts": self.restarts,
        }
        if self.page_size:
            cache = dict(self.cache)
            # bytes ONE pool page costs across every cache plane (values +
            # scale/zero), from array avals (valid even for donated
            # buffers): the number the GOFR_ML_KV_BITS halving claim is
            # audited against
            page_bytes = sum(int(arr.nbytes) // self.n_pages
                             for key, arr in cache.items() if key != "len")
            value_bytes = sum(int(cache[key].nbytes) // self.n_pages
                              for key in ("k", "v") if key in cache)
            out.update(
                page_size=self.page_size,
                n_pages=self.n_pages,
                free_pages=self.free_pages,
                kv_bits=getattr(self.cfg, "kv_bits", 16),
                page_bytes=page_bytes,
                page_value_bytes=value_bytes,
                prefix_evictions=getattr(self, "prefix_evictions", 0),
                registered_prefixes=len(getattr(self, "_prefixes", {})),
                pinned_prefixes=sum(
                    1 for i in getattr(self, "_prefixes", {}).values()
                    if i.get("pinned")),
                kv_spills=self.kv_spills,
                kv_restores=self.kv_restores,
                kv_restore_fallbacks=self.kv_restore_fallbacks,
                prefix_prefills=self.prefix_prefills,
            )
        if "state" in self.cache:
            # a family with per-slot state that has no length axis
            # (models/slot_state.py): how the slots' memory divides, and
            # what the state's sweep was spent on
            cache = dict(self.cache)
            out.update(
                recurrent_state_bytes=int(cache["state"].nbytes
                                          + cache["conv"].nbytes),
                kv_cache_bytes=int(cache["k"].nbytes + cache["v"].nbytes),
                state_rows_swept=self.state_rows_swept,
                state_rows_live=self.state_rows_live)
        if "moe_counts" in self.cache:
            out.update(self._expert_counts())  # what routing did so far
        return out

    def _keep_counts(self) -> None:
        """After a dispatch, on the serving thread: a copy of the model's
        routing counters in a buffer of its own. The cache they are summed
        in is donated to every program, so a reader on another thread
        could never hold it; the copy is a program of a few bytes a
        dispatch, and no transfer."""
        self._counts_dev = self._copy_counts(self.cache["moe_counts"])

    def _expert_counts(self) -> dict:
        """The model's routing counters (its ``MOE_COUNTERS``) as the last
        dispatch left them: fetched from the device only here."""
        words = np.asarray(self._counts_dev)
        return {name: int(low) | int(high) << 32
                for name, (low, high) in zip(self._m.MOE_COUNTERS, words,
                                             strict=True)}

    # -- shared-prefix prefill (paged mode) ----------------------------------
    def register_prefix(self, prefix_ids, pinned: bool = False) -> int:
        """Compute a shared prefix's KV pages ONCE; requests then admit
        with ``prefix=<id>`` and prefill only their SUFFIX while attending
        the shared pages read-only. Sharing needs no copy-on-write: decode
        never writes below a slot's own start position, so the prefix
        pages are immutable by construction. Only WHOLE pages are shared —
        the remainder (< page_size tokens) re-prefills with each suffix.

        ``pinned`` prefixes (the explicit registration API) are evicted
        under pool pressure only as a LAST RESORT — after every unpinned
        (auto-promoted) idle candidate; borrowed prefixes never evict.

        The vLLM-style system-prompt lever: N concurrent chat slots pay
        the prefix's HBM and prefill compute once instead of N times.
        """
        if not self.page_size:
            raise ValueError("prefix sharing requires page_size > 0")
        ids = np.asarray(prefix_ids, np.int32).reshape(-1)
        ps = self.page_size
        shared_len = (len(ids) // ps) * ps
        n_need = shared_len // ps
        if self._n_free_pages() < n_need:
            # drop idle (refs == 0) prefixes LRU-first before giving up —
            # a rotating set of system prompts must not brick registration
            self._reclaim_prefix_pages(n_need)
        if self._n_free_pages() < n_need:
            raise PagePoolExhausted(
                f"prefix needs {n_need} pages, {self.free_pages} free")
        pages = [self._pop_free_page() for _ in range(n_need)]
        if shared_len:
            bucket = next((b for b in self.prefill_buckets
                           if shared_len <= b), None)
            if bucket is None and not self.prefill_chunk:
                self._return_pages(pages)
                raise ValueError(
                    f"prefix length {shared_len} exceeds the largest "
                    f"prefill bucket {self.prefill_buckets[-1]} (set "
                    f"prefill_chunk to register long prefixes in segments)")
            row = np.zeros((self._p_max,), np.int32)
            row[:n_need] = pages
            # bucket None (prefix longer than every bucket, chunked
            # prefill armed): the prefix KV builds in LARGEST-BUCKET
            # segments through the same suffix-prefill program — the
            # chunked-prefill ladder applied to registration, so a
            # disaggregated prefill replica can compute KV for prompts
            # no single prefill program covers
            sp_built = False
            if (self._sp is not None and bucket is not None
                    and shared_len >= self._sp.min_tokens):
                # sequence-parallel prefix build: the whole prefix in ONE
                # wave sharded over the mesh — this is what turns a
                # prefill-biased disagg replica into an SP prefill
                # worker (its register→spill→ship path starts here). A
                # recoverable failure falls through to the single-device
                # segment ladder below, which rewrites every position —
                # bit-identical, like the admission-path fallback.
                toks_sp = np.zeros((1, bucket), np.int32)
                toks_sp[0, :shared_len] = ids[:shared_len]
                lens_sp = np.array([shared_len], np.int32)
                with self._mesh_ctx():
                    sp_built = self._run_sp_prefill(
                        toks_sp, lens_sp, row, 0, prefix=True) is not None
            if not sp_built:
                seg_cap = bucket if bucket is not None \
                    else self.prefill_buckets[-1]
                with self._mesh_ctx():
                    for off in range(0, shared_len, seg_cap):
                        seg = ids[off:min(off + seg_cap, shared_len)]
                        toks = np.zeros((1, seg_cap), np.int32)
                        toks[0, :len(seg)] = seg
                        _logits, self.cache = self._prefix_prefill(
                            self.params, toks,
                            np.array([len(seg)], np.int32),
                            self.cache, row, np.int32(off), np.int32(0),
                        )
            # the compute a restore avoids: re-registrations after a
            # discard land here, restores land in kv_restores instead
            self.prefix_prefills += 1
        pid = self._next_prefix
        self._next_prefix += 1
        self._prefix_clock += 1
        self._prefixes[pid] = {"pages": pages, "len": shared_len,
                               "tail": [int(t) for t in ids[shared_len:]],
                               # full ids: spec-mode admission seeds the
                               # slot's device history row with these
                               "ids_full": [int(t) for t in ids],
                               "refs": 0, "last_use": self._prefix_clock,
                               "pinned": bool(pinned)}
        return pid

    def has_prefix(self, pid: int) -> bool:
        """False once a prefix has been dropped or LRU-evicted — callers
        holding suffix-only ids must re-register before admitting."""
        return pid in self._prefixes

    def _reclaim_prefix_pages(self, n_need: int) -> bool:
        """Evict idle (refs == 0) prefixes until at least ``n_need`` pages
        are free: UNPINNED (auto-promoted cache entries) go first,
        least-recently-used; PINNED (explicitly registered) ones only as a
        last resort, so an operator's system prompt outlives the cache's
        opportunistic registrations but can never brick the pool. Prefix
        pages are a CACHE: under pool pressure an idle system prompt's
        pages are worth less than a live stream's next tokens (VERDICT r4
        #6 — without this, rotating system prompts exhaust the pool
        forever). Borrowed prefixes (refs > 0) are never candidates —
        which also means a borrowed prefix can never be mid-spill: only
        fully idle page sets ever reach the host tier."""
        while self._n_free_pages() < n_need:
            idle = [(info.get("pinned", False), info["last_use"], pid)
                    for pid, info in self._prefixes.items()
                    if info["refs"] == 0]
            if not idle:
                return False
            _, _, pid = min(idle)
            info = self._prefixes.pop(pid)
            # spill before freeing: the gather snapshots the pages into a
            # fresh device buffer, so reusing them right after is safe
            self._spill_prefix(info)
            self._return_pages(info["pages"])
            self.prefix_evictions += 1
        return True

    def _spill_prefix(self, info: dict) -> bool:
        """Copy an evicted idle prefix's whole pages into the host tier
        (device gather -> async D2H; the store settles the copy lazily so
        this never blocks the dispatch loop). False when the tier is off,
        the entry exceeds the host budget, or the prefix shares no whole
        pages — the pages are then discarded exactly as before."""
        if self.host_kv is None or not info["pages"] or not info["len"]:
            return False
        if self.fault is not None:
            self.fault("spill")
        key = tuple(int(t) for t in info["ids_full"])
        pages = np.asarray(info["pages"], np.int32)
        with self._mesh_ctx():
            if "paged/gather" not in self.programs:
                # first spill compiles the gather op: record it like the
                # warmup ladder (rare path — the membership test is one
                # lock + set probe per spill)
                args = (self.cache, pages)
                abstract = abstractify(args)
                t0 = time.perf_counter()
                with watch_compiles() as acc:
                    slabs = self._gather_pages(*args)
                self.programs.record(
                    "paged/gather", wall_s=time.perf_counter() - t0,
                    acc=acc, shapes={"pages": list(pages.shape)},
                    fn=self._gather_pages, abstract=abstract)
            else:
                slabs = self._gather_pages(self.cache, pages)
        try:
            for arr in slabs.values():
                arr.copy_to_host_async()
        except Exception:
            # same contract as the token prefetch: losing the async copy
            # only costs latency at settle time (np.asarray still lands
            # the bytes); count it on the shared prefetch counter
            self.prefetch_errors += 1
        ok = self.host_kv.put(key, slabs, {
            "len": info["len"], "tail": list(info["tail"]),
            "ids_full": list(info["ids_full"]),
            "pinned": bool(info.get("pinned", False)),
        })
        if ok:
            self.kv_spills += 1
        return ok

    def has_offloaded(self, prefix_ids) -> bool:
        """True when the host tier holds this exact prefix — the radix
        cache uses it to mark a generator-evicted registration restorable
        instead of gone."""
        if self.host_kv is None:
            return False
        return tuple(int(t) for t in prefix_ids) in self.host_kv

    def restore_prefix(self, prefix_ids) -> int:
        """Bring an offloaded prefix back into pool pages: allocate, one
        batched ``jax.device_put`` of the host slabs, jitted scatter into
        the pool, and re-register under a fresh prefix id. The H2D copy
        and the scatter dispatch asynchronously — they overlap the
        in-flight decode chunk — and the restored tokens are charged to
        the token-budget scheduler so the following dispatches yield the
        device time the DMA+scatter consumed (restores interleave with
        decode instead of stalling it).

        Raises ``KeyError`` when the tier doesn't hold the prefix and
        ``PagePoolExhausted`` when pool pressure wins the race (the entry
        stays in the host tier; the caller falls back to full prefill —
        the same contract as ``PrefixEvicted``). Restored pages are
        bit-identical to the spilled ones, so decode after spill→restore
        matches the never-evicted path exactly."""
        if not self.page_size:
            raise ValueError("kv offload requires page_size > 0")
        if self.host_kv is None:
            raise KeyError("host kv tier is disabled")
        if self.fault is not None:
            self.fault("restore")
        key = tuple(int(t) for t in prefix_ids)
        popped = self.host_kv.pop(key)  # popped FIRST: a reclaim below may
        if popped is None:              # spill others and LRU-evict us
            raise KeyError(f"prefix {key[:8]}... not in the host tier")
        arrays, meta = popped
        n_need = meta["len"] // self.page_size
        if self._n_free_pages() < n_need:
            self._reclaim_prefix_pages(n_need)
        if self._n_free_pages() < n_need:
            self.host_kv.put_back(key, arrays, meta)
            self.kv_restore_fallbacks += 1
            # goodput: the CALLER classifies the restore_fallback — only
            # it knows how much of the lost reuse a shallower registered
            # match still covers (prefix_cache.observe's floor)
            raise PagePoolExhausted(
                f"restore needs {n_need} pages, {self.free_pages} free")
        pages = [self._pop_free_page() for _ in range(n_need)]
        if n_need:
            # one batched async H2D
            dev_slabs = jax.device_put(arrays, self._device)
            with self._mesh_ctx():
                page_arr = np.asarray(pages, np.int32)
                if "paged/scatter" not in self.programs:
                    args = (self.cache, page_arr, dev_slabs)
                    abstract = abstractify(args)
                    t0 = time.perf_counter()
                    with watch_compiles() as acc:
                        self.cache = self._scatter_pages(*args)
                    self.programs.record(
                        "paged/scatter", wall_s=time.perf_counter() - t0,
                        acc=acc, shapes={"pages": list(page_arr.shape)},
                        fn=self._scatter_pages, abstract=abstract)
                else:
                    self.cache = self._scatter_pages(
                        self.cache, page_arr, dev_slabs)
        pid = self._next_prefix
        self._next_prefix += 1
        self._prefix_clock += 1
        self._prefixes[pid] = {"pages": pages, "len": meta["len"],
                               "tail": list(meta["tail"]),
                               "ids_full": list(meta["ids_full"]),
                               "refs": 0, "last_use": self._prefix_clock,
                               "pinned": bool(meta.get("pinned", False))}
        self.kv_restores += 1
        if self.scheduler is not None:
            self.scheduler.charge_restore(meta["len"])
        return pid

    def drop_prefix(self, pid: int, spill: bool = False) -> bool:
        """Return a prefix's pages to the pool (no live borrowers).
        ``spill=True`` (capacity evictions, e.g. the radix cache's
        registered-set cap) offloads the pages to the host tier first;
        returns whether they were actually stored. A plain drop (the
        explicit release API) always discards."""
        info = self._prefixes[pid]
        if info["refs"] > 0:
            raise RuntimeError(f"prefix {pid} still used by {info['refs']} slots")
        spilled = self._spill_prefix(info) if spill else False
        self._return_pages(info["pages"])
        del self._prefixes[pid]
        return spilled

    def _admit_prefixed(self, pid: int, ids: np.ndarray, max_new: int,
                        callback) -> int:
        """Admit one request on top of a registered prefix: borrow its
        pages, prefill only the suffix at start=shared_len."""
        if pid not in self._prefixes:
            raise PrefixEvicted(f"prefix {pid} was evicted; re-register")
        if self.fault is not None:
            self.fault("prefill")
        info = self._prefixes[pid]
        self._prefix_clock += 1
        info["last_use"] = self._prefix_clock
        suffix = info["tail"] + [int(t) for t in ids]
        n_suf = len(suffix)
        start = info["len"]
        if n_suf == 0:
            raise ValueError("prompt adds no tokens beyond the prefix")
        if start + n_suf >= self.max_seq:
            raise ValueError(
                f"prefix {start} + suffix {n_suf} exceeds max_seq")
        self.drain()  # settle bookkeeping before reusing slots
        slot = self.free_slot()
        if slot is None:
            raise RuntimeError("no free generation slot")
        self.slots[slot].live = True  # reserve
        if self._slot_pages[slot]:
            # a reused dead slot still holds its previous pages — return
            # them first or overwriting the list would leak them forever
            self._free_slot_pages(slot)
        try:
            shared = info["pages"]
            self._slot_pages[slot] = list(shared)
            self._slot_shared[slot] = len(shared)
            self._slot_prefix[slot] = pid
            info["refs"] += 1  # the except path's _free_slot_pages unrefs
            self._table[slot, :len(shared)] = shared
            self._table_dirty = True
            upto = min(start + n_suf + 2 * self.chunk,
                       start + n_suf + max_new, self.max_seq)
            if not self._alloc_pages_to(slot, upto):
                # idle prefixes are reclaimable cache (this one is pinned:
                # refs was just incremented) — without this, a pool full of
                # abandoned prefixes livelocks admission on requeue
                missing = (-(-upto // self.page_size)
                           - len(self._slot_pages[slot]))
                self._reclaim_prefix_pages(max(missing, 1))
            if not self._alloc_pages_to(slot, upto):
                need_own = -(-upto // self.page_size) - len(shared)
                if need_own > self._pages_ever_free():
                    raise ValueError(
                        f"request needs {need_own} own pages but the pool "
                        f"can only ever free {self._pages_ever_free()}")
                raise PagePoolExhausted(
                    f"kv page pool exhausted ({self.free_pages} pages free)")
            bucket = next((b for b in self.prefill_buckets if n_suf <= b),
                          None)
            if bucket is None:
                raise ValueError(
                    f"suffix length {n_suf} exceeds the largest "
                    f"prefill bucket {self.prefill_buckets[-1]}")
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :n_suf] = suffix
            lens = np.array([n_suf], np.int32)
            with self._mesh_ctx():
                logits, self.cache = self._suffix_prefill(
                    self.params, toks, lens, self.cache,
                    self._table[slot].copy(), np.int32(start),
                    np.int32(slot),
                )
                if self.spec_k:
                    # the suffix-only _after_prefill would seed a wrong
                    # history; write the full prefix+suffix row instead
                    # (suffix already carries the tail — take only the
                    # paged whole-page part of the registered ids)
                    self._seed_spec_history(
                        slot, info["ids_full"][:info["len"]] + suffix,
                        logits)
                else:
                    self._after_prefill(logits, toks, lens, np.int32(slot))
        except Exception:
            self.slots[slot].live = False
            self._free_slot_pages(slot)
            raise
        self._n_requests += 1
        self._pending_first.append(slot)
        s = _Slot()
        s.live = True
        s.max_new = max_new
        s.produced = 1  # the pending first token counts as sampled
        s.prompt_len = start + n_suf
        s.callback = callback
        if self._plain_armed:
            s.hist = [int(t)
                      for t in info["ids_full"][:info["len"]]] + suffix
        self.slots[slot] = s
        return slot

    def _host_visible(self, x):
        """Force replicated layout on arrays the host will read — in
        multi-controller mode every process must hold the full value.
        (Constant at trace time; safe inside the jitted programs.)"""
        return (x if self._repl is None
                else jax.lax.with_sharding_constraint(x, self._repl))

    def _repl_zeros(self, shape):
        """int32 zeros the host and every process can see: created INSIDE
        jit with replicated out_shardings under multi-controller (an eager
        array would be process-local), plain eager zeros otherwise."""
        if self._repl is not None:
            return _jit("init_tokens", lambda: jnp.zeros(shape, jnp.int32),
                        out_shardings=self._repl)()
        with jax.default_device(self._device):
            return jnp.zeros(shape, jnp.int32)

    def _serving_cache_specs(self) -> dict:
        """Cache partition specs for sharded multi-controller serving:
        slots over dp (distinct requests per dp group — aggregate
        throughput scales with dp), kv heads over tp (matching the
        attention weights' Megatron split). An axis is only used when the
        mesh has it and the dimension divides evenly; ``len`` stays
        replicated (tiny, host-adjacent)."""
        from ..parallel import P as _P

        cfg, mesh = self.cfg, self.mesh
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape, strict=True))
        dp = "dp" if (sizes.get("dp", 1) > 1
                      and self.batch_slots % sizes["dp"] == 0) else None
        tp = "tp" if (sizes.get("tp", 1) > 1
                      and cfg.n_kv_heads % sizes["tp"] == 0) else None
        if getattr(cfg, "kv_quant", False):
            # int8 layout: flat values [L, B, S, KV*D] (tp splits the flat
            # axis head-contiguously), scales [L, B, KV, S]
            return {"k": _P(None, dp, None, tp),
                    "v": _P(None, dp, None, tp),
                    "k_scale": _P(None, dp, tp, None),
                    "v_scale": _P(None, dp, tp, None),
                    "len": _P()}
        return {"k": _P(None, dp, None, tp, None),
                "v": _P(None, dp, None, tp, None),
                "len": _P()}

    def _reset_cache_storage(self) -> None:
        """(Re)create the KV cache arrays — and, in paged mode, the page
        pool's host bookkeeping — in whichever of the four layouts this
        generator runs (paged / multi-controller sharded / sequence-
        parallel / dense). Shared by ``__init__`` and ``recover()``: a
        crashed dispatch may have consumed the donated cache buffers, and
        rebuilding must produce exactly the construction-time layout."""
        model = self._m
        cfg = self.cfg
        if self.page_size:
            with jax.default_device(self._device):
                self.cache = model.init_paged_cache(
                    cfg, self.batch_slots, self.n_pages, self.page_size)
            if self._sp is not None:
                # stripe the POOL across the sp mesh: the page axis
                # shards so device d owns pages [d*P_loc, (d+1)*P_loc) —
                # a single request's KV spans every device's HBM, and
                # sp_paged_decode_step combines the shards exactly
                from ..parallel import NamedSharding
                from ..parallel import P as _P

                spec5 = _P(None, "sp", None, None, None)
                spec4 = _P(None, "sp", None, None)
                self.cache = {
                    key: (arr if key == "len" else jax.device_put(
                        arr, NamedSharding(
                            self.mesh, spec5 if arr.ndim == 5 else spec4)))
                    for key, arr in self.cache.items()
                }
            # page 0 is scratch; the free list is a stack of real pages.
            # Striped mode keeps ONE STACK PER DEVICE and the allocator
            # round-robins across them (_pop_free_page), so a slot's
            # consecutive virtual pages land on different shards — the
            # striping that spreads one long context over every HBM.
            if self._sp is not None:
                p_loc = self.n_pages // self._sp.shards
                self._free_dev = [
                    [pg for pg in range(self.n_pages - 1, 0, -1)
                     if pg // p_loc == d]
                    for d in range(self._sp.shards)]
                self._stripe_rr = 0
                self._free_pages: list[int] | None = None
            else:
                self._free_pages = list(range(self.n_pages - 1, 0, -1))
                self._free_dev = None
            self._slot_pages: list[list[int]] = [
                [] for _ in range(self.batch_slots)]
            self._table = np.zeros((self.batch_slots, self._p_max), np.int32)
            # device-cached copy of the host table: re-uploaded lazily,
            # only when the host copy changes (dispatch-launch fusion —
            # the per-dispatch table staging was pure launch overhead)
            self._table_dev = None
            self._table_dirty = True
            self._slot_shared = [0] * self.batch_slots
            self._slot_prefix: list[int | None] = [None] * self.batch_slots
            return
        if self._shard_cache:
            from ..parallel import NamedSharding

            specs = self._serving_cache_specs()
            self.cache = _jit(
                "init_cache",
                lambda: model.init_cache(cfg, self.batch_slots, self.max_seq),
                out_shardings={
                    key: NamedSharding(self.mesh, s)
                    for key, s in specs.items()
                },
            )()
            return
        if self.mesh is not None and (
                self._sp is not None
                or getattr(cfg, "sequence_parallel", False)):
            # long-context serving: KV cache sequence axis sharded over sp,
            # decode attention combines shards via pmax/psum (ring.py)
            from ..parallel import NamedSharding
            from ..parallel import P as _P

            cache = model.init_cache(cfg, self.batch_slots, self.max_seq)
            if getattr(cfg, "kv_quant", False):
                # int8 layout (models/llama.init_cache): flat values
                # [L, B, S, KV*D], seq-MINOR scales [L, B, KV, S]
                specs = {"k": _P(None, "dp", "sp", None),
                         "v": _P(None, "dp", "sp", None),
                         "k_scale": _P(None, "dp", None, "sp"),
                         "v_scale": _P(None, "dp", None, "sp"),
                         "len": _P("dp")}
            else:
                specs = {"k": _P(None, "dp", "sp", None, None),
                         "v": _P(None, "dp", "sp", None, None),
                         "len": _P("dp")}
            self.cache = {
                key: jax.device_put(arr,
                                    NamedSharding(self.mesh, specs[key]))
                for key, arr in cache.items()
            }
            return
        with jax.default_device(self._device):
            self.cache = model.init_cache(cfg, self.batch_slots, self.max_seq)

    def quarantine_borrowed(self) -> list[int]:
        """Invalidate the prefix registrations BORROWED by live slots and
        return their pids — the cheap, device-free slice of ``recover``
        the watchdog runs *before* failing the crashed slots' consumers.
        A woken consumer's first act is often ``has_prefix``/re-register;
        the borrowed registrations are suspect (a crashed slot was
        attending their pages) and must already read as gone, or the
        consumer races ``recover`` and can observe a stale True.
        Idempotent with ``recover``: it re-discovers nothing (the pops
        happened here) and ``_free_slot_pages`` tolerates the missing
        pids."""
        if not self.page_size:
            return []
        invalidated: list[int] = []
        for pid in [p for p, info in self._prefixes.items()
                    if info["refs"] > 0]:
            info = self._prefixes.pop(pid)
            self._return_pages(info["pages"])
            invalidated.append(pid)
        return invalidated

    def recover(self) -> list[int]:
        """Crash recovery for the serving watchdog (llm.py): discard
        everything the crashed dispatch may have corrupted and rebuild
        decode state so the WAITING queue can admit again.

        In-flight slot state (tokens, callbacks, borrowed pages, chunked-
        prefill progress, the async token pipeline) is dropped — the
        serving layer has already failed those requests with a typed
        error. Registered prefixes survive when their device pages were
        untouched: BORROWED registrations (a crashed slot was attending
        them) are invalidated, and when the crash consumed the donated
        cache buffers every registration goes with the rebuilt pool. The
        host KV tier is deliberately untouched — offloaded entries were
        never device-resident during the crash, so they stay restorable.
        Returns the invalidated prefix ids so the serving layer can clear
        its radix cache.

        Finishes with a 1-step re-warmup dispatch from the pre-jitted
        ladder and a blocking fetch: recovery either proves the decode
        path works end-to-end or raises (the watchdog then declares the
        server dead)."""
        self._inflight.clear()
        self._pending_first.clear()
        self._chunked.clear()
        self._chunked_order.clear()
        invalidated: list[int] = []
        if self.page_size:
            borrowed = [pid for pid, info in self._prefixes.items()
                        if info["refs"] > 0]
            for i in range(self.batch_slots):
                self.slots[i].live = False
                self._free_slot_pages(i)
            for pid in borrowed:
                info = self._prefixes.pop(pid, None)
                if info is not None:
                    self._return_pages(info["pages"])
                    invalidated.append(pid)
        leaves = jax.tree_util.tree_leaves(self.cache)
        if any(getattr(leaf, "is_deleted", lambda: False)()
               for leaf in leaves):
            # the crash consumed the donated cache: every device-resident
            # prefix page went with it — rebuild the pool from scratch
            if self.page_size:
                invalidated.extend(self._prefixes)
                self._prefixes.clear()
            self._reset_cache_storage()
        for i in range(self.batch_slots):
            self.slots[i] = _Slot()
        # the token row (and spec history) ride donated buffers too:
        # always rebuild rather than probing their liveness
        self._tok_dev = self._repl_zeros((self.batch_slots,))
        if self.spec_k:
            self._spec_rows_stale = False  # fresh rows, no live slots
            self._tokens_dev = self._repl_zeros(
                (self.batch_slots, self._hist_cap))
            if self.draft_params is not None:
                with jax.default_device(self._device):
                    self._draft_cache = self._m.init_cache(
                        self.draft_cfg, self.batch_slots,
                        self.max_seq + self.spec_k + 2)
        self.restarts += 1
        with self._mesh_ctx():
            self._warm_dispatch(self._mini_chunk_fn)
        np.asarray(self._tok_dev)
        return invalidated

    def _warm_dispatch(self, fn, spec: bool | None = None,
                       name: str | None = None) -> None:
        """One dead-batch dispatch of a chunk program (all slots garbage):
        compiles it on first use (warmup) and proves a rebuilt decode
        state executes (recover). ``spec`` overrides the ladder family
        (a spec generator warms its PLAIN fallback ladder too). Callers
        hold the mesh context. ``name`` records the program (with its
        compile wall and cache provenance) in the telemetry inventory —
        unnamed calls (recover's re-warm probe) skip the bookkeeping."""
        spec = bool(self.spec_k) if spec is None else spec
        win = bool(self.decode_window)
        B = self.batch_slots
        if spec and win:
            # all-frozen probe: active0 all False realizes zero steps, so
            # the dead-batch dispatch stays side-effect free
            args = (self.params, self._tok_dev, self.cache,
                    self._tokens_dev, self._draft_cache,
                    np.zeros((B,), bool), np.zeros((B,), bool),
                    np.zeros((B,), np.int32), np.zeros_like(self._table))
        elif spec and self.page_size:
            args = (self.params, self._tok_dev, self.cache,
                    self._tokens_dev, self._draft_cache,
                    np.zeros((B,), bool),
                    np.zeros_like(self._table))
        elif spec:
            args = (self.params, self._tok_dev, self.cache,
                    self._tokens_dev, self._draft_cache,
                    np.zeros((B,), bool))
        elif win:
            args = (self.params, self._tok_dev, self.cache,
                    np.int32(0), self._base_key, np.zeros((B,), bool),
                    np.zeros((B,), np.int32), np.zeros_like(self._table))
        elif self.page_size:
            args = (self.params, self._tok_dev, self.cache,
                    np.int32(0), self._base_key,
                    np.zeros_like(self._table))  # all-scratch tables
        else:
            args = (self.params, self._tok_dev, self.cache,
                    np.int32(0), self._base_key)
        record = name is not None and name not in self.programs
        if record:
            abstract = abstractify(args)
            t0 = time.perf_counter()
            with watch_compiles() as acc:
                out = fn(*args)
            self.programs.record(
                name, wall_s=time.perf_counter() - t0, acc=acc,
                shapes={"tok": list(args[1].shape)}, fn=fn,
                abstract=abstract)
        else:
            out = fn(*args)
        if spec and win:
            (_row0, _e, _c, _rw, self._tok_dev, self.cache,
             self._tokens_dev, self._draft_cache) = out
        elif spec:
            (_row0, _e, _c, self._tok_dev, self.cache,
             self._tokens_dev, self._draft_cache) = out
        elif win:
            _block, _n, _r, self._tok_dev, self.cache = out
        else:
            _toks, self._tok_dev, self.cache = out

    def warmup(self) -> None:
        """Compile the decode programs (full chunk + TTFT mini-chunk) and
        a one-row prefill program for every length of ``prefill_buckets``
        (inventory rows ``prefill/1x<L>``) before the first request, so
        that no admissible prompt compiles afterwards — a lazy first-use
        compile would land on exactly the TTFT path the mini-chunk exists
        to shorten. All slots are dead during warmup, so the sampled
        garbage never reaches bookkeeping; admission overwrites slot state.

        With the token-budget scheduler active, EVERY ladder entry compiles
        here (any size may be dispatched under load); the fixed path keeps
        its two-program warmup. jax's persistent compilation cache is
        on (``maybe_enable_compilation_cache``), so restarts load the
        (now larger) ladder from disk instead of recompiling it.
        """
        maybe_enable_compilation_cache()
        per_step = (self.spec_k + 1) if self.spec_k else 1
        full_ladder = self.scheduler is not None and (
            self.prefill_chunk
            or self.scheduler.budget
            < self.chunk * self.batch_slots * per_step)
        # the decode family's telemetry name: a spec generator's primary
        # ladder dispatches K+1-position verify windows, not plain chunks;
        # a fused-window generator's ladder entries are multi-step windows
        win = bool(self.decode_window)
        fam = ("spec/window" if self.spec_k
               else "decode/window" if win else "decode/chunk")
        plain_fam = "decode/window" if win else "decode/chunk"
        if full_ladder:
            # any ladder entry may be dispatched under load — compile them
            # all, largest first (the steady-state program is hot soonest)
            fns = [(f"{fam}{n}", self._chunk_fns[n])
                   for n in reversed(self._chunk_ladder)]
        else:
            # without chunked prefill (and with a budget covering the full
            # batch) plan() provably always picks `chunk`: the intermediate
            # ladder entries are unreachable — don't pay their compiles
            fns = [(f"{fam}{self.chunk}", self._chunk_fn)]
            if self._mini_chunk_fn is not self._chunk_fn:
                fns.append((f"{fam}1", self._mini_chunk_fn))
        with self._mesh_ctx():
            for name, fn in fns:
                self._warm_dispatch(fn, name=name)
            if self.spec_k and self._plain_armed:
                # the all-disabled fallback dispatches the PLAIN ladder:
                # compile it here too, or the first adversarial burst pays
                # the compile exactly when it's already degraded
                if full_ladder:
                    plain = [(f"{plain_fam}{n}", self._plain_fns[n])
                             for n in reversed(self._chunk_ladder)]
                else:
                    plain = [(f"{plain_fam}{self.chunk}",
                              self._plain_fns[self.chunk])]
                    if self.chunk != 1:
                        plain.append((f"{plain_fam}1", self._plain_fns[1]))
                for name, fn in plain:
                    self._warm_dispatch(fn, spec=False, name=name)
            if self.prefill_chunk:
                # segment program: startup pays the compile, not the first
                # long prompt (len reset by the bucket prefills below)
                seg = np.zeros((1, self.prefill_chunk), np.int32)
                one = np.array([1], np.int32)
                seg_name = f"prefill/segment{self.prefill_chunk}"
                if self.page_size:
                    fn = self._segment_prefill_paged
                    args = (self.params, seg, one, self.cache,
                            np.zeros((self._p_max,), np.int32), np.int32(0),
                            np.int32(0),
                            np.int32(self._p_max * self.page_size))
                else:
                    fn = self._segment_prefill
                    args = (self.params, seg, one, self.cache, np.int32(0),
                            np.int32(0), np.int32(self.cache["k"].shape[2]))
                abstract = abstractify(args)
                t0 = time.perf_counter()
                with watch_compiles() as acc:
                    _logits, self.cache = fn(*args)
                self.programs.record(
                    seg_name, wall_s=time.perf_counter() - t0, acc=acc,
                    shapes={"tokens": [1, self.prefill_chunk]}, fn=fn,
                    abstract=abstract)
            for bucket in self.prefill_buckets:
                padded = np.zeros((1, bucket), np.int32)
                ones = np.array([1], np.int32)
                # the length's whole warm block (prefill + first-token
                # sampling) is one inventory row: its wall is what a cold
                # restart pays for it; the lazy cost analysis covers the
                # prefill program
                t0 = time.perf_counter()
                with watch_compiles() as acc:
                    if self.page_size:
                        fn = self._prefill_paged
                        args = (self.params, padded, ones, self.cache,
                                np.zeros((bucket // self.page_size,),
                                         np.int32),
                                np.int32(0))
                    else:
                        fn = self._prefill_into
                        args = (self.params, padded, ones, self.cache,
                                np.int32(0))
                    abstract = abstractify(args)
                    logits, self.cache = fn(*args)
                    self._after_prefill(logits, padded, ones, np.int32(0))
                self.programs.record(
                    f"prefill/1x{bucket}",
                    wall_s=time.perf_counter() - t0, acc=acc,
                    shapes={"tokens": [1, bucket]}, fn=fn,
                    abstract=abstract)
            if self._sp is not None:
                # the SP prefill program for every bucket the dual-path
                # threshold can route to: a cold first long prompt must
                # not pay the compile the plain buckets already avoid
                for bucket in self.prefill_buckets:
                    if bucket < self._sp.min_tokens:
                        continue
                    padded = np.zeros((1, bucket), np.int32)
                    ones = np.array([1], np.int32)
                    if self.page_size:
                        fn = self._sp_prefill_paged
                        args = (self.params, padded, ones, self.cache,
                                np.zeros((bucket // self.page_size,),
                                         np.int32), np.int32(0))
                    else:
                        fn = self._sp_prefill_into
                        args = (self.params, padded, ones, self.cache,
                                np.int32(0))
                    abstract = abstractify(args)
                    t0 = time.perf_counter()
                    with watch_compiles() as acc:
                        logits, self.cache = fn(*args)
                        self._after_prefill(logits, padded, ones,
                                            np.int32(0))
                    self.programs.record(
                        f"sp_prefill/b{bucket}",
                        wall_s=time.perf_counter() - t0, acc=acc,
                        shapes={"tokens": [1, bucket],
                                "shards": self._sp.shards},
                        fn=fn, abstract=abstract)
        if "moe_counts" in self.cache:
            self._keep_counts()  # builds the counters' copy program
        # a real device->host fetch: it returns only once every queued
        # warm-up dispatch has drained, so the first live request's token
        # fetch cannot absorb the warm-up queue — the TTFT hit warmup
        # exists to prevent
        np.asarray(self._tok_dev)

    # -- sequence-parallel prefill (ml/sp_serving.py plan) -------------------
    def _sp_eligible(self, n: int) -> bool:
        """Does a prompt of ``n`` tokens take the sequence-parallel
        prefill path? The dual-path threshold: below min_tokens the
        existing single-device program runs, byte-identically."""
        return (self._sp is not None and n >= self._sp.min_tokens
                and n <= self.prefill_buckets[-1])

    def _routes_chunked(self, n: int) -> bool:
        """Does a prompt of ``n`` tokens take the SEGMENTED prefill
        path? SP-eligible prompts that fit a bucket prefill WHOLE
        instead — one sequence-parallel wave beats prefill_chunk-sized
        single-device segments."""
        if not self.prefill_chunk or n <= self.prefill_chunk:
            return False
        return not self._sp_eligible(n)

    def _run_sp_prefill(self, tokens, lens, row, slot, *,
                        prefix: bool = False):
        """One sequence-parallel prefill wave — a slot admission, or
        (``prefix=True``) a register_prefix page build. The prompt's
        forward shards over the sp mesh (ring/Ulysses) and its KV lands
        sharded — striped pages (paged mode) or the S-sharded dense
        row. Returns last-token logits, or None after a RECOVERABLE
        failure (the ``sp_prefill``/``sp_gather`` fault points, or an
        error that left the donated cache intact): the caller then runs
        the single-device prefill program over the same rows/pages,
        which overwrites them fully — the fallback is bit-identical to
        never having tried SP. An error that CONSUMED the donated cache
        mid-execution (e.g. OOM on a real chip) re-raises instead:
        there is nothing valid left to fall back onto, and the serving
        watchdog's rebuild is the existing contract for a destroyed
        prefill dispatch. Charged to the token-budget scheduler at
        tokens/shards: each shard sweeps only its slice of the prompt.
        Callers hold the mesh context."""
        sp = self._sp
        try:
            # its own phase label: an SP wave is neither a plain assemble
            # nor a decode launch, and the stall attribution must name it
            # when long prompts dominate a dispatch
            with phase(self.recorder, "sp_prefill"):
                if self.fault is not None:
                    self.fault("sp_prefill")
                if prefix:
                    logits, self.cache = self._sp_prefix_paged(
                        self.params, tokens, lens, self.cache, row,
                        np.int32(slot))
                elif self.page_size:
                    logits, self.cache = self._sp_prefill_paged(
                        self.params, tokens, lens, self.cache, row,
                        np.int32(slot))
                else:
                    logits, self.cache = self._sp_prefill_into(
                        self.params, tokens, lens, self.cache,
                        np.int32(slot))
                if self.fault is not None:
                    self.fault("sp_gather")
        except Exception as exc:
            if any(getattr(leaf, "is_deleted", lambda: False)()
                   for leaf in jax.tree_util.tree_leaves(self.cache)):
                raise  # donated cache consumed: watchdog territory
            self.sp_fallbacks += 1
            _log.warning(
                "sp prefill fell back to single-device (%s: %s)",
                type(exc).__name__, exc)
            return None
        self.sp_prefills += 1
        self.sp_tokens += int(lens[0])
        if self.scheduler is not None:
            self.scheduler.charge_sp(-(-int(lens[0]) // sp.shards))
        return logits

    def sp_stats(self) -> dict | None:
        """Sequence-parallel serving block for /debug/serving — None
        when GOFR_ML_SP is off (no SP machinery exists then)."""
        if self._sp is None:
            return None
        return {
            **self._sp.snapshot(),
            "striped_pages": bool(self.page_size),
            "prefills": self.sp_prefills,
            "fallbacks": self.sp_fallbacks,
            "tokens": self.sp_tokens,
        }

    # -- request management ---------------------------------------------------
    def free_slot(self) -> int | None:
        for i, s in enumerate(self.slots):
            if not s.live:
                return i
        return None

    def add_request(self, prompt_ids, max_new_tokens: int,
                    callback=None, prefix: int | None = None) -> int:
        """Prefill the prompt into a free slot; returns the slot index.
        ``callback(slot, tokens)`` receives each arriving BURST of sampled
        tokens (a list: the slot's share of one processed chunk).
        ``prefix`` (paged mode) continues from a ``register_prefix``
        result — only the suffix prefills."""
        if prefix is not None:
            ids = np.asarray(prompt_ids, np.int32).reshape(-1)
            return self._admit_prefixed(prefix, ids, max_new_tokens,
                                        callback)
        return self.add_requests([(prompt_ids, max_new_tokens, callback)])[0]

    def add_requests(self, requests) -> list[int]:
        """Admit a WAVE of requests — ``[(prompt_ids, max_new, callback)]``
        — all or none. Every prompt is prefilled by a one-row program of
        its own, in the smallest of ``prefill_buckets`` that holds it (the
        dense layout's ladder: 128, 256, 512, 768, 1024, powers of two up
        to ``max_seq``); the programs are launched back to back, so the
        device queue holds the whole wave while the host goes on. A
        program costs some 5 ms on the chip, a padded token some 40 us:
        padding a wave to its longest prompt costs more than it saves.

        Admission stays fully ASYNC: sampled first tokens stay on device in
        ``_tok_dev`` and their values reach the host in row 0 of the next
        decode chunk (see chunk_fn) — a synchronous fetch here serialized
        every admission on a ~150 ms round-trip (the r1 "prefill stall").
        """
        self.drain()  # settle bookkeeping before reusing slots
        prepped = []
        chunked = []
        for prompt_ids, max_new, callback in requests:
            ids = np.asarray(prompt_ids, np.int32).reshape(-1)
            n = len(ids)
            if n == 0 or n >= self.max_seq:
                raise ValueError(
                    f"prompt length {n} out of range (1..{self.max_seq - 1})")
            if self._routes_chunked(n):
                chunked.append((ids, n, max_new, callback))
            else:
                prepped.append((ids, n, max_new, callback))

        free = sum(1 for s in self.slots if not s.live)
        if len(prepped) + len(chunked) > free:
            raise RuntimeError(
                f"no free generation slot "
                f"({len(prepped) + len(chunked)} requested, {free} free)")
        if chunked and not prepped:
            return self._admit_chunked_batch(chunked)
        if chunked:
            slots_c = self._admit_chunked_batch(chunked)
            try:
                slots_p = self.add_requests(
                    [(ids, m, cb) for ids, _, m, cb in prepped])
            except Exception:
                # all-or-nothing: the caller sees the whole batch fail, so
                # the chunked slots must not stay admitted either
                self._rollback_chunked(slots_c)
                raise
            # preserve the caller's request order in the returned slots
            it_c, it_p = iter(slots_c), iter(slots_p)
            return [next(it_c)
                    if self._routes_chunked(
                        len(np.asarray(r[0]).reshape(-1)))
                    else next(it_p)
                    for r in requests]

        out: list[int] = []
        try:
            return self._admit_waves(prepped, out)
        except Exception:
            # An admission raising means the CALLER sees the whole batch
            # fail — so no slot from this call may stay admitted, or it
            # would decode to max_new_tokens for a consumer that was told
            # "error" and can never cancel it.
            dead = set(out)
            for j in dead:
                self.slots[j].live = False
                if self.page_size:
                    self._free_slot_pages(j)
            if dead:
                self._pending_first = collections.deque(
                    s for s in self._pending_first if s not in dead)
            raise

    def _rollback_chunked(self, slots_c: list) -> None:
        """Unwind chunked admissions so a failed batch leaves nothing
        live (the all-or-nothing contract add_requests documents)."""
        for j in slots_c:
            self._chunked.pop(j, None)
            if j in self._chunked_order:
                self._chunked_order.remove(j)
            self.slots[j].live = False
            if self.page_size:
                self._free_slot_pages(j)

    def _admit_chunked_batch(self, chunked) -> list:
        slots_c: list = []
        try:
            for c in chunked:
                slots_c.append(self._admit_chunked(*c))
        except Exception:
            # a later admission failing (e.g. PagePoolExhausted) must not
            # leave earlier siblings live: the caller sees the whole
            # batch fail and will retry it wholesale
            self._rollback_chunked(slots_c)
            raise
        return slots_c

    def _seed_spec_history(self, slot: int, hist: list, logits) -> None:
        """Write a slot's FULL token history into the device drafting row
        (+ the greedy first token), and re-ingest the draft model's own
        cache — shared by prefixed and chunked admission."""
        if self.draft_params is not None:
            bucket_h = next((b for b in self.prefill_buckets
                             if len(hist) <= b), None)
            if bucket_h is None:
                raise ValueError(
                    f"history length {len(hist)} exceeds the largest "
                    f"prefill bucket {self.prefill_buckets[-1]} (the "
                    f"draft model must ingest the full history)")
            toks_h = np.zeros((1, bucket_h), np.int32)
            toks_h[0, :len(hist)] = hist
            _, self._draft_cache = self._draft_prefill_into(
                self.draft_params, toks_h,
                np.array([len(hist)], np.int32),
                self._draft_cache, np.int32(slot))
        row = np.zeros((self._hist_cap,), np.int32)
        row[:len(hist)] = hist
        self._tok_dev, self._tokens_dev = self._spec_prefix_post(
            self._tok_dev, self._tokens_dev, logits, row,
            np.int32(len(hist)), np.int32(slot))

    def _admit_chunked(self, ids, n: int, max_new: int, callback) -> int:
        """Reserve a slot and queue the prompt for SEGMENTED prefill:
        step() advances one segment per decode chunk, so live streams keep
        producing while this prompt fills in. The slot joins decode (and
        gets its first token) only after the final segment. Paged mode
        applies the usual admission control here (the first segment's
        pages must allocate; an impossible request rejects outright)."""
        slot = self.free_slot()
        if slot is None:
            raise RuntimeError("no free generation slot")
        if (self.spec_k and self.draft_params is not None
                and n > self.prefill_buckets[-1]):
            # reject at ADMISSION (clean client error) — discovering it at
            # the final segment would either crash the serving loop or
            # silently run the draft on a stale cache
            raise ValueError(
                f"prompt length {n} exceeds the largest prefill bucket "
                f"{self.prefill_buckets[-1]} (the draft model must ingest "
                f"the full history)")
        if self.page_size:
            upto_total = min(n + 2 * self.chunk, n + max_new, self.max_seq)
            need = -(-upto_total // self.page_size)
            if need > self._pages_ever_free():
                raise ValueError(
                    f"request needs {need} pages but the pool can only "
                    f"ever free {self._pages_ever_free()}")
            self.slots[slot].live = True  # reserve for the alloc below
            if self._slot_pages[slot]:
                self._free_slot_pages(slot)
            first_upto = min(self.prefill_chunk, n)
            if not self._alloc_pages_to(slot, first_upto):
                self._reclaim_prefix_pages(
                    -(-first_upto // self.page_size))
            if not self._alloc_pages_to(slot, first_upto):
                self.slots[slot].live = False
                self._free_slot_pages(slot)
                raise PagePoolExhausted(
                    f"kv page pool exhausted ({self.free_pages} pages "
                    f"free)")
        s = _Slot()
        s.live = True
        s.max_new = max_new
        s.prompt_len = n
        s.callback = callback
        self.slots[slot] = s
        self._chunked[slot] = {"ids": ids, "done": 0, "max_new": max_new}
        self._chunked_order.append(slot)
        return slot

    def _decodable(self) -> bool:
        """Any slot actually producing tokens (live and not mid-prefill)?"""
        return bool(self._pending_first) or any(
            s.live and i not in self._chunked
            for i, s in enumerate(self.slots))

    def _n_decodable(self) -> int:
        """Slots producing tokens this dispatch — the scheduler's live-work
        count (a slot mid-chunked-prefill decodes garbage, not tokens)."""
        return sum(1 for i, s in enumerate(self.slots)
                   if s.live and i not in self._chunked)

    def _advance_chunked(self, max_segments: int = 1) -> None:
        """Run up to ``max_segments`` prefill segments across the chunked
        slots (round-robin) before the next decode dispatch. The fixed path
        interleaves exactly one; the token-budget scheduler passes the
        budget's remainder — several segments when decode is light, the
        single stall-free minimum when decode is saturated. While nothing
        is decodable the segments run back-to-back regardless — no reason
        to interleave garbage decode chunks into an idle batch."""
        done = 0
        while self._chunked_order:
            slot = self._chunked_order[0]
            st = self._chunked.get(slot)
            if st is None:
                # released elsewhere: drop ONLY the order entry — the slot
                # may already host an unrelated new request
                self._chunked_order.popleft()
                continue
            if not self.slots[slot].live:
                # cancelled mid-prefill: drop the bookkeeping
                self._chunked.pop(slot, None)
                self._chunked_order.popleft()
                continue
            if self.fault is not None:
                self.fault("prefill")
            C = self.prefill_chunk
            start = st["done"]
            seg = st["ids"][start:start + C]
            toks = np.zeros((1, C), np.int32)
            toks[0, :len(seg)] = seg
            lens = np.array([len(seg)], np.int32)
            final = start + len(seg) == len(st["ids"])
            if self.page_size:
                # cover this segment's positions (pages beyond stay
                # scratch); mid-prefill pool-dry reclaims idle prefixes,
                # then truncates honestly like a mid-decode eviction
                if not self._alloc_pages_to(slot, start + len(seg)):
                    self._reclaim_prefix_pages(1)
                if not self._alloc_pages_to(slot, start + len(seg)):
                    self.drain()
                    self._chunked.pop(slot)
                    self._chunked_order.popleft()
                    self.slots[slot].live = False
                    self.slots[slot].evicted = True
                    self.evictions += 1
                    continue
                s_cap = self._p_max * self.page_size
                new_len = np.int32(len(st["ids"]) if final else s_cap)
                with self._mesh_ctx():
                    logits, self.cache = self._segment_prefill_paged(
                        self.params, toks, lens, self.cache,
                        self._table[slot].copy(), np.int32(start),
                        np.int32(slot), new_len)
            else:
                s_cap = self.cache["k"].shape[2]
                # capacity len parks the row: interleaved decode chunks
                # drop their garbage writes out of bounds instead of
                # corrupting prefilled positions (prefill_segment_into)
                new_len = np.int32(len(st["ids"]) if final else s_cap)
                with self._mesh_ctx():
                    logits, self.cache = self._segment_prefill(
                        self.params, toks, lens, self.cache, np.int32(slot),
                        np.int32(start), new_len)
            st["done"] += len(seg)
            self.prefill_segments_run += 1
            if final:
                # flush decode chunks dispatched while this slot was
                # mid-prefill FIRST: their garbage rows for the slot must
                # be dropped while the _chunked guard still holds
                self.drain()
                self._chunked.pop(slot)
                self._chunked_order.popleft()
                self._n_requests += 1
                self._pending_first.append(slot)
                self.slots[slot].produced = 1  # the pending first token
                if self._plain_armed:
                    self.slots[slot].hist = [int(t) for t in st["ids"]]
                if self.spec_k:
                    # seed the device history row with the FULL prompt
                    # (the segment-shaped _after_prefill would write a
                    # C-token suffix only); the draft cache re-ingests too
                    # (feasibility was validated at admission)
                    self._seed_spec_history(
                        slot, [int(t) for t in st["ids"]], logits)
                else:
                    self._after_prefill(logits, toks, lens, np.int32(slot))
            else:
                self._chunked_order.append(self._chunked_order.popleft())
            done += 1
            if self._decodable() and (done >= max_segments
                                      or self._pending_first):
                # budget spent — or a final segment just queued a first
                # token: surface it via the mini-chunk NOW instead of
                # burning the remaining segment allowance on its TTFT
                return

    def _admit_waves(self, prepped, out: list[int]) -> list[int]:
        """Admit a wave prompt by prompt: reserve a slot, launch the
        prompt's own one-row prefill and its first-token sampler in the
        smallest of ``prefill_buckets`` that holds it, and go on to the
        next with no host sync: the device queue holds the wave."""
        if self.fault is not None and prepped:
            self.fault("prefill")
        for ids, n, max_new, callback in prepped:
            slot = self.free_slot()
            if slot is None:  # unreachable after the capacity pre-check
                raise RuntimeError("no free generation slot")
            self.slots[slot].live = True  # reserved
            sp_used = False  # this prompt prefilled sequence-parallel
            s_bucket = next((s for s in self.prefill_buckets if n <= s),
                            self.max_seq)
            tokens = np.zeros((1, s_bucket), np.int32)
            tokens[0, :n] = ids
            lens = np.array([n], np.int32)
            try:
                with self._mesh_ctx():
                    logits = None
                    if self.page_size:
                        if self._slot_shared[slot]:
                            # previous occupant borrowed prefix pages:
                            # reusing its list would write INTO the shared
                            # prefix — reset to a fresh own-page list
                            self._free_slot_pages(slot)
                        # admission control: no pages, no slot — the
                        # caller requeues on PagePoolExhausted instead of
                        # risking a silent mid-generation eviction. The
                        # estimate never exceeds the request's own budget.
                        upto = min(n + 2 * self.chunk, n + max_new,
                                   self.max_seq)
                        if not self._alloc_pages_to(slot, upto):
                            # reclaim idle prefixes before declaring
                            # back-pressure (see _admit_prefixed)
                            missing = (-(-upto // self.page_size)
                                       - len(self._slot_pages[slot]))
                            self._reclaim_prefix_pages(max(missing, 1))
                        if not self._alloc_pages_to(slot, upto):
                            need = -(-upto // self.page_size)
                            if need > self._pages_ever_free():
                                raise ValueError(
                                    f"request needs {need} pages but the "
                                    f"pool can only ever free "
                                    f"{self._pages_ever_free()}")
                            raise PagePoolExhausted(
                                "kv page pool exhausted "
                                f"({self.free_pages} pages free)")
                        row = np.zeros((s_bucket // self.page_size,),
                                       np.int32)
                        pages = self._slot_pages[slot]
                        row[:min(len(pages), len(row))] = \
                            pages[:len(row)]
                        if self._sp_eligible(n):
                            logits = self._run_sp_prefill(
                                tokens, lens, row, slot)
                            sp_used = logits is not None
                        if logits is None:
                            logits, self.cache = self._prefill_paged(
                                self.params, tokens, lens, self.cache,
                                row, np.int32(slot),
                            )
                    else:
                        if self._sp_eligible(n):
                            logits = self._run_sp_prefill(
                                tokens, lens, None, slot)
                            sp_used = logits is not None
                        if logits is None:
                            with phase(self.recorder, "launch",
                                       kind="prefill", rows=1, seq=s_bucket,
                                       real_tokens=n):
                                logits, self.cache = self._prefill_into(
                                    self.params, tokens, lens, self.cache,
                                    np.int32(slot),
                                )
                            self.prefill_tokens_real += n
                            self.prefill_tokens_padded += s_bucket
                    self._after_prefill(logits, tokens, lens, np.int32(slot))
            except Exception:
                self.slots[slot].live = False  # unwind the reservation
                if self.page_size:
                    self._free_slot_pages(slot)
                raise
            self._n_requests += 1
            self._pending_first.append(slot)
            s = _Slot()
            s.live = True
            s.tokens = []
            s.max_new = max_new
            s.produced = 1  # the pending first token counts as sampled
            s.prompt_len = n
            s.eos_hit = False
            s.callback = callback
            if sp_used:
                # journey marks and the sp debug block read the shard
                # count off the slot — admission is the one moment
                # the SP-vs-plain decision is known
                s.sp_shards = self._sp.shards
            if self._plain_armed:
                s.hist = [int(t) for t in ids]
            self.slots[slot] = s
            out.append(slot)
        return out

    def _resolve_first(self, tok_in_row: np.ndarray) -> None:
        """Fold newly-admitted slots' first tokens (row 0 of an arriving
        chunk = the token row that chunk decoded FROM) into slot state,
        before the chunk's own samples are processed. add_request drains
        the pipeline before admitting, so every pending slot's first is in
        the next chunk's input row."""
        while self._pending_first:
            slot = self._pending_first.popleft()
            s = self.slots[slot]
            t = int(tok_in_row[slot])
            if not s.live:
                continue
            s.tokens.append(t)
            if self._plain_armed:
                s.hist.append(t)
            if t in self._eos:
                s.eos_hit = True
            if s.callback is not None:
                s.callback(slot, [t])
            self._maybe_finish(slot)

    def _maybe_finish(self, i: int) -> None:
        s = self.slots[i]
        if s.live and (
            s.produced >= s.max_new
            or s.eos_hit
            or s.prompt_len + s.produced >= self.max_seq
        ):
            s.live = False

    @property
    def n_live(self) -> int:
        return sum(s.live for s in self.slots)

    # -- decode ---------------------------------------------------------------
    def _plan_window(self, use_spec: bool,
                     n_steps: int) -> tuple[np.ndarray, np.ndarray]:
        """Per-slot masks for the next fused window dispatch: ``active0``
        (decodable rows) and ``step_cap`` (tokens each row may still emit
        on device). The cap folds three bounds — remaining ``max_new``,
        remaining sequence capacity, and the deadline step bound (time to
        the slot's deadline over the observed per-step wall) — MINUS the
        token capacity of windows already in flight: host ``produced``
        lags the one-deep pipeline, and without the subtraction a row
        could be granted the same budget twice. Conservative under-
        production is safe (the next window continues); _apply_burst is
        the final host-side truncation either way."""
        active0 = np.array(
            [s.live and i not in self._chunked
             for i, s in enumerate(self.slots)], bool)
        pending = 0
        for k, _item, m, _stamp in self._inflight:
            if k == "window":
                pending += m[0]
            elif k == "specwin":
                pending += m[0] * (self.spec_k + 1)
        step_cap = np.zeros((self.batch_slots,), np.int32)
        now = time.perf_counter()  # slot.deadline_at's clock (llm.py)
        for i, s in enumerate(self.slots):
            if not active0[i]:
                continue
            cap = min(s.max_new - s.produced,
                      self.max_seq - s.prompt_len - s.produced) - pending
            if s.deadline_at is not None and self._step_ema:
                cap = min(cap, int(max(s.deadline_at - now, 0.0)
                                   / self._step_ema))
            step_cap[i] = max(cap, 0)
        # dispatch cadence EMA, in seconds per planned device step: the
        # deadline bound's clock (advisory — the serving reaper stays
        # authoritative)
        t = time.perf_counter()
        if self._last_dispatch is not None:
            t_prev, n_prev = self._last_dispatch
            per = (t - t_prev) / max(n_prev, 1)
            self._step_ema = (per if self._step_ema is None
                              else 0.8 * self._step_ema + 0.2 * per)
        unit = (self.spec_k + 1) if use_spec else 1
        self._last_dispatch = (t, n_steps * unit)
        return active0, step_cap

    def step(self) -> None:
        """Dispatch one chunk of decode steps; process the previous
        chunk's tokens (host bookkeeping lags one dispatch — the device
        never waits for the host's round trip).

        With the token-budget scheduler active, each dispatch spends ONE
        budget: segmented prefill consumes its planned share first (several
        segments when decode is light), then decode dispatches the ladder
        entry that fills the rest given the live decodable slots. Without
        it, exactly the fixed ``chunk`` program plus one interleaved
        prefill segment — the original behavior. Greedy outputs are
        bit-identical either way; sampling keys fold the ABSOLUTE step
        counter, so sampled outputs also match whenever requests land on
        the same steps (a shifted interleave under concurrent sampled
        traffic redraws from the same distribution)."""
        if self.n_live == 0:
            self.drain()
            return
        if self.fault is not None:
            self.fault("step")
        rec = self.recorder
        sched = self.scheduler
        # Adaptive speculation: which decodable slots still speculate this
        # dispatch. With every one of them auto-disabled (and the plain
        # fallback armed — lookup mode), the WHOLE dispatch degrades to
        # the plain ladder: no K+1 verify positions for always-rejected
        # drafts. The mask is snapshotted here and travels with the
        # in-flight item so acceptance accounting matches what the device
        # actually ran, one pipeline step later.
        spec_mask = None
        use_spec = False
        if self.spec_k:
            spec_mask = np.array(
                [s.live and i not in self._chunked and not s.spec_disabled
                 for i, s in enumerate(self.slots)], bool)
            use_spec = bool(spec_mask.any()) or not self._plain_armed
        unit = (self.spec_k + 1) if use_spec else 1
        n_steps = self.chunk
        if sched is not None:
            with phase(rec, "decide"):
                n_steps, n_segments = sched.plan(self._n_decodable(),
                                                 bool(self._chunked), unit)
        if self._chunked:
            # segmented prefill rides the same device queue as the decode
            # chunk — its program-launch cost is launch time of this pass
            with phase(rec, "launch"):
                self._advance_chunked(n_segments if sched is not None else 1)
            if not self._decodable():
                return  # everything live is still mid-prefill
        # Pending first tokens -> ONE 1-step mini-chunk so they surface a
        # full chunk earlier (TTFT); otherwise the throughput-sized chunk.
        # All firsts pending at dispatch ride that chunk's input row, and
        # the mini path drains synchronously below, so pending_first is
        # empty again before the next step() call.
        primary = not self.spec_k or use_spec
        fns = self._chunk_fns if primary else self._plain_fns
        mini = bool(self._pending_first)
        if mini:
            n_steps = 1
            fn = self._mini_chunk_fn if primary else fns[1]
            if sched is not None:
                # admission-driven, not a ladder pick: kept out of the
                # dispatch-size mix so it can't read as 1-step collapse
                sched.mini_dispatches += 1
        elif sched is not None:
            fn = fns[n_steps]
            sched.note_dispatch(n_steps)
        else:
            fn = self._chunk_fn if primary else fns[self.chunk]
        if self.spec_k and use_spec and self._spec_rows_stale:
            # coming back from plain-ladder dispatches: settle host
            # bookkeeping, then rewrite the device drafting rows from the
            # host mirror so the re-probe drafts from real history
            self.drain()
            self._reseed_spec_rows()
        win = self.decode_window
        active0 = step_cap = None
        if win:
            active0, step_cap = self._plan_window(use_spec, n_steps)
            if not mini and not bool((active0 & (step_cap > 0)).any()):
                # no row can emit anything this window (budgets spent
                # host-side, or everything decodable died since the last
                # dispatch): settle the pipeline instead of burning a
                # launch on an all-frozen program. The mini path never
                # takes this exit — pending firsts ride the next input
                # row, so it must always dispatch.
                self.drain()
                return
        spec = bool(self.spec_k and use_spec)
        kind = (("specwin" if spec else "window") if win
                else "spec" if spec else "chunk")
        with self._mesh_ctx():
            if self.page_size:
                # page growth + the (cached) table upload are host-side
                # batch ASSEMBLY, not program launch — split out so the
                # launch number names only the dispatch machinery
                with phase(rec, "assemble"):
                    self._grow_pages()
                    table = self._table_device()
            # the record and the launch annotation say what runs: the
            # program's kind, its decode steps, the rows producing tokens
            rows = self._n_decodable()
            if "state" in self.cache:
                self.state_rows_swept += n_steps * self.batch_slots
                self.state_rows_live += n_steps * rows
            with phase(rec, "launch", kind="mini" if mini else kind,
                       steps=n_steps, rows=rows):
                if kind == "specwin":
                    (row0, emits, counts, realized, self._tok_dev,
                     self.cache, self._tokens_dev, self._draft_cache) = fn(
                        self.params, self._tok_dev, self.cache,
                        self._tokens_dev, self._draft_cache, spec_mask,
                        active0, step_cap, table)
                    item: Any = (row0, emits, counts, realized)
                    meta: Any = (n_steps, active0, spec_mask)
                elif kind == "window":
                    (block, n_out, realized, self._tok_dev,
                     self.cache) = fn(
                        self.params, self._tok_dev, self.cache,
                        np.int32(self.steps), self._base_key, active0,
                        step_cap, table)
                    item = (block, n_out, realized)
                    meta = (n_steps, active0)
                elif kind == "spec":
                    (row0, emits, counts, self._tok_dev, self.cache,
                     self._tokens_dev, self._draft_cache) = fn(
                        self.params, self._tok_dev, self.cache,
                        self._tokens_dev, self._draft_cache, spec_mask,
                        *((table,) if self.page_size else ()))
                    item = (row0, emits, counts)
                    meta = spec_mask
                else:
                    item, self._tok_dev, self.cache = fn(
                        self.params, self._tok_dev, self.cache,
                        np.int32(self.steps), self._base_key,
                        *((table,) if self.page_size else ()))
                    meta = None
                    if "moe_counts" in self.cache:
                        self._keep_counts()
                self.steps += n_steps
                if self.spec_k and not use_spec:
                    # a plain dispatch leaves the device drafting rows
                    # behind the host mirror; repair before the next spec
                    # dispatch
                    self._spec_rows_stale = True
        # issuing the async D2H of the token block — the other half of
        # what used to be one "dispatch" phase (the blocking read-back is
        # device_wait, in _pop_process)
        with phase(rec, "d2h_issue") as issue:
            try:
                # best-effort prefetch; where this is itself a blocking
                # transfer the cost is the same as the np.asarray in
                # _pop_process — the pipeline depth below is what keeps
                # the device busy while the host reads.
                for arr in (item if isinstance(item, tuple) else (item,)):
                    arr.copy_to_host_async()
            except Exception as exc:
                # losing the prefetch only costs latency, but a transport
                # whose prefetch path broke should be visible: count
                # every failure, log the first once per generator
                self.prefetch_errors += 1
                if not self._prefetch_warned:
                    self._prefetch_warned = True
                    _log.debug(
                        "token prefetch (copy_to_host_async) failed; "
                        "falling back to blocking reads [%s: %s]",
                        type(exc).__name__, exc)
        stamp = None
        if rec is not None:
            # launch stamp: when this dispatch was issued, how many were
            # already outstanding (the record's ``overlap`` dim) and its
            # planned device positions — settled back into the recorder's
            # host-idle estimate in _pop_process
            stamp = (issue.t0, len(self._inflight), n_steps * unit)
            rec.note_overlap(len(self._inflight))
        self._inflight.append((kind, item, meta, stamp))
        if mini:
            # TTFT: the chunk carrying new requests' first tokens is read
            # back NOW instead of lagging one dispatch — one blocking
            # round-trip traded for a whole chunk cycle of first-token
            # latency; steady-state decode keeps the async pipeline.
            self.drain()
        else:
            # double-buffered dispatch (GOFR_ML_PIPELINE=1): hold TWO
            # dispatches outstanding across serve passes — window N
            # settles only once N+2 has launched, so the blocking
            # read-back finds N's tokens long landed while N+1 computes
            # through this pass's emit/admission host work. Off, the
            # classic lag-one pipeline: exactly one stays outstanding.
            depth = 2 if self.pipeline else 1
            while len(self._inflight) > depth:
                self._pop_process()
            if self.pipeline and len(self._inflight) >= 2:
                self.pipeline_windows += 1

    def drain(self) -> None:
        """Flush pending token chunks into host bookkeeping."""
        while self._inflight:
            self._pop_process()

    def _pop_process(self) -> None:
        """Settle the oldest dispatch in flight: the blocking read-back is
        ``device_wait``, and its launch stamp (when the recorder was armed
        at launch) feeds the launch→settle span into the recorder's
        host-idle estimate."""
        kind, item, meta, stamp = self._inflight.popleft()
        self.settled += 1
        rec = self.recorder
        with phase(rec, "device_wait") as wait:
            host = (np.asarray(item) if kind == "chunk"
                    else tuple(np.asarray(x) for x in item))
        if rec is not None and stamp is not None:
            t_launch, depth0, steps = stamp
            rec.note_settle(wait.t1 - t_launch, depth0, steps,
                            wait.t1 - wait.t0)
        if kind == "chunk":
            self._process(host)
        elif kind == "spec":
            self._process_spec(*host, meta)
        elif kind == "window":
            block, n_out, realized = host
            self._process_window(block, n_out, int(realized), meta)
        else:  # "specwin"
            row0, emits, counts, realized = host
            planned, active0, mask = meta
            self._process_spec(row0, emits, counts, mask, planned=planned,
                               active0=active0, realized_w=int(realized))

    def _apply_burst(self, i: int, s: _Slot, col: np.ndarray,
                     bursts: dict) -> int:
        """Fold one slot's token COLUMN (decode-step order) into slot
        state as a single batch: cap at the slot's remaining budget,
        truncate at the first eos, extend the lists once. Replaces the
        per-token Python loop (the dominant per-slot host assemble cost
        at chunk 16 x 64 slots). Returns tokens applied."""
        cap = min(len(col), s.max_new - s.produced,
                  self.max_seq - s.prompt_len - s.produced)
        if cap <= 0:
            self._maybe_finish(i)
            return 0
        col = col[:cap]
        if self._eos_arr is not None:
            hits = np.nonzero(np.isin(col, self._eos_arr))[0]
            if hits.size:
                col = col[:int(hits[0]) + 1]
                s.eos_hit = True
        burst = col.tolist()
        s.tokens.extend(burst)
        s.produced += len(burst)
        if self._plain_armed:
            s.hist.extend(burst)
        if s.callback is not None:
            bursts.setdefault(i, []).extend(burst)
        self._maybe_finish(i)
        return len(burst)

    def _process_window(self, block: np.ndarray, n_out: np.ndarray,
                        realized: int, meta) -> None:
        """Apply one fused decode window — token block [K+1, B] with row 0
        the input-token ride-along, per-row emit counts [B], and the
        realized step count — to slot state. Each active row applies only
        its own ``n_out`` tokens; device steps a row computed past its
        EOS or budget (the pipeline lag, a host-side death since
        dispatch) are charged to the goodput ledger as
        ``window_overshoot`` — computed, never delivered."""
        planned, active0 = meta
        self.windows += 1
        self.window_steps_planned += planned
        self.window_steps_realized += realized
        rec = self.recorder
        if rec is not None:
            # stamped from the PROCESSING pass: the committed dispatch
            # record describes the window whose tokens this pass drained
            rec.note_window(planned, realized)
        self._resolve_first(block[0])
        body = block[1:]
        bursts: dict[int, list[int]] = {}
        overshoot = 0
        lagged = 0  # tokens for rows already dead when this window settled
        for i, s in enumerate(self.slots):
            if not active0[i] or i in self._chunked:
                continue  # frozen at dispatch, or mid-prefill garbage
            n = int(n_out[i])
            was_live = s.live
            applied = (self._apply_burst(i, s, body[:n, i], bursts)
                       if was_live else 0)
            if was_live or not self.pipeline:
                overshoot += max(n - applied, 0)
            else:
                # the slot finished, released, or was reaped while this
                # window sat in flight behind another (GOFR_ML_PIPELINE):
                # its tokens are the double-buffer's speculative
                # re-dispatch bill, itemized apart from the window's own
                # early-exit raggedness
                lagged += max(n - applied, 0)
        if overshoot:
            self.window_overshoot += overshoot
            if self.goodput is not None:
                self.goodput.note("window_overshoot", overshoot)
        if lagged:
            self.pipeline_overshoot += lagged
            if self.goodput is not None:
                self.goodput.note("pipeline_overshoot", lagged)
        self._fire_bursts(bursts)

    def _process_spec(self, row0: np.ndarray, emits: np.ndarray,
                      counts: np.ndarray, mask, planned: int | None = None,
                      active0=None, realized_w: int | None = None) -> None:
        """Apply one speculative chunk — input row [B] (resolves pending
        firsts), emitted candidates [W, B, K+1], counts [W, B], and the
        per-slot enable mask the dispatch ran with — to slot state. Each
        window contributes 1..K+1 tokens per live slot; windows of
        mask-disabled slots emit exactly 1 (their plain-decode token).

        The fused-window dispatch path (``realized_w`` not None) adds the
        early-exit accounting: frozen rows emit 0 for a window (their
        verify positions are ``window_overshoot``), only ``realized_w``
        of the planned windows actually ran, and rows that died host-side
        since dispatch charge their computed tokens the same way."""
        self._resolve_first(row0)
        windowed = realized_w is not None
        if windowed:
            self.windows += 1
            self.window_steps_planned += planned
            self.window_steps_realized += realized_w
            if self.recorder is not None:
                self.recorder.note_window(planned, realized_w)
        bursts: dict[int, list[int]] = {}
        n_windows = emits.shape[0]
        rejected = 0   # draft positions the verify windows discarded
        overshoot = 0  # positions computed past a row's EOS/budget
        lagged = 0     # positions for rows already dead at settle
        for i, s in enumerate(self.slots):
            if windowed:
                if not active0[i] or i in self._chunked:
                    continue
            elif not s.live or i in self._chunked:
                continue  # mid-prefill rows decode garbage; drop it
            enabled = mask is None or bool(mask[i])
            was_live = s.live
            seen = 0
            over_row = 0
            for w in range(n_windows):
                if windowed:
                    if w >= realized_w:
                        break  # the whole batch froze before this window
                elif not s.live:
                    break
                n = int(counts[w, i])
                if windowed and n == 0:
                    # this row was frozen for this window while the batch
                    # kept running: its share of the verify sweep bought
                    # nothing (disabled rows only burn their one plain
                    # position — matching the spec_rejected convention of
                    # billing only enabled rows for the K+1 sweep)
                    over_row += (self.spec_k + 1) if enabled else 1
                    continue
                seen += 1
                self.spec_windows += 1
                s.spec_windows += 1
                s.spec_emitted += n
                if enabled:
                    s.spec_recent_w += 1
                    s.spec_recent_e += n
                    # the device computed K+1 positions for this window;
                    # n survived verification — the rest is the drafting
                    # bill the goodput ledger itemizes
                    rejected += self.spec_k + 1 - n
                applied = (self._apply_burst(i, s, emits[w, i, :n], bursts)
                           if s.live else 0)
                self.spec_emitted += applied
                if windowed:
                    over_row += n - applied
            if was_live or not self.pipeline:
                overshoot += over_row
            else:
                # dead before this dispatch ever settled: the whole row's
                # verify-sweep bill is the double-buffer's speculative
                # re-dispatch charge (GOFR_ML_PIPELINE), not the window's
                # own early-exit economics
                lagged += over_row
            if not windowed or was_live:
                self._eval_spec_slot(s, enabled, seen)
        if rejected and self.goodput is not None:
            self.goodput.note("spec_rejected", rejected)
        if overshoot:
            self.window_overshoot += overshoot
            if self.goodput is not None:
                self.goodput.note("window_overshoot", overshoot)
        if lagged:
            self.pipeline_overshoot += lagged
            if self.goodput is not None:
                self.goodput.note("pipeline_overshoot", lagged)
        self._fire_bursts(bursts)

    def _eval_spec_slot(self, s: _Slot, enabled: bool,
                        windows: int) -> None:
        """Adaptive per-slot speculation control, run once per processed
        dispatch: an ENABLED slot whose rolling accept rate over >=
        ``_spec_probe_min`` windows falls below ``spec_min_accept`` is
        disabled (it degrades to plain decode via the dispatch mask); a
        DISABLED slot counts its cooldown down and re-probes — fresh
        judging window — when it expires. Lossless either way: the mask
        only moves tokens between the accept path and the verify-argmax
        path, never changes them."""
        if not windows:
            return
        if not enabled:
            if not s.spec_disabled:
                return  # flag flipped since that dispatch was planned
            s.spec_cooldown_left -= windows
            if s.spec_cooldown_left <= 0:
                s.spec_disabled = False
                s.spec_recent_w = s.spec_recent_e = 0
                self.spec_reprobes += 1
            return
        if s.spec_disabled:
            # the symmetric mirror race: an item dispatched enabled just
            # before the disable verdict landed must not re-disable the
            # slot (double-counting the alarm counter, restarting the
            # cooldown clock)
            return
        if self.spec_min_accept <= 0 or not self.spec_k:
            return
        if s.spec_recent_w < self._spec_probe_min:
            return
        rate = max(0.0, (s.spec_recent_e - s.spec_recent_w)
                   / (s.spec_recent_w * self.spec_k))
        if rate < self.spec_min_accept:
            s.spec_disabled = True
            s.spec_cooldown_left = self.spec_cooldown
            self.spec_disables += 1
        s.spec_recent_w = s.spec_recent_e = 0

    def _reseed_spec_rows(self) -> None:
        """Rewrite the device drafting history from the host mirror —
        the plain→spec transition repair (plain dispatches advance the
        cache but not ``_tokens_dev``) — assembled host-side and
        uploaded as ONE [B, hist_cap] transfer (the _table_device
        pattern), so a re-probe transition costs one launch, not one per
        live slot. Rows of dead or mid-chunked-prefill slots zero out:
        dead rows are garbage either way, and a chunked slot's row is
        (re)seeded whole at its final segment. Callers drain first so
        the mirror is complete."""
        rows = np.zeros((self.batch_slots, self._hist_cap), np.int32)
        for i, s in enumerate(self.slots):
            if not s.live or i in self._chunked:
                continue
            hist = s.hist[-self._hist_cap:]
            rows[i, :len(hist)] = hist
        with self._mesh_ctx():
            self._tokens_dev = self._reseed_hist(rows)
        self._spec_rows_stale = False

    def spec_stats(self) -> dict | None:
        """Speculation block for /debug/serving (None when spec is off):
        config, lifetime window/acceptance totals, and the adaptive
        disable/re-probe state."""
        if not self.spec_k:
            return None
        accept = (max(0.0, (self.spec_emitted - self.spec_windows)
                      / (self.spec_windows * self.spec_k))
                  if self.spec_windows else None)
        return {
            "spec_k": self.spec_k,
            "mode": "draft" if self.draft_params is not None else "lookup",
            "min_accept": self.spec_min_accept,
            "cooldown_windows": self.spec_cooldown,
            "windows": self.spec_windows,
            "emitted": self.spec_emitted,
            "accept_rate": (round(accept, 4) if accept is not None
                            else None),
            "disabled_slots": sum(1 for s in self.slots
                                  if s.live and s.spec_disabled),
            "disables_total": self.spec_disables,
            "reprobes_total": self.spec_reprobes,
            "plain_fallback_armed": self._plain_armed,
        }

    def window_stats(self) -> dict | None:
        """Fused-window block for /debug/serving (None when window mode
        is off): configured K, lifetime window/step totals, how much of
        the planned work the early-exit masks actually ran, and the
        overshoot charge."""
        if not self.decode_window:
            return None
        planned = self.window_steps_planned
        return {
            "window": self.decode_window,
            "windows": self.windows,
            "steps_planned": planned,
            "steps_realized": self.window_steps_realized,
            "realized_share": (round(self.window_steps_realized / planned, 4)
                               if planned else None),
            "overshoot_tokens": self.window_overshoot,
            "step_ema_s": (round(self._step_ema, 6)
                           if self._step_ema is not None else None),
        }

    def pipeline_stats(self) -> dict | None:
        """Double-buffer block for /debug/serving (None when
        GOFR_ML_PIPELINE is off): the depth, how many passes actually
        ended with two dispatches outstanding, the speculative
        re-dispatch bill, and the flight recorder's estimate of the
        device's idle share from the host's clock (None when the recorder
        is off)."""
        if not self.pipeline:
            return None
        idle = None
        rec = self.recorder
        if rec is not None:
            idle = rec.snapshot().get("host_idle_estimate")
        return {
            "depth": 2,
            "windows_overlapped": self.pipeline_windows,
            "overshoot_tokens": self.pipeline_overshoot,
            "host_idle_estimate": idle,
        }

    def _process(self, toks: np.ndarray) -> None:
        """Apply one [1 input + chunk sampled, B] token block to slot
        state. The input row resolves pending firsts; each slot's column
        is folded in as ONE batch (_apply_burst) instead of a per-token
        Python loop — token order within the chunk is preserved because a
        slot only ever reads its own column in step order.

        Callbacks fire once per slot per chunk with the slot's BURST of
        tokens, not once per token: at 64 slots x chunk 16 a per-token
        callback is 1,024 host calls per ~27 ms dispatch — and in the
        serving stack each was a ``call_soon_threadsafe`` wakeup of the
        asyncio loop. One list per slot cuts that 16x."""
        self._resolve_first(toks[0])
        body = toks[1:]
        bursts: dict[int, list[int]] = {}
        for i, s in enumerate(self.slots):
            if not s.live or i in self._chunked:
                continue  # mid-prefill rows decode garbage; drop it
            self._apply_burst(i, s, body[:, i], bursts)
            if self.spec_k and s.spec_disabled:
                # plain-fallback dispatches must still run the cooldown
                # clock (one decode step ~ one window of cadence), or an
                # all-disabled batch could never re-probe
                self._eval_spec_slot(s, False, len(body))
        self._fire_bursts(bursts)

    def _fire_bursts(self, bursts: dict[int, list[int]]) -> None:
        """Deliver each slot's token burst to its callback — the emit
        phase of the dispatch breakdown (in the serving stack every call
        is a ``call_soon_threadsafe`` wakeup of the consumer's loop)."""
        if not bursts and self.after_bursts is None:
            return
        with phase(self.recorder, "emit"):
            for i, burst in bursts.items():
                cb = self.slots[i].callback
                if cb is not None:
                    cb(i, burst)
            if self.after_bursts is not None:
                # also where ``bursts`` is empty: a first token's callback
                # (``_resolve_first``) may have run before
                self.after_bursts()

    def release(self, i: int) -> None:
        """Return a finished slot to the free pool (its tokens are consumed)."""
        if self.slots[i].live:
            # reject BEFORE touching the chunked-prefill bookkeeping: an
            # erroneous release of a mid-prefill slot must not destroy the
            # _chunked guard that drops its garbage decode rows
            raise RuntimeError(f"slot {i} still decoding")
        self._chunked.pop(i, None)
        if i in self._chunked_order:  # a stale entry would later hand the
            self._chunked_order.remove(i)  # slot's NEW occupant a kill
        if self.page_size:
            self._free_slot_pages(i)
        self.slots[i] = _Slot()

    def generate(self, prompt_ids, max_new_tokens: int = 32) -> list[int]:
        """Blocking single-request convenience: returns generated ids."""
        i = self.add_request(prompt_ids, max_new_tokens)
        while self.slots[i].live:
            self.step()
        self.drain()
        out = self.slots[i].tokens[:max_new_tokens]
        self.release(i)
        return out

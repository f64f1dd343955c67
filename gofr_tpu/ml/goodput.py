"""Goodput ledger: what did the devices actually buy us?

The dispatch axis (flight_recorder.py) and the request axis (journey.py)
are observable; this module opens the third — **device economics**. Every
token the devices compute is classified at the point where its fate is
decided, into ``delivered`` (it reached a consumer as part of a completed
answer) or one of the wasted reasons:

- ``spec_rejected`` — draft tokens a speculative verify window discarded
  (the price of drafting; the verifier's own token still delivers);
- ``deadline_cancelled`` — tokens produced for a slot its deadline reaped
  mid-decode (the answer never shipped as a whole);
- ``crashed`` — tokens produced for slots a generator crash failed;
- ``disconnected`` — tokens produced for a consumer that went away (or a
  force-close that dropped in-flight slots);
- ``failover_recompute`` — prompt tokens re-prefilled on a survivor after
  a replica loss (the fleet already paid that prefill once);
- ``restore_fallback`` — prefix tokens re-prefilled because a host-tier
  restore fell through (pool pressure beat the restore, the tier dropped
  or rejected the entry, or the registration evicted in the admission
  race);
- ``migration_cold`` — prefix tokens that left a draining replica during
  an elastic scale event and were lost on the way (the survivor
  cold-starts them);
- ``window_overshoot`` — tokens a fused decode window computed past a
  slot's EOS/budget before the on-device early-exit mask froze the row
  (the price of batching K steps into one program; delivered tokens in
  the same window still count as delivered);
- ``pipeline_overshoot`` — tokens a double-buffered dispatch
  (``GOFR_ML_PIPELINE``) computed for a slot that had already finished,
  been released, or been reaped by the time its window settled — the
  window was speculatively re-dispatched while its predecessor was
  still in flight (the price of keeping two windows outstanding;
  ``window_overshoot`` keeps naming live rows' early-exit raggedness);
- ``canary`` — tokens a shadow-canary replica (``GOFR_ML_CANARY``)
  computed for mirrored traffic samples. Canary output never reaches a
  client, so nothing it produces is ``delivered``; the mirror is the
  price of judging a candidate config on live traffic, and charging it
  here keeps the ledger balanced by construction;
- ``federation_recompute`` — prompt tokens re-prefilled on the local
  host after a federated remote route failed before its first burst
  (the peer died, partitioned, or went silent past the liveness
  deadline): the remote host may have spent prefill the fleet never
  saw, so the local recompute is charged as waste — the federation
  cousin of ``failover_recompute`` one level up.

The ledger **balances by construction**: every classification point
increments exactly one reason, so ``delivered + sum(wasted reasons) ==
device-computed tokens`` — the invariant tests/test_goodput.py asserts
for each reason in turn (speculation, deadlines, disconnects, crashes,
failover). Aggregated per model (a replica pool's cores roll up under
the pool name via the same ``pool/idx`` prefix match the event log uses)
and fleet-wide; served at ``GET /debug/goodput``, as a ``goodput`` block
in ``/debug/serving``, and as ``app_llm_tokens_wasted_total{model,
reason}`` + the ``app_llm_goodput_fraction`` gauge.

``GOFR_ML_GOODPUT=0`` disables the ledger under the same is-not-None
zero-overhead contract as ``GOFR_ML_FLIGHT_RECORDER``/``GOFR_ML_JOURNEY``
— every instrumented site guards on ``is not None`` and the hot loop
does no extra per-token work.

Everything here is host-side stdlib — no jax imports, safe to import
from the debug endpoints without paying the ml package's startup cost.
"""

from __future__ import annotations

import os
import threading
import time

__all__ = ["WASTE_REASONS", "GoodputLedger", "ModelGoodput",
           "goodput_ledger", "goodput_enabled"]

# the wasted-token taxonomy (the ``reason`` label values of
# app_llm_tokens_wasted_total); ``delivered`` is the ledger's other side
WASTE_REASONS = ("spec_rejected", "deadline_cancelled", "crashed",
                 "disconnected", "failover_recompute", "restore_fallback",
                 "migration_cold", "window_overshoot", "pipeline_overshoot",
                 "canary", "federation_recompute")


def goodput_enabled() -> bool:
    """``GOFR_ML_GOODPUT`` (default on): 0 disables the ledger — the
    instrumented sites see ``None`` and do zero extra work."""
    return os.environ.get("GOFR_ML_GOODPUT", "").strip() != "0"


class ModelGoodput:
    """A ledger handle bound to one model name — what the serving layer
    installs on a Generator / prefix cache / host-KV store (which don't
    know their model) so their classification points stay one-liners."""

    __slots__ = ("ledger", "model")

    def __init__(self, ledger: "GoodputLedger", model: str) -> None:
        self.ledger = ledger
        self.model = model

    def note(self, reason: str, tokens: int) -> None:
        self.ledger.note(self.model, reason, tokens)


class GoodputLedger:
    """Per-model token-fate counters with a process lifetime clock.

    ``note()`` is the ONE write API: one lock, two dict increments —
    cheap enough for burst cadence (it is never called per token; the
    callers batch per slot finish / verify window / fallback event).
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # model -> {"delivered": int, "wasted": {reason: int}}
        self._models: dict[str, dict] = {}
        self.t0 = time.monotonic()

    def handle(self, model: str) -> ModelGoodput:
        return ModelGoodput(self, model)

    def note(self, model: str, reason: str, tokens: int) -> None:
        """Classify ``tokens`` device-computed tokens for ``model`` as
        ``reason`` (``"delivered"`` or one of ``WASTE_REASONS``)."""
        if tokens <= 0:
            return
        if reason != "delivered" and reason not in WASTE_REASONS:
            raise ValueError(
                f"unknown goodput reason {reason!r} "
                f"(one of delivered|{'|'.join(WASTE_REASONS)})")
        with self._lock:
            row = self._models.get(model)
            if row is None:
                row = self._models[model] = {"delivered": 0, "wasted": {}}
            if reason == "delivered":
                row["delivered"] += int(tokens)
            else:
                row["wasted"][reason] = (row["wasted"].get(reason, 0)
                                         + int(tokens))

    # -- read side -----------------------------------------------------------
    def wasted_totals(self) -> dict[tuple[str, str], int]:
        """Lifetime ``(model, reason) -> tokens`` for the metric pass
        (the sampler publishes deltas as Prometheus counters)."""
        with self._lock:
            return {(model, reason): n
                    for model, row in self._models.items()
                    for reason, n in row["wasted"].items()}

    def models(self) -> list[str]:
        with self._lock:
            return sorted(self._models)

    @staticmethod
    def _summarize(delivered: int, wasted: dict, elapsed: float) -> dict:
        wasted_total = sum(wasted.values())
        total = delivered + wasted_total
        return {
            "device_tokens": total,
            "delivered": delivered,
            "wasted": dict(sorted(wasted.items(), key=lambda kv: -kv[1])),
            "wasted_total": wasted_total,
            "goodput": round(delivered / total, 4) if total else None,
            "delivered_per_s": (round(delivered / elapsed, 2)
                                if elapsed > 0 else None),
        }

    def snapshot_model(self, model: str) -> dict:
        """One model's ledger — a pool name aggregates its replica cores
        (``chat`` rolls up ``chat/0``, ``chat/1``, … like the event
        log's model filter)."""
        elapsed = time.monotonic() - self.t0
        delivered = 0
        wasted: dict[str, int] = {}
        with self._lock:
            for name, row in self._models.items():
                if name == model or name.startswith(model + "/"):
                    delivered += row["delivered"]
                    for reason, n in row["wasted"].items():
                        wasted[reason] = wasted.get(reason, 0) + n
        return self._summarize(delivered, wasted, elapsed)

    def snapshot(self) -> dict:
        """The ``/debug/goodput`` body: the fleet-wide ledger plus one
        row per model (replica cores appear under their own names; the
        pool-level row is the per-LLM block's aggregation)."""
        elapsed = time.monotonic() - self.t0
        with self._lock:
            models = {name: (row["delivered"], dict(row["wasted"]))
                      for name, row in self._models.items()}
        fleet_delivered = sum(d for d, _ in models.values())
        fleet_wasted: dict[str, int] = {}
        for _, w in models.values():
            for reason, n in w.items():
                fleet_wasted[reason] = fleet_wasted.get(reason, 0) + n
        return {
            "since_s": round(elapsed, 3),
            "fleet": self._summarize(fleet_delivered, fleet_wasted, elapsed),
            "models": {name: self._summarize(d, w, elapsed)
                       for name, (d, w) in sorted(models.items())},
        }


# the process-global instance every serving component shares — ONE
# ledger per process, like the fleet event log. ``goodput_ledger()``
# answers None when GOFR_ML_GOODPUT=0, so call sites get the
# is-not-None guard free.
_LEDGER = GoodputLedger()


def goodput_ledger() -> GoodputLedger | None:
    return _LEDGER if goodput_enabled() else None

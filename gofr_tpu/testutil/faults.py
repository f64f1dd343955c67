"""Fault injection for the LLM serving plane (chaos hook).

``GOFR_ML_FAULT`` arms probabilistic faults at named points of the device
dispatch path so the resilience layer (watchdog, crash recovery, typed
errors) can actually be exercised — by tests/test_resilience.py. Spec
grammar, comma-separated::

    point:rate[:ExcName]

    GOFR_ML_FAULT=step:0.02:RuntimeError
    GOFR_ML_FAULT=step:0.05,restore:1:OSError

Points (where the serving stack calls ``fire``):

- ``step``     — a decode-chunk dispatch (Generator.step)
- ``prefill``  — a prompt/suffix prefill or chunked-prefill segment
- ``spill``    — a device→host KV offload (Generator._spill_prefix)
- ``restore``  — a host→device KV restore (Generator.restore_prefix)
- ``emit``     — the token-burst callback into the serving layer
- ``route``    — a ReplicaPool routing decision (ml/replica.py)
- ``ship``     — a KV transport handoff out of a prefill replica
  (ml/kv_transport.py; the pages are already off the source)
- ``land``     — a KV transport arrival into a decode replica's host
  tier (fired on the receiving serving thread, before the store insert)
- ``scale_up`` — an elastic scale-up event (ml/replica.py: fired by the
  pool front before the new core is built)
- ``scale_down`` — an elastic scale-down event (fired before the
  retiring replica stops routing)
- ``migrate``  — one live-KV-migration attempt off a draining replica
  (fired on the SOURCE replica's serving thread, so
  ``GOFR_ML_FAULT_REPLICA`` narrows it to one replica's exports)
- ``sp_prefill`` — a sequence-parallel prefill wave (GOFR_ML_SP), fired
  BEFORE the sharded forward dispatches; the generator falls back to
  the single-device full prefill, bit-identically
- ``sp_gather`` — the landing/gather side of an SP prefill wave, fired
  after the sharded forward completed; the landed shards are discarded
  and the single-device full prefill rewrites the rows/pages
- ``peer_send`` — a federation/multihost wire write (``send_frame`` /
  ``send_bytes`` in ml/multihost.py), fired before the bytes hit the
  socket: the frame is lost and the sender sees a send failure
- ``peer_recv`` — a federation/multihost wire read (``recv_frame``),
  fired before the header read: the reader treats it as a torn
  connection, exactly like a peer that died mid-frame
- ``peer_partition`` — a network partition at the federation link layer
  (ml/federation.py): outbound frames fail to send and inbound frames
  are silently dropped, so the peer looks alive-but-unreachable (gossip
  silence → suspect → dead) rather than cleanly disconnected

The injector only exists when the env var is set (``from_env`` returns
``None`` otherwise) and the instrumented call sites guard with an
``is not None`` check — the disabled path costs one attribute test per
dispatch, nothing else. Draws come from a dedicated ``random.Random``
seeded by ``GOFR_ML_FAULT_SEED`` (default 1234) so a fault sequence is
reproducible run-to-run.

With a replica pool, ``GOFR_ML_FAULT_REPLICA=<idx>`` narrows the blast
radius to exactly one replica: only that replica's serving core gets an
injector (``from_env_for_replica``), so a failover test can
kill replica N deterministically while its peers stay clean. The front's
own ``route`` point is replica-independent and stays armed.
"""

from __future__ import annotations

import builtins
import os
import random

__all__ = ["FAULT_POINTS", "FaultInjector", "InjectedFault",
           "fault_snapshot"]

FAULT_POINTS = ("step", "prefill", "spill", "restore", "emit", "route",
                "ship", "land", "scale_up", "scale_down", "migrate",
                "sp_prefill", "sp_gather", "peer_send", "peer_recv",
                "peer_partition")


class InjectedFault(RuntimeError):
    """Default raised fault — recognizably synthetic in logs and error
    payloads (a subclass of RuntimeError, so everything that supervises
    real device failures supervises this too)."""


def _resolve_exc(name: str) -> type[BaseException]:
    exc = getattr(builtins, name, None)
    if isinstance(exc, type) and issubclass(exc, BaseException):
        if not issubclass(exc, Exception):
            # KeyboardInterrupt/SystemExit/GeneratorExit would bypass the
            # watchdog's ``except Exception`` and kill the serving thread
            # outright — that tests thread-death, not recovery
            raise ValueError(f"refusing to inject {name}: not supervisable")
        return exc
    raise ValueError(f"unknown exception type {name!r} in fault spec")


class FaultInjector:
    """Parsed ``GOFR_ML_FAULT`` spec + per-point fire counters.

    Callable: serving code invokes ``injector(point)`` (or ``fire``) at
    each instrumented site; with probability ``rate`` the configured
    exception is raised there, otherwise the call is a counter bump.
    """

    def __init__(self, points: dict[str, tuple[float, type[BaseException]]],
                 seed: int | None = None) -> None:
        for name in points:
            if name not in FAULT_POINTS:
                raise ValueError(
                    f"unknown fault point {name!r} (one of {FAULT_POINTS})")
        self.points = dict(points)
        self.seed = 1234 if seed is None else int(seed)
        self._rng = random.Random(self.seed)
        self.attempts: dict[str, int] = dict.fromkeys(FAULT_POINTS, 0)
        self.injected: dict[str, int] = dict.fromkeys(FAULT_POINTS, 0)

    @classmethod
    def parse(cls, spec: str, seed: int | None = None) -> "FaultInjector":
        """Parse a spec string; raises ValueError on malformed entries so a
        typo'd chaos config fails loudly at startup, not silently never."""
        points: dict[str, tuple[float, type[BaseException]]] = {}
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            fields = part.split(":")
            if len(fields) not in (2, 3):
                raise ValueError(
                    f"bad fault entry {part!r} (want point:rate[:ExcName])")
            point = fields[0].strip().lower()
            try:
                rate = float(fields[1])
            except ValueError:
                raise ValueError(
                    f"bad fault rate {fields[1]!r} in {part!r}") from None
            if not 0.0 < rate <= 1.0:
                raise ValueError(
                    f"fault rate {rate} out of range (0, 1] in {part!r}")
            exc = (_resolve_exc(fields[2].strip())
                   if len(fields) == 3 else InjectedFault)
            points[point] = (rate, exc)
        if not points:
            raise ValueError(f"empty fault spec {spec!r}")
        return cls(points, seed=seed)

    @classmethod
    def from_env(cls) -> "FaultInjector | None":
        """Build from ``GOFR_ML_FAULT``; ``None`` (injection disabled,
        zero overhead) when unset or empty."""
        spec = os.environ.get("GOFR_ML_FAULT", "").strip()
        if not spec:
            return None
        seed_raw = os.environ.get("GOFR_ML_FAULT_SEED", "").strip()
        return cls.parse(spec, seed=int(seed_raw) if seed_raw else None)

    @classmethod
    def armed_replica(cls) -> int | None:
        """``GOFR_ML_FAULT_REPLICA`` as an index, or None (all replicas).
        A malformed value fails loudly like a malformed spec would."""
        raw = os.environ.get("GOFR_ML_FAULT_REPLICA", "").strip()
        if not raw:
            return None
        try:
            return int(raw)
        except ValueError:
            raise ValueError(
                f"GOFR_ML_FAULT_REPLICA must be a replica index, "
                f"got {raw!r}") from None

    @classmethod
    def from_env_for_replica(cls, idx: int) -> "FaultInjector | None":
        """Per-replica arming for the pool: the env spec applies to
        replica ``idx`` only when ``GOFR_ML_FAULT_REPLICA`` is unset or
        names it. Each armed replica gets its OWN injector (independent,
        deterministically seeded draw sequence: base seed + idx)."""
        armed = cls.armed_replica()
        if armed is not None and armed != idx:
            return None
        inj = cls.from_env()
        if inj is None:
            return None
        return inj.for_replica(idx)

    def for_replica(self, idx: int) -> "FaultInjector | None":
        """Derive THIS injector for replica ``idx`` — the programmatic
        twin of ``from_env_for_replica``: same ``GOFR_ML_FAULT_REPLICA``
        narrowing, same independent per-replica seeding, so an injector
        handed to ``register_llm(..., fault=...)`` arms the replica cores
        exactly like the env spec would."""
        armed = self.armed_replica()
        if armed is not None and armed != idx:
            return None
        return type(self)(self.points, seed=self.seed + idx)

    def fire(self, point: str) -> None:
        armed = self.points.get(point)
        if armed is None:
            return
        self.attempts[point] += 1
        rate, exc = armed
        if rate >= 1.0 or self._rng.random() < rate:
            self.injected[point] += 1
            raise exc(f"injected fault at {point!r} "
                      f"(#{self.injected[point]}, GOFR_ML_FAULT)")

    __call__ = fire

    def snapshot(self) -> dict:
        """Chaos config + realized fire counts for /debug/serving."""
        return {
            "spec": {name: {"rate": rate, "raises": exc.__name__}
                     for name, (rate, exc) in self.points.items()},
            "seed": self.seed,
            "attempts": {k: v for k, v in self.attempts.items() if v},
            "injected": {k: v for k, v in self.injected.items() if v},
        }


def fault_snapshot(hook) -> dict | None:
    """Render an armed fault hook for /debug/serving — an injector's own
    ``snapshot()`` when it has one, a bare callable's identity otherwise.
    The ONE renderer behind ``LLMServer.resilience_snapshot`` and
    ``ReplicaPool.routing_snapshot`` so the two debug planes agree."""
    if hook is None:
        return None
    if hasattr(hook, "snapshot"):
        return hook.snapshot()
    return {"hook": getattr(hook, "__qualname__", repr(hook))}

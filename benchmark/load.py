"""Drives a plan at the system's gRPC stream and stamps what a client
sees. One event loop, no threads of its own.

All times are ``time.perf_counter()`` seconds; ``t0`` is the window's
start, so the pre-roll runs at negative offsets.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import statistics
import time


@dataclasses.dataclass
class Record:
    request: object
    due: float               # absolute; in a closed loop, when it was sent
    sent: float = 0.0
    frames: list = dataclasses.field(default_factory=list)   # (time, n_tokens)
    tokens: list = dataclasses.field(default_factory=list)
    end: float = 0.0
    error: str = ""

    @property
    def first(self) -> float | None:
        return self.frames[0][0] if self.frames else None

    @property
    def ok(self) -> bool:
        return not self.error and len(self.tokens) == self.request.max_new


def percentile(samples: list, pct: float) -> float:
    """Copied from ``bench/common.py``: inclusive quantiles, 1..99."""
    if not samples:
        raise ValueError("no samples")
    if len(samples) == 1:
        return samples[0]
    qs = statistics.quantiles(samples, n=100, method="inclusive")
    return qs[min(98, max(0, int(pct) - 1))]


class Client:
    """The example's ``llm.Chat/Generate`` stream, JSON frames."""

    def __init__(self, port: int, vocab: int) -> None:
        import grpc.aio

        self._channel = grpc.aio.insecure_channel(f"127.0.0.1:{port}")
        self._call = self._channel.unary_stream(
            "/llm.Chat/Generate",
            request_serializer=lambda o: json.dumps(o).encode(),
            response_deserializer=lambda raw: json.loads(raw) if raw else {})
        self._vocab = vocab

    async def close(self) -> None:
        await self._channel.close()

    async def send(self, rec: Record, deadline: float | None = None) -> Record:
        """``deadline`` (perf_counter): past it the answer is given up."""
        req = rec.request
        rec.sent = time.perf_counter()
        loop = asyncio.get_running_loop()
        until = (None if deadline is None
                 else loop.time() + deadline - time.perf_counter())
        try:
            async with asyncio.timeout_at(until):
                async for msg in self._call({"prompt_ids": req.prompt,
                                             "max_new_tokens": req.max_new}):
                    burst = msg.get("tokens", ())
                    rec.frames.append((time.perf_counter(), len(burst)))
                    rec.tokens.extend(burst)
        except Exception as exc:  # refused, broken or never answered: failed
            rec.error = f"{type(exc).__name__}: {exc}"[:300]
        rec.end = time.perf_counter()
        if not rec.error and not all(
                isinstance(t, int) and 0 <= t < self._vocab
                for t in rec.tokens):
            rec.error = "token outside the vocabulary"
        return rec


async def run_open(client: Client, plan, t0: float,
                   deadline: float | None = None) -> list:
    """Send each request when it is due, whatever is still in flight, and
    wait for all of them."""
    tasks = []
    for req in plan.requests:
        due = t0 + req.due_s
        delay = due - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(
            client.send(Record(req, due), deadline)))
    return list(await asyncio.gather(*tasks))


async def run_closed(client: Client, plan, t0: float,
                     deadline: float | None = None) -> list:
    """Each client sends its next request when the last is answered, from
    ``t0 - preroll_s`` until the window closes."""
    begin, close = t0 - plan.preroll_s, t0 + plan.seconds

    async def one(reqs) -> list:
        out, k = [], 0
        delay = begin - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        while time.perf_counter() < close:
            out.append(await client.send(
                Record(reqs[k % len(reqs)], time.perf_counter()), deadline))
            k += 1
        return out

    per_client = await asyncio.gather(*[one(reqs) for reqs in plan.clients])
    return [rec for recs in per_client for rec in recs]


async def run(client: Client, plan, t0: float,
              deadline: float | None = None) -> list:
    return await (run_open if plan.kind == "open" else run_closed)(
        client, plan, t0, deadline)


def in_window(records: list, t0: float, seconds: float) -> list:
    """The requests due (closed loop: sent) inside the window."""
    return [r for r in records if t0 <= r.due < t0 + seconds]


def lateness_ms(records: list) -> dict:
    """How late the generator sent against its schedule."""
    late = [(r.sent - r.due) * 1e3 for r in records]
    return {"n": len(late), "p50": percentile(late, 50),
            "p99": percentile(late, 99), "max": max(late)}

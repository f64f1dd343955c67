"""The one traffic generator: a mix is a data file of parameters
(``benchmark/traffic/<name>.json``), a plan is a pure function of the mix,
the vocabulary, the window's length and ``--seed``.

Everything is a session: a system prompt shared with other sessions (or
none), then turns, each adding user tokens to the history and asking for
an answer. A chat request is a session of one turn with no system prompt.
History holds filler drawn from the seed in place of the served answers,
so every prompt and every due time is known before the window opens and
the loop stays open.

The schedule is the mix's, the content is the seed's. When each request
is due, how long its prompt and its answer are and how many turns a
session takes are drawn from the mix's own ``schedule_seed``: the load a
cell offers is part of the cell, as its rate is, and every run of it
holds the same work at the same times. ``--seed`` draws what is sent: every
token id (and, elsewhere, the weights). Lengths, gaps and turn counts are
the quantile grid of their distributions in a shuffled order, so a short
window holds the whole distribution and not a lucky sample of it.

Arrivals:
- ``{"kind": "open", "sessions_per_s": r}``: ``round(r * seconds)``
  sessions start at exponential gaps and their turns follow at think-time
  gaps, on a circle as long as the window: a turn that would fall past
  the window's end falls as far after its start, as the turn of a session
  that began before the window does (its prompt holds the earlier turns).
  So the window is as busy at its first second as at its last. The
  pre-roll before it is the tail of a second such circle.
- ``{"kind": "closed", "clients": n}``: ``n`` clients, each sending its
  next request when the last is answered; a client's requests are a fixed
  cycle of lengths.
"""

from __future__ import annotations

import dataclasses
import math
import statistics


@dataclasses.dataclass
class Request:
    due_s: float | None      # against the window's start; None in a closed loop
    prompt: list
    max_new: int
    session: int
    turn: int                # 0 = a session's first turn
    shared: int              # tokens of the prompt other requests also start with


@dataclasses.dataclass
class Plan:
    kind: str                # "open" | "closed"
    requests: list           # open: sorted by due_s; closed: unused
    clients: list            # closed: one request cycle per client
    preroll_s: float
    seconds: float


def _grid(spec: dict, n: int) -> list:
    """``n`` values at the midpoint quantiles of the distribution."""
    lo, hi = spec["min"], spec["max"]
    qs = [(i + 0.5) / n for i in range(n)]
    if spec["dist"] == "uniform":
        vals = [lo + q * (hi - lo) for q in qs]
    elif spec["dist"] == "lognormal":
        nd = statistics.NormalDist(math.log(spec["median"]), spec["sigma"])
        vals = [math.exp(nd.inv_cdf(q)) for q in qs]
    elif spec["dist"] == "exponential":
        vals = [-math.log(1.0 - q) * spec["mean"] for q in qs]
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return [min(hi, max(lo, v)) for v in vals]


def _draws(rng, spec: dict, n: int, grid: int, integer: bool = True) -> list:
    """``n`` values: the grid, repeated as often as needed, in the seed's
    order."""
    base = _grid(spec, grid)
    vals = [base[i % grid] for i in range(n)]
    order = rng.permutation(n)
    out = [vals[i] for i in order]
    return [int(round(v)) for v in out] if integer else out


def generate(mix: dict, vocab: int, seconds: float, seed: int) -> Plan:
    import numpy as np

    rng = np.random.default_rng([int(mix["schedule_seed"]), 0x7AFF1C])
    content = np.random.default_rng([int(seed), 0xC0FFEE])
    grid = int(mix.get("grid", 64))
    arrivals = mix["arrivals"]
    preroll = float(mix.get("preroll_s", 0.0))
    sysp = mix.get("system_prompts")
    turns_lo, turns_hi = mix.get("turns", [1, 1])
    think = mix.get("think_s", {"dist": "uniform", "min": 0.0, "max": 0.0})

    def ids(n: int) -> list:
        return content.integers(1, vocab, int(n)).tolist()

    systems = []
    if sysp:
        lens = _draws(rng, {"dist": "uniform", "min": sysp["tokens"][0],
                            "max": sysp["tokens"][1]},
                      sysp["count"], sysp["count"])
        systems = [ids(n) for n in lens]

    def session(idx: int, n_turns: int, users, answers, thinks, start):
        """The turns of one session: (due, prompt, max_new, turn, shared)."""
        sp = systems[idx % len(systems)] if systems else []
        history, due, out = list(sp), start, []
        for t in range(n_turns):
            shared = len(history)
            history = history + ids(users[t])
            out.append((due, list(history), answers[t], t,
                        shared if (sp or t) else 0))
            history = history + ids(answers[t])  # filler for the answer
            due = due + thinks[t]
        return out

    if arrivals["kind"] == "closed":
        n_clients = int(arrivals["clients"])
        per = int(arrivals.get("cycle", 32))
        n = n_clients * per
        users = _draws(rng, mix["prompt_tokens"], n, grid)
        answers = _draws(rng, mix["answer_tokens"], n, grid)
        clients = []
        for c in range(n_clients):
            reqs = []
            for k in range(per):
                i = c * per + k
                (_, prompt, max_new, turn, shared), = session(
                    i, 1, [users[i]], [answers[i]], [0.0], 0.0)
                reqs.append(Request(None, prompt, max_new, i, turn, shared))
            clients.append(reqs)
        return Plan("closed", [], clients, preroll, float(seconds))

    if arrivals["kind"] != "open":
        raise ValueError(f"unknown arrivals kind {arrivals['kind']!r}")
    rate = float(arrivals["sessions_per_s"])
    n_sessions = max(1, int(round(rate * seconds)))

    def circle() -> list:
        """One window's worth of sessions on a circle of ``seconds``: a
        turn that would fall past the window's end falls as far after its
        start instead, as a session does that began before the window."""
        gaps = _draws(rng, {"dist": "exponential", "mean": 1.0, "min": 0.0,
                            "max": 1e9}, n_sessions, grid, integer=False)
        scale = seconds / sum(gaps)
        t, starts = float(rng.uniform(0.0, seconds)), []
        for g in gaps:
            t += g * scale
            starts.append(t % seconds)
        k_turns = _draws(rng, {"dist": "uniform", "min": turns_lo - 0.499,
                               "max": turns_hi + 0.499}, n_sessions, grid)
        k_turns = [min(turns_hi, max(turns_lo, k)) for k in k_turns]
        total = sum(k_turns)
        users = _draws(rng, mix["prompt_tokens"], total, grid)
        answers = _draws(rng, mix["answer_tokens"], total, grid)
        thinks = _draws(rng, think, total, grid, integer=False)
        out, at = [], 0
        for s, (start, k) in enumerate(zip(starts, k_turns, strict=True)):
            sl = slice(at, at + k)
            at += k
            for due, prompt, max_new, turn, shared in session(
                    s, k, users[sl], answers[sl], thinks[sl], start):
                out.append(Request(due % seconds, prompt, max_new, s, turn,
                                   shared))
        return out

    window = circle()
    # the pre-roll: the tail of another such window, before this one
    before = [dataclasses.replace(r, due_s=r.due_s - seconds,
                                  session=-1 - r.session)
              for r in circle() if r.due_s >= seconds - preroll]
    requests = sorted(before + window, key=lambda r: r.due_s)
    return Plan("open", requests, [], preroll, float(seconds))


def longest_context(mix: dict) -> int:
    """The longest prompt plus answer the mix can ask for."""
    sysp = mix.get("system_prompts")
    turns_hi = mix.get("turns", [1, 1])[1]
    return ((sysp["tokens"][1] if sysp else 0)
            + turns_hi * (mix["prompt_tokens"]["max"]
                          + mix["answer_tokens"]["max"]))

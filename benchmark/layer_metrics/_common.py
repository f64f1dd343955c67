"""Arithmetic the readers share. Not a metric: no ``.json`` beside it."""

from __future__ import annotations

import importlib


def bench_module(name: str):
    """A module of the benchmark's own (``flops``, ``load``)."""
    return importlib.import_module(f"benchmark.{name}")


def window_records(obs: dict) -> list:
    return bench_module("load").in_window(obs["records"], obs["t0"],
                                          obs["seconds"])


def window_journeys(obs: dict) -> list:
    """Journeys of the requests enqueued inside the window."""
    t0, t1 = obs["t0"], obs["t0"] + obs["seconds"]
    return [j for j in obs.get("journeys", []) if t0 <= j["t0"] < t1]


def first_mark(journey: dict, name: str):
    for m in journey["marks"]:
        if m["mark"] == name:
            return m
    return None


def counter_delta(obs: dict, *path):
    """How far a counter moved between the window's start and its end."""
    def dig(d):
        for key in path:
            d = d[key]
        return d

    return (dig(obs["marks"]["end"]["counters"])
            - dig(obs["marks"]["start"]["counters"]))


def slice_tokens(obs: dict) -> tuple:
    """What was processed inside the traced slice, by the client's stamps
    (host clock; the slice is seconds long, the stamps are good to a
    millisecond): ``prefills`` as (prompt tokens, shared tokens) of the
    requests whose first frame came inside it, and ``decoded`` as the
    context length of every later token delivered inside it."""
    h0, h1 = obs["slice"]["h0"], obs["slice"]["h1"]
    prefills, decoded = [], []
    for r in obs["records"]:
        n_prompt = len(r.request.prompt)
        at = 0
        for i, (t, n) in enumerate(r.frames):
            if h0 <= t < h1:
                if i == 0:
                    prefills.append((n_prompt, r.request.shared))
                    decoded.extend(n_prompt + at + k for k in range(1, n))
                else:
                    decoded.extend(n_prompt + at + k for k in range(n))
            at += n
    return prefills, decoded


def matching(table: dict, patterns: list) -> dict:
    """Rows of a trace table whose name holds one of the patterns."""
    return {name: v for name, v in table.items()
            if any(p in name for p in patterns)}

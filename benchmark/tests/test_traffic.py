"""The generator is a pure function of the mix and the seed; the schedule
is the mix's and the same for every seed, the token ids are the seed's;
the runner reports its lateness."""

import asyncio
import json
import os

import pytest

from benchmark import load, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
# the registered mixes, and the rehearsal's unshared open-loop one (no
# registered cell sends such traffic yet)
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")))
MIXES.append("tests/toy/traffic/chat")


def mix(name):
    path = name if "/" in name else os.path.join("traffic", name)
    with open(os.path.join(BENCH, path + ".json")) as f:
        return json.load(f)


def flat(plan):
    reqs = plan.requests or [r for c in plan.clients for r in c]
    return [(r.due_s, tuple(r.prompt), r.max_new, r.session, r.turn) for r in reqs]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_plan_other_seed_other_plan(name):
    a = traffic.generate(mix(name), 32768, 20.0, 2**31 + 77)
    b = traffic.generate(mix(name), 32768, 20.0, 2**31 + 77)
    c = traffic.generate(mix(name), 32768, 20.0, 5)
    assert flat(a) == flat(b)
    assert flat(a) != flat(c)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_holds_the_same_schedule(name):
    m = mix(name)
    plans = [traffic.generate(m, 32768, 20.0, s) for s in (1, 2, 2**31 + 5)]

    def schedule(p):
        reqs = p.requests or [r for c in p.clients for r in c]
        return [(r.due_s, len(r.prompt), r.shared, r.max_new, r.session,
                 r.turn) for r in reqs]

    assert schedule(plans[0]) == schedule(plans[1]) == schedule(plans[2])
    other = traffic.generate(dict(m, schedule_seed=m["schedule_seed"] + 1),
                             32768, 20.0, 1)
    assert schedule(other) != schedule(plans[0])
    for p in plans:
        for r in (p.requests or [r for c in p.clients for r in c]):
            assert len(r.prompt) + r.max_new <= traffic.longest_context(m)
            assert all(1 <= t < 32768 for t in r.prompt)


def test_open_plan_is_sorted_and_inside_its_window():
    p = traffic.generate(mix("sessions"), 1000, 20.0, 9)
    dues = [r.due_s for r in p.requests]
    assert dues == sorted(dues)
    assert -p.preroll_s <= dues[0] and dues[-1] < 20.0
    # a later turn's prompt starts with the earlier turn's whole prompt
    by_session = {}
    for r in p.requests:
        by_session.setdefault(r.session, []).append(r)
    deep = [sorted(rs, key=lambda r: r.turn) for rs in by_session.values()
            if len(rs) > 1]
    assert deep
    for rs in deep:
        for a, b in zip(rs, rs[1:]):
            assert b.prompt[:len(a.prompt)] == a.prompt and b.shared > len(a.prompt)


class FakeClient:
    """Answers after a fixed delay: the runner's own lateness shows."""

    async def send(self, rec, deadline=None):
        import time

        rec.sent = time.perf_counter()
        await asyncio.sleep(0.01)
        now = time.perf_counter()
        rec.frames.append((now, 1))
        rec.tokens = [1] * rec.request.max_new
        rec.frames.append((now, rec.request.max_new - 1))
        rec.end = now
        return rec


def test_runner_reports_lateness_and_window():
    import time

    m = {"arrivals": {"kind": "open", "sessions_per_s": 40.0},
         "schedule_seed": 1, "preroll_s": 0.2,
         "prompt_tokens": {"dist": "uniform", "min": 4, "max": 8},
         "answer_tokens": {"dist": "uniform", "min": 2, "max": 4}, "grid": 8}
    plan = traffic.generate(m, 100, 1.0, 3)

    async def go():
        t0 = time.perf_counter() + plan.preroll_s
        return t0, await load.run(FakeClient(), plan, t0)

    t0, recs = asyncio.run(go())
    assert len(recs) == len(plan.requests)
    late = load.lateness_ms(recs)
    assert late["n"] == len(recs) and 0 <= late["p50"] <= late["max"] < 100
    inside = load.in_window(recs, t0, 1.0)
    assert 0 < len(inside) < len(recs)


def test_closed_runner_keeps_every_client_busy():
    import time

    m = {"arrivals": {"kind": "closed", "clients": 3, "cycle": 4},
         "schedule_seed": 1, "preroll_s": 0.1,
         "prompt_tokens": {"dist": "uniform", "min": 4, "max": 8},
         "answer_tokens": {"dist": "uniform", "min": 2, "max": 4}, "grid": 8}
    plan = traffic.generate(m, 100, 0.5, 3)

    async def go():
        t0 = time.perf_counter() + plan.preroll_s
        return await load.run(FakeClient(), plan, t0)

    recs = asyncio.run(go())
    assert len(recs) >= 3 * 20  # 0.6 s of 10 ms answers, three clients


def test_percentile_matches_statistics():
    xs = [float(i) for i in range(1, 101)]
    assert load.percentile(xs, 50) == pytest.approx(50.5)
    assert load.percentile(xs, 95) == pytest.approx(95.05)
    assert load.percentile([7.0], 95) == 7.0

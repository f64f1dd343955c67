"""From a profiler trace to device numbers: a pure function of an
``.xplane.pb``.

The traced window is ONE interval ``[t0, t1]`` on the trace's own clock:
the span of the harness's marker annotation (``bench_window``), which the
profiler records on a host line with the same clock as the device lines.
Every device interval is clipped to it. Busy is the union of the clipped
intervals per device; over several devices the result is their mean,
never a sum and never device 0 alone. A trace with no marker, no device
plane, or no device event inside the window is an error with its own
message, never a zero.
"""

from __future__ import annotations

MARKER = "bench_window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class TraceError(RuntimeError):
    """The trace does not hold what the reduction needs."""


def load(path: str) -> list:
    """Planes -> lines -> ``(name, start_ns, duration_ns)`` events, with
    nothing but jax."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return planes_of(data)


def short_name(name: str) -> str:
    """A device operation is traced under its whole HLO text
    (``%fusion.3 = bf16[...] fusion(...)``); its own name is what stands
    before the ``=``. A program's name (``jit_chunk_fn(1374...)``) and a
    host span's are kept as they are."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def planes_of(data) -> list:
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name,
                          "events": [(short_name(e.name), float(e.start_ns),
                                      float(e.duration_ns))
                                     for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def window_of(planes: list, marker: str = MARKER) -> tuple:
    """The marker's span on the trace's clock."""
    spans = [(s, s + d) for plane in planes
             if not plane["name"].startswith(DEVICE_PREFIX)
             for line in plane["lines"]
             for name, s, d in line["events"] if name == marker]
    if not spans:
        raise TraceError(f"the trace holds no {marker!r} annotation: the "
                         f"window cannot be placed on the trace's clock")
    if len(spans) > 1:
        raise TraceError(f"the trace holds {len(spans)} {marker!r} "
                         f"annotations, one window was traced")
    t0, t1 = spans[0]
    if t1 <= t0:
        raise TraceError(f"the {marker!r} annotation is empty: {t0}..{t1}")
    return t0, t1


def clip(events: list, t0: float, t1: float) -> list:
    """``(name, start, end)`` of the events' parts inside ``[t0, t1]``."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b))
    return out


def union(intervals: list) -> list:
    """Merged, sorted ``(start, end)`` of ``(name, start, end)``."""
    merged: list = []
    for _, a, b in sorted(intervals, key=lambda x: x[1]):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def self_times(intervals: list) -> dict:
    """Seconds by name with nested events' time taken out of their
    parents (a ``while`` holds its body's operations on the same line)."""
    out: dict = {}
    stack: list = []  # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + own / 1e9

    for name, a, b in sorted(intervals, key=lambda x: (x[1], -(x[2] - x[1]))):
        close(a)
        if stack:
            b = min(b, stack[-1][1])  # a child never outlasts its parent
            stack[-1][2] -= b - a
        stack.append([name, b, b - a])
    close(float("inf"))
    return out


def innermost_loops(ops: list) -> list:
    """Start times of the ``while`` operations that hold no other: a
    decode program runs its layers as one loop per step, so a program
    run's steps are the innermost loops inside it, whatever its name."""
    loops = sorted(((a, b) for name, a, b in ops if name.startswith("while")),
                   key=lambda ab: (ab[0], -(ab[1] - ab[0])))
    out = []
    for i, (a, b) in enumerate(loops):
        nxt = loops[i + 1] if i + 1 < len(loops) else None
        if nxt is None or nxt[0] >= b:   # the next loop starts after this ends
            out.append(a)
    return out


def reduce(planes: list, *, device_ids: list | None = None,
           marker: str = MARKER) -> dict:
    """``window_s``, ``busy_s`` (mean over devices), per device its busy
    seconds, the operations' self times, the programs' (modules') time and
    runs, and the longest idle gaps. ``device_ids`` are the chips the cell
    runs on (all the trace holds, if not given): a chip that ran nothing
    while the profiler was on may have no plane or no operations line, and
    is then idle for the whole window, not left out of the mean."""
    t0, t1 = window_of(planes, marker)
    found = {p["name"]: p for p in planes
             if p["name"].startswith(DEVICE_PREFIX)}
    if not found:
        raise TraceError(
            "the trace holds no device plane "
            f"({DEVICE_PREFIX}*); planes: {[p['name'] for p in planes]}")
    names = (sorted(found) if device_ids is None
             else [f"{DEVICE_PREFIX}{i}" for i in device_ids])
    per_device = []
    for name in names:
        plane = found.get(name, {"lines": []})
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        ops = clip(lines.get(OPS_LINE, []), t0, t1)
        merged = union(ops)
        modules = clip(lines.get(MODULES_LINE, []), t0, t1)
        loops = innermost_loops(ops)
        programs: dict = {}
        for prog, a, b in modules:
            row = programs.setdefault(
                prog, {"seconds": 0.0, "runs": 0, "inner_loops": 0})
            row["seconds"] += (b - a) / 1e9
            row["runs"] += 1
            row["inner_loops"] += sum(1 for t in loops if a <= t < b)
        edges = [t0] + [x for ab in merged for x in ab] + [t1]
        gaps = sorted((edges[i + 1] - edges[i]
                       for i in range(0, len(edges), 2)), reverse=True)
        per_device.append({
            "plane": name,
            "in_trace": name in found,
            # the window less its gaps: never over the window
            "busy_s": ((t1 - t0) - sum(gaps)) / 1e9,
            "events": len(ops),
            "op_self_s": self_times(ops),
            "op_total_s": _totals(ops),
            "op_runs": _counts(ops),
            "programs": programs,
            "idle_gaps_s": [g / 1e9 for g in gaps[:10] if g > 0],
        })
    if not any(d["events"] for d in per_device):
        raise TraceError(
            f"no device operation ran inside the traced window "
            f"({(t1 - t0) / 1e9:.3f} s) on any of {names} (the trace holds "
            f"{sorted(found)}): the slice held no work, or the device "
            f"lines are on another clock than the marker")
    window_s = (t1 - t0) / 1e9
    busy_s = sum(d["busy_s"] for d in per_device) / len(per_device)
    if not 0 < busy_s <= window_s:
        raise TraceError(f"busy {busy_s} s outside (0, window {window_s} s]")
    return {"window_s": window_s, "busy_s": busy_s, "t0_ns": t0, "t1_ns": t1,
            "devices": per_device}


def _totals(intervals: list) -> dict:
    out: dict = {}
    for name, a, b in intervals:
        out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def _counts(intervals: list) -> dict:
    out: dict = {}
    for name, _, _ in intervals:
        out[name] = out.get(name, 0) + 1
    return out


def summed(reduced: dict, field: str) -> dict:
    """One of the per-device tables summed over the devices."""
    out: dict = {}
    for dev in reduced["devices"]:
        for name, v in dev[field].items():
            if isinstance(v, dict):
                row = out.setdefault(name, dict.fromkeys(v, 0))
                for key in v:
                    row[key] += v[key]
            else:
                out[name] = out.get(name, 0) + v
    return out


def breakdown(reduced: dict) -> dict:
    """The contract's ``breakdown``: the ten operations with most device
    time (self time, mean over the devices) and the ten longest idle gaps.
    The program carries no annotation yet, so a gap has no host cause."""
    n = len(reduced["devices"])
    ops = sorted(summed(reduced, "op_self_s").items(), key=lambda kv: -kv[1])
    gaps = sorted((g for d in reduced["devices"] for g in d["idle_gaps_s"]),
                  reverse=True)
    return {"device_ops": [[name, s / n] for name, s in ops[:10]],
            "idle_gaps": [["host: unattributed", g] for g in gaps[:10]]}

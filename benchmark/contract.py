"""The last line: built by one function, checked by another before it is
printed. ``validate`` holds the line to the driver's contract and to
``BENCHMARK.json``; ``run.py`` prints nothing it refuses.
"""

from __future__ import annotations

import json
import math

DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


class ContractError(ValueError):
    """The line is not what the driver's contract asks for."""


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise ContractError(f"no workload {workload!r} in BENCHMARK.json")


def metrics_of(bench: dict, workload: str, traced: bool) -> list:
    """The metric entries this cell reports in this kind of run."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def build_line(*, correct: bool, attempted: int, failed: int, metrics: dict,
               units: dict, device: dict, breakdown: dict | None = None,
               compared: dict | None = None) -> dict:
    line = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {name: {"value": value, "unit": units[name]}
                    for name, value in metrics.items()},
        "device": dict(device),
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared or {}  # last, as the contract asks
    return line


def _finite(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def _walk(obj, path: str, bad: list) -> None:
    if isinstance(obj, dict):
        for k, v in obj.items():
            _walk(v, f"{path}.{k}", bad)
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            _walk(v, f"{path}[{i}]", bad)
    elif obj is None or (isinstance(obj, float) and not math.isfinite(obj)):
        bad.append(f"{path} is {obj!r}")


def validate(line: dict, bench: dict, workload: str, traced: bool) -> None:
    """Raises ``ContractError`` naming everything that is wrong."""
    bad: list = []
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in line:
            bad.append(f"key {key!r} is missing")
    if bad:
        raise ContractError("; ".join(bad))
    if not isinstance(line["correct"], bool):
        bad.append(f"correct is {line['correct']!r}, not a boolean")
    for key in ("attempted", "failed"):
        v = line[key]
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            bad.append(f"{key} is {v!r}, not a count")
    if not bad and line["failed"] > line["attempted"]:
        bad.append(f"failed {line['failed']} > attempted {line['attempted']}")
    _walk({k: line[k] for k in line if k != "compared"}, "line", bad)

    want = metrics_of(bench, workload, traced)
    got = line["metrics"]
    for m in want:
        entry = got.get(m["name"])
        if entry is None:
            # a reader that found nothing to read in a cell that lists it
            bad.append(f"metric {m['name']} is missing")
            continue
        if not isinstance(entry, dict) or set(entry) != {"value", "unit"}:
            bad.append(f"metric {m['name']} is {entry!r}, not value and unit")
            continue
        if not _finite(entry["value"]):
            bad.append(f"metric {m['name']} has value {entry['value']!r}")
        if entry["unit"] != m["unit"]:
            bad.append(f"metric {m['name']} has unit {entry['unit']!r}, "
                       f"BENCHMARK.json says {m['unit']!r}")
    extra = set(got) - {m["name"] for m in want}
    if extra:
        bad.append(f"metrics not of this cell and run: {sorted(extra)}")
    if not got:
        bad.append("no metric at all")
    if not traced and "setup_s" in got and _finite(got["setup_s"].get("value")) \
            and got["setup_s"]["value"] <= 0:
        bad.append(f"setup_s is {got['setup_s']['value']}")

    dev = line["device"]
    for key in DEVICE_KEYS:
        if key not in dev:
            bad.append(f"device.{key} is missing")
    if not isinstance(dev.get("platform"), str) or not dev.get("platform"):
        bad.append(f"device.platform is {dev.get('platform')!r}")
    if not isinstance(dev.get("kind"), str) or not dev.get("kind"):
        bad.append(f"device.kind is {dev.get('kind')!r}")
    want_chips = cell(bench, workload)["chips"]
    if dev.get("count") != want_chips:
        bad.append(f"device.count is {dev.get('count')!r}, the cell asks "
                   f"for {want_chips}")
    peak = dev.get("memory_peak_bytes")
    if not _finite(peak) or peak <= 0:
        bad.append(f"device.memory_peak_bytes is {peak!r}")
    if traced:
        busy, window = dev.get("busy_s"), dev.get("window_s")
        if not _finite(window) or window <= 0:
            bad.append(f"device.window_s is {window!r}")
        if not _finite(busy) or busy <= 0:
            bad.append(f"device.busy_s is {busy!r}, it has to be above 0")
        if _finite(busy) and _finite(window) and busy > window:
            bad.append(f"device.busy_s {busy} > device.window_s {window}")
        bd = line.get("breakdown")
        if bd is not None:
            for key in ("device_ops", "idle_gaps"):
                rows = bd.get(key)
                if not isinstance(rows, list) or len(rows) > 10:
                    bad.append(f"breakdown.{key} is not a list of at most 10")
                    continue
                for row in rows:
                    if (not isinstance(row, (list, tuple)) or len(row) != 2
                            or not isinstance(row[0], str)
                            or not _finite(row[1])):
                        bad.append(f"breakdown.{key} has the row {row!r}")
    for name, m in got.items():
        if (isinstance(m, dict) and _finite(m.get("value"))
                and m.get("unit") == "%"
                and (name.endswith("_roofline") or "mfu" in name)
                and not 0 < m["value"] <= 105):
            bad.append(f"{name} is {m['value']}%: a share of a peak lies "
                       f"above 0 and cannot pass 100")
    try:
        text = json.dumps(line, allow_nan=False)
        if "\n" in text:
            bad.append("the line has a line break")
    except ValueError as exc:
        bad.append(f"the line does not serialise as strict JSON: {exc}")
    if bad:
        raise ContractError("; ".join(bad))

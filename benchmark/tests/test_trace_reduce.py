"""``trace_reduce`` on the recorded traces in ``benchmark/testdata``: busy
is clipped to one window on the trace's clock, taken per device and never
passes the window; an empty window is an error, not a zero."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "testdata")


def recorded(name, window=None, markers=1):
    with open(os.path.join(DATA, name)) as f:
        rec = json.load(f)
    planes = [{"name": p["name"],
               "lines": [{"name": l["name"],
                          "events": [tuple(e) for e in l["events"]]}
                         for l in p["lines"]]} for p in rec["planes"]]
    t0, t1 = window or rec["extract_ns"]
    planes.append({"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [(tr.MARKER, t0, t1 - t0)] * markers
         + [("np.asarray(jax.Array)", t0 - 5e6, 1e6)]}]})
    return planes


def test_one_device_busy_is_clipped_and_inside_the_window():
    out = tr.reduce(recorded("trace_one_device.json"), device_ids=[0])
    assert out["window_s"] == pytest.approx(0.06)
    assert 0 < out["busy_s"] <= out["window_s"]
    dev, = out["devices"]
    # the first program began 670 ms before the window: only its part
    # inside counts, to the nanosecond
    long_run = dev["programs"]["jit__lambda(8820425504960188394)"]
    assert long_run["seconds"] == pytest.approx(0.05301061, abs=1e-6)
    assert sum(dev["op_self_s"].values()) == pytest.approx(dev["busy_s"], rel=1e-6)
    assert all(g >= 0 for g in dev["idle_gaps_s"])
    assert dev["busy_s"] + sum(dev["idle_gaps_s"]) <= out["window_s"] + 1e-9


def test_a_narrower_window_clips_harder():
    whole = tr.reduce(recorded("trace_one_device.json"), device_ids=[0])
    half = tr.reduce(recorded("trace_one_device.json", window=(0, 30_000_000)),
                     device_ids=[0])
    assert half["window_s"] == pytest.approx(0.03)
    assert 0 < half["busy_s"] <= half["window_s"]
    assert half["busy_s"] < whole["busy_s"]


def test_four_devices_mean_not_sum_not_device_zero():
    out = tr.reduce(recorded("trace_four_devices.json"), device_ids=[0, 1, 2, 3])
    busy = [d["busy_s"] for d in out["devices"]]
    assert busy[2] == 0.0                      # nothing inside its window
    assert busy[3] == pytest.approx(out["window_s"])   # busy throughout
    assert 0 < busy[0] <= out["window_s"] and 0 < busy[1] <= out["window_s"]
    assert out["busy_s"] == pytest.approx(sum(busy) / 4)
    assert out["busy_s"] <= out["window_s"]
    assert out["busy_s"] != pytest.approx(busy[0])
    bd = tr.breakdown(out)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s >= 0 for n, s in bd["device_ops"])
    # the idle device's whole window is the longest gap
    assert bd["idle_gaps"][0][1] == pytest.approx(out["window_s"])


def test_an_empty_window_is_an_error_not_a_zero():
    planes = recorded("trace_four_devices.json",
                      window=(400_000_000, 460_000_000))
    with pytest.raises(tr.TraceError, match="no device operation"):
        tr.reduce(planes, device_ids=[0, 1, 2])


def test_what_is_missing_has_its_own_message():
    planes = recorded("trace_one_device.json")
    with pytest.raises(tr.TraceError, match="no device plane"):
        tr.reduce([p for p in planes if p["name"] == "/host:CPU"])
    with pytest.raises(tr.TraceError, match="annotation"):
        tr.reduce([p for p in planes if p["name"] != "/host:CPU"])
    with pytest.raises(tr.TraceError, match="2 'bench_window'"):
        tr.reduce(recorded("trace_one_device.json", markers=2))


def test_a_chip_without_a_plane_or_an_ops_line_is_idle_not_left_out():
    planes = recorded("trace_four_devices.json")
    whole = tr.reduce(planes, device_ids=[0, 1, 2, 3])
    for p in planes:
        if p["name"] == "/device:TPU:2":   # ran nothing: no operations line
            p["lines"] = [l for l in p["lines"] if l["name"] != tr.OPS_LINE]
    no_plane = [p for p in planes if p["name"] != "/device:TPU:1"]
    out = tr.reduce(no_plane, device_ids=[0, 1, 2, 3])
    busy = [d["busy_s"] for d in out["devices"]]
    assert busy[1] == 0.0 and busy[2] == 0.0 and len(busy) == 4
    assert [d["in_trace"] for d in out["devices"]] == [True, False, True, True]
    assert out["busy_s"] == pytest.approx((busy[0] + busy[3]) / 4)
    assert 0 < out["busy_s"] < whole["busy_s"]
    # only the cell's own chips are read on a host that holds more
    one = tr.reduce(planes, device_ids=[3])
    assert one["busy_s"] == pytest.approx(one["window_s"])
    assert len(one["devices"]) == 1


def test_decode_steps_are_the_innermost_loops():
    ops = [("while.19", 0, 100), ("while.20", 1, 11), ("fusion.3", 2, 5),
           ("while.20", 12, 22), ("while.20", 30, 40),
           ("while.7", 200, 260), ("copy.1", 205, 210)]
    assert tr.innermost_loops(ops) == [1, 12, 30, 200]


def test_self_times_take_children_out_of_their_parents():
    ops = [("while.1", 0, 100), ("fusion.1", 10, 30), ("fusion.2", 40, 90),
           ("custom.1", 50, 60), ("fusion.1", 120, 130)]
    got = tr.self_times(ops)
    assert got == {"while.1": pytest.approx(30e-9), "fusion.1": pytest.approx(30e-9),
                   "fusion.2": pytest.approx(40e-9), "custom.1": pytest.approx(10e-9)}


def test_load_reads_an_xplane_file(tmp_path):
    from jax.profiler import ProfileData

    text = '''
planes { name: "/device:TPU:0"
  event_metadata { key: 1 value { id: 1 name: "%fusion.9 = bf16[8]{0} fusion(bf16[8]{0} %p)" } }
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 5000000 } } }
planes { name: "/host:CPU"
  event_metadata { key: 1 value { id: 1 name: "bench_window" } }
  lines { name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 } } }
'''
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    out = tr.reduce(tr.load(str(path)), device_ids=[0])
    assert out["window_s"] == pytest.approx(20e-6)
    assert out["busy_s"] == pytest.approx(5e-6)
    assert list(out["devices"][0]["op_self_s"]) == ["fusion.9"]

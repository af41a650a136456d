"""The reduction from trace events to busy time, idle share and
breakdown."""

import json
import os

import pytest

from perfbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def ev(plane, name, start, dur, line="XLA Ops"):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur}


DEV = "/device:TPU:0"
HOST = "/host:CPU"


def test_idle_counts_gaps_before_first_and_after_last_op():
    # window 0..1000 ns; device busy 300..400 and 500..600
    events = [
        ev(HOST, "perfbench.window", 0, 1000, line="python"),
        ev(HOST, "perfbench.init", 0, 300, line="python"),
        ev(HOST, "perfbench.run", 300, 700, line="python"),
        ev(DEV, "%fusion.1 = f32[8]{0} fusion(x)", 300, 100),
        ev(DEV, "%fusion.2 = f32[8]{0} fusion(x)", 500, 100),
    ]
    r = tr.reduce(events)
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["busy_s"] == pytest.approx(2e-7)
    assert r["idle_frac"] == pytest.approx(0.8)
    gaps = r["breakdown"]["idle_gaps"]
    # the gap after the last op (600..1000) is the longest, then the one
    # before the first (0..300), then the one between (400..500)
    assert [g[0] for g in gaps] == ["perfbench.run", "perfbench.init",
                                    "perfbench.run"]
    assert [g[1] for g in gaps] == pytest.approx([4e-7, 3e-7, 1e-7])
    # obs/prof.py's denominator (first op to last) would read 1/3 here
    span = 600 - 300
    assert 1 - 200 / span == pytest.approx(1 / 3)


def test_ops_outside_the_window_are_clipped():
    events = [
        ev(HOST, "perfbench.window", 100, 100, line="python"),
        ev(DEV, "a", 50, 100),       # half inside
        ev(DEV, "b", 250, 10),       # outside
    ]
    r = tr.reduce(events)
    assert r["busy_s"] == pytest.approx(50e-9)
    assert r["breakdown"]["device_ops"] == [["a", pytest.approx(50e-9)]]


def test_nested_ops_count_self_time_and_busy_once():
    events = [
        ev(HOST, "perfbench.window", 0, 100, line="python"),
        ev(DEV, "%while.3 = (s32[], f32[4]) while(x)", 10, 80),
        ev(DEV, "%fusion.131 = f32[4]{0} fusion(y)", 20, 30),
        ev(DEV, "%fusion.131 = f32[4]{0} fusion(y)", 55, 30),
    ]
    r = tr.reduce(events)
    assert r["busy_s"] == pytest.approx(80e-9)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion.131 f32[4]"] == pytest.approx(60e-9)
    assert ops["while.3 (s32[],"] == pytest.approx(20e-9)


def test_several_devices_average():
    events = [
        ev(HOST, "perfbench.window", 0, 100, line="python"),
        ev(DEV, "a", 0, 100),
        ev("/device:TPU:1", "a", 0, 50),
    ]
    r = tr.reduce(events)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(75e-9)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce([ev(DEV, "a", 0, 1)])


def test_recorded_v5e_trace():
    with open(os.path.join(DATA, "trace_v5e_small.json")) as f:
        events = json.load(f)["events"]
    r = tr.reduce(events)
    w = [e for e in events if e["name"] == "perfbench.window"][0]
    dev = [e for e in events if e["plane"].startswith("/device:")]
    first = min(e["start_ns"] for e in dev)
    last = max(e["start_ns"] + e["dur_ns"] for e in dev)
    assert r["window_s"] == pytest.approx(w["dur_ns"] / 1e9)
    # the window opens 50 ms before the first op and closes after the last
    assert first - w["start_ns"] > 4e7
    assert w["start_ns"] + w["dur_ns"] - last > 4e7
    busy = sum(e["dur_ns"] for e in dev) / 1e9
    assert r["busy_s"] == pytest.approx(busy)
    assert r["idle_frac"] == pytest.approx(1 - busy / r["window_s"])
    assert r["idle_frac"] > 0.8
    assert r["breakdown"]["device_ops"][0][0] == "fusion f32[262144]"

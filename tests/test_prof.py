"""obs/prof.py: the profile.v1 parser against adversarial Chrome
traces, the interval algebra, region-name validation, the device-profile
registry, and the bench-gate device_kind fail-closed rule.

The smoke (`make prof-smoke`) proves the pipeline against a REAL
jax.profiler capture; these tests feed the parser synthetic traces a
real capture cannot reliably produce — nested regions, zero-length
events, out-of-order timestamps, multi-device streams, missing
durations, gzip truncation — and require either correct math or a loud
``ProfileParseError``, never a silently wrong report.
"""

import gzip
import json
import os
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS)
sys.path.insert(0, REPO)

from lux_tpu.obs import prof, report  # noqa: E402

OPS = {"module": "jit_step", "ops": {
    "all-gather.1": "lux.test.exchange",
    "fusion.2": "lux.test.compute",
}}


def ev(name, ts, dur, pid=1, hlo_op=None, module="jit_step", **extra):
    e = {"ph": "X", "name": name, "ts": ts, "dur": dur, "pid": pid,
         "tid": 1}
    if hlo_op is not None:
        e["args"] = {"hlo_op": hlo_op, "hlo_module": module}
    e.update(extra)
    return e


def parse(events, **kw):
    kw.setdefault("op_maps", [OPS])
    return prof.parse_events({"traceEvents": events}, **kw)


# -- interval algebra ------------------------------------------------------


def test_merge_coalesces_and_drops_empty():
    assert prof.merge_intervals([(5, 7), (0, 2), (1, 3), (7, 7)]) == \
        [(0.0, 3.0), (5.0, 7.0)]
    assert prof.union_total([(0.0, 3.0), (5.0, 7.0)]) == 5.0


def test_intersect_merged():
    a = prof.merge_intervals([(0, 10)])
    b = prof.merge_intervals([(2, 4), (6, 8), (9, 12)])
    assert prof.intersect_merged(a, b) == [(2.0, 4.0), (6.0, 8.0),
                                          (9.0, 10.0)]


# -- classification and the union/intersection math ------------------------


def test_two_phase_union_and_overlap():
    rep = parse([
        ev("all-gather.1", 0, 10, hlo_op="all-gather.1"),
        ev("fusion.2", 5, 10, hlo_op="fusion.2"),
    ])
    d = rep["devices"]["1"]
    assert d["exchange_us"] == 10 and d["compute_us"] == 10
    assert d["overlap_us"] == 5 and d["union_us"] == 15
    assert d["realized_hidden_frac"] == 0.5
    assert rep["realized_hidden_frac"] == 0.5
    assert rep["tags"] == ["lux.test.compute", "lux.test.exchange"]


def test_nested_regions_do_not_double_count():
    # Nested/overlapping events of ONE phase must union, not sum: three
    # nested exchange ops spanning [0, 10] are 10us of exchange.
    rep = parse([
        ev("all-gather.1", 0, 10, hlo_op="all-gather.1"),
        ev("all-gather.1", 2, 4, hlo_op="all-gather.1"),
        ev("all-gather.1", 3, 2, hlo_op="all-gather.1"),
    ])
    assert rep["devices"]["1"]["exchange_us"] == 10


def test_zero_length_events_are_harmless():
    rep = parse([
        ev("all-gather.1", 5, 0, hlo_op="all-gather.1"),
        ev("fusion.2", 0, 4, hlo_op="fusion.2"),
    ])
    d = rep["devices"]["1"]
    assert d["exchange_us"] == 0 and d["compute_us"] == 4
    assert d["realized_hidden_frac"] is None  # no exchange time to hide


def test_out_of_order_timestamps():
    # Chrome traces carry no ordering guarantee; the math must not.
    rep = parse([
        ev("fusion.2", 100, 10, hlo_op="fusion.2"),
        ev("all-gather.1", 0, 10, hlo_op="all-gather.1"),
        ev("fusion.2", 4, 2, hlo_op="fusion.2"),
    ])
    d = rep["devices"]["1"]
    assert d["exchange_us"] == 10 and d["compute_us"] == 12
    assert d["overlap_us"] == 2
    assert d["span_us"] == 110


def test_multi_device_streams_stay_separate():
    rep = parse([
        ev("all-gather.1", 0, 10, pid=1, hlo_op="all-gather.1"),
        ev("fusion.2", 0, 10, pid=2, hlo_op="fusion.2"),
        {"ph": "M", "name": "process_name", "pid": 2,
         "args": {"name": "/device:TPU:1"}},
    ])
    assert set(rep["devices"]) == {"1", "2"}
    # Device 1 has exchange only, device 2 compute only — concurrent
    # streams on DIFFERENT devices are not overlap on either.
    assert rep["devices"]["1"]["overlap_us"] == 0
    assert rep["devices"]["2"]["overlap_us"] == 0
    assert rep["devices"]["2"]["device"] == "/device:TPU:1"
    assert rep["realized_hidden_frac"] == 0.0


def test_idle_counts_gaps_before_first_and_after_last_op():
    # Capture window 0..1000 us; the device is busy 300..400 and
    # 500..600, so idle is 0.8 of the window (the first-op-to-last-op
    # span would read 1/3).
    events = [
        ev(prof.WINDOW, 0, 1000),
        ev("fusion.2", 300, 100, hlo_op="fusion.2"),
        ev("fusion.2", 500, 100, hlo_op="fusion.2"),
    ]
    d = parse(events)["devices"]["1"]
    assert d["window_us"] == 1000 and d["span_us"] == 300
    assert d["idle_frac"] == pytest.approx(0.8)
    # Without the window span the trace's whole extent is the window:
    # a host span before the first op counts as captured idle time.
    events[0] = ev("lux.serve.engine", 0, 700)
    d = parse(events)["devices"]["1"]
    assert d["window_us"] == 700
    assert d["idle_frac"] == pytest.approx(1 - 200 / 700)


def test_missing_dur_counts_as_instant():
    d = parse([
        ev("all-gather.1", 0, 10, hlo_op="all-gather.1"),
        {"ph": "X", "name": "fusion.2", "ts": 3, "pid": 1, "tid": 1,
         "args": {"hlo_op": "fusion.2", "hlo_module": "jit_step"}},
    ])["devices"]["1"]
    assert d["compute_us"] == 0 and d["exchange_us"] == 10


def test_non_numeric_ts_is_loud():
    with pytest.raises(prof.ProfileParseError, match="non-numeric"):
        parse([ev("all-gather.1", "soon", 10, hlo_op="all-gather.1")])


def test_non_object_event_is_loud():
    with pytest.raises(prof.ProfileParseError, match="non-object"):
        parse(["not-an-event"])


def test_host_regions_never_join_device_unions():
    # A host TraceAnnotation span covering the whole window must not
    # manufacture overlap (async dispatch!): device overlap stays 0.
    rep = parse([
        ev("lux.serve.engine", 0, 100),           # host span, no hlo_op
        ev("all-gather.1", 0, 10, hlo_op="all-gather.1"),
        ev("fusion.2", 20, 10, hlo_op="fusion.2"),
    ])
    assert rep["devices"]["1"]["overlap_us"] == 0
    assert rep["host_regions"]["lux.serve.engine"]["count"] == 1
    assert "lux.serve.engine" in rep["tags"]


def test_non_lux_host_spans_ignored():
    rep = parse([ev("SomeFrameworkSpan", 0, 50)])
    assert rep["host_regions"] == {} and rep["devices"] == {}


def test_unknown_ops_count_busy_not_phase():
    d = parse([ev("copy.3", 0, 10, hlo_op="copy.3")])["devices"]["1"]
    assert d["busy_us"] == 10
    assert d["exchange_us"] == 0 and d["compute_us"] == 0


def test_ambiguous_op_only_fallback_declines():
    maps = [
        {"module": "a", "ops": {"op.1": "lux.a.exchange"}},
        {"module": "b", "ops": {"op.1": "lux.b.compute"}},
    ]
    rep = parse([ev("op.1", 0, 10, hlo_op="op.1", module="c")],
                op_maps=maps)
    d = rep["devices"]["1"]
    # Module "c" matches neither map and the op name is ambiguous
    # across them -> unclassified, never guessed.
    assert d["exchange_us"] == 0 and d["compute_us"] == 0


def test_gzip_truncated_artifact_is_loud(tmp_path):
    whole = gzip.compress(json.dumps(
        {"traceEvents": [ev("fusion.2", 0, 10, hlo_op="fusion.2")] * 100}
    ).encode())
    p = tmp_path / "t.trace.json.gz"
    p.write_bytes(whole[:len(whole) // 2])
    with pytest.raises(prof.ProfileParseError):
        prof.parse(str(p))


def test_bare_event_list_and_missing_file(tmp_path):
    p = tmp_path / "bare.trace.json"
    p.write_text(json.dumps([ev("fusion.2", 0, 4, hlo_op="fusion.2")]))
    assert prof.parse(str(p), op_maps=[OPS])["devices"]["1"][
        "compute_us"] == 4
    with pytest.raises(prof.ProfileParseError):
        prof.find_trace_artifact(str(tmp_path))  # no .gz artifact


def test_validate_rejects_broken_invariants():
    rep = parse([ev("all-gather.1", 0, 10, hlo_op="all-gather.1")])
    bad = json.loads(json.dumps(rep))
    bad["devices"]["1"]["union_us"] = 3.0     # < max phase
    with pytest.raises(prof.ProfileParseError, match="union"):
        prof.validate(bad)
    worse = json.loads(json.dumps(rep))
    worse["realized_hidden_frac"] = 1.5
    with pytest.raises(prof.ProfileParseError, match="outside"):
        prof.validate(worse)


def test_steps_cross_check_blocks():
    rep = parse(
        [ev("fusion.2", 0, 2_000_000, hlo_op="fusion.2")],
        steps=4, iterlog_summary={"num_iters": 4, "execute_s": 2.0})
    st = rep["steps"]
    assert st["captured"] == 4
    assert st["steps_per_s"] == pytest.approx(2.0)
    assert st["iterlog"]["steps_per_s"] == pytest.approx(2.0)


# -- region-name discipline at runtime -------------------------------------


def test_region_rejects_bad_names():
    for bad in ("pull.exchange", "lux.Pull", "lux.", "LUX.x", "lux x"):
        with pytest.raises(ValueError):
            prof.region(bad)
    prof.region("lux.pull_sharded.exchange")   # must not raise


def test_op_map_from_hlo():
    hlo = """HloModule jit_step, entry_computation_layout={()->f32[]}
  %all-gather.1 = f32[8]{0} all-gather(x), metadata={op_name="jit(step)/lux.pull_sharded.exchange/all_gather"}
  %fusion.2 = f32[8]{0} fusion(y), metadata={op_name="jit(step)/outer/lux.pull_sharded.compute/mul"}
  %copy.3 = f32[8]{0} copy(z), metadata={op_name="jit(step)/plain/mul"}
"""
    m = prof.op_map_from_hlo(hlo)
    assert m["module"] == "jit_step"
    assert m["ops"] == {
        "all-gather.1": "lux.pull_sharded.exchange",
        "fusion.2": "lux.pull_sharded.compute",
    }


# -- device-profile registry ------------------------------------------------


def test_device_profile_rows_and_overrides(monkeypatch):
    v5e = report.device_profile("TPU v5e")
    assert v5e["hbm_peak_gbps"] == 819.0 and v5e["known"]
    v5p = report.device_profile("TPU v5p")
    assert v5p["hbm_peak_gbps"] > v5e["hbm_peak_gbps"]
    cpu = report.device_profile("cpu")
    assert cpu["known"] and cpu["hbm_peak_gbps"] is None
    unk = report.device_profile("TPU v9")
    assert not unk["known"] and unk["hbm_peak_gbps"] is None
    monkeypatch.setenv("LUX_HBM_PEAK_GBPS", "1234.5")
    assert report.device_profile("TPU v9")["hbm_peak_gbps"] == 1234.5


def test_roofline_unknown_kind_yields_none_frac(monkeypatch):
    monkeypatch.setattr(report, "_kind_cache", ["TPU v99"])
    summary = {"num_iters": 10, "execute_s": 1.0,
               "hbm_bytes_per_iter": 10**9,
               "exchange_bytes_per_iter": 10**8, "parts": 2}
    roof = report.roofline(summary)
    assert roof["device_kind"] == "TPU v99"
    assert roof["hbm_gbps"] == pytest.approx(10.0)
    assert roof["hbm_frac"] is None and roof["ici_frac"] is None
    # The n/a rendering must survive the report table.
    table = report._format_table({
        "engine": "pull", "program": "PageRank", "nv": 1, "ne": 1,
        "num_iters": 10, "compile_s": 0.0, "execute_s": 1.0,
        "gteps": 0.1, "roofline": roof})
    assert "n/a" in table


# -- bench-gate device_kind context ----------------------------------------


def _gate():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import bench_gate
    return bench_gate


def test_gate_fails_closed_on_foreign_chip():
    bg = _gate()
    cur = {"mode": "fast", "scale": 10, "ef": 8, "layout": "flat",
           "platform": "tpu", "exchange": "full",
           "device_kind": "TPU v5e"}
    ok, reason = bg.comparable(cur, dict(cur, device_kind="TPU v5p"))
    assert not ok and "device_kind" in reason
    ok, _ = bg.comparable(cur, dict(cur))
    assert ok
    # Baseline predating the device_kind key: fail closed on TPU...
    legacy = dict(cur)
    legacy.pop("device_kind")
    ok, reason = bg.comparable(cur, legacy)
    assert not ok and "device_kind" in reason
    # ...but cpu-vs-cpu stays comparable (the kind IS the platform).
    cur_cpu = dict(cur, platform="cpu", device_kind="cpu")
    legacy_cpu = dict(cur_cpu)
    legacy_cpu.pop("device_kind")
    ok, reason = bg.comparable(cur_cpu, legacy_cpu)
    assert ok, reason

"""Telemetry subsystem tests: metrics registry semantics, trace-file
round-trips, and end-to-end iteration logs from real executor runs."""

import json

import numpy as np
import pytest

from lux_tpu import obs
from lux_tpu.engine.pull import PullExecutor
from lux_tpu.engine.push import PushExecutor
from lux_tpu.graph import generate
from lux_tpu.models.components import ConnectedComponents
from lux_tpu.models.pagerank import PageRank
from lux_tpu.obs import metrics, report, trace


@pytest.fixture(autouse=True)
def clean_telemetry(monkeypatch):
    """Every test starts with telemetry off and an empty registry; env
    mutations inside the test are undone and re-read at teardown."""
    monkeypatch.delenv("LUX_METRICS", raising=False)
    monkeypatch.delenv("LUX_TRACE", raising=False)
    trace.reconfigure()
    metrics.reset()
    yield
    monkeypatch.undo()
    trace.reconfigure()
    metrics.reset()


# -- metrics registry -----------------------------------------------------


def test_counter_semantics():
    c = metrics.counter("t_iters", {"engine": "pull"})
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_semantics():
    g = metrics.gauge("t_bytes")
    g.set(10)
    g.inc(5)
    g.dec(3)
    assert g.value == 12


def test_histogram_semantics():
    h = metrics.histogram("t_secs", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count == 3 and h.sum == pytest.approx(5.55)
    snap = h.snapshot()
    assert [b["count"] for b in snap["buckets"]] == [1, 1, 1]
    assert snap["buckets"][-1]["le"] == "+Inf"


def test_label_dedup_and_kind_conflict():
    a = metrics.counter("t_dedup", {"engine": "pull", "k": "1"})
    b = metrics.counter("t_dedup", {"k": "1", "engine": "pull"})
    assert a is b  # label order is irrelevant to identity
    c = metrics.counter("t_dedup", {"engine": "push"})
    assert c is not a
    with pytest.raises(TypeError):
        metrics.gauge("t_dedup", {"engine": "pull", "k": "1"})


def test_snapshot_json_roundtrip():
    metrics.counter("t_snap").inc(2)
    metrics.histogram("t_snap_h").observe(0.2)
    snap = json.loads(json.dumps(metrics.snapshot()))
    names = [m["name"] for m in snap]
    assert names == sorted(names) and "t_snap" in names


# -- trace writer ---------------------------------------------------------


def test_trace_span_pairs(tmp_path, monkeypatch):
    path = str(tmp_path / "trace.jsonl")
    monkeypatch.setenv("LUX_TRACE", path)
    trace.reconfigure()
    assert trace.enabled()
    with trace.span("outer", cat="test", detail=1):
        with trace.span("inner", cat="test"):
            pass
    trace.pair("retro", 1.0, 2.0, cat="test")
    trace.instant("mark", cat="test")
    monkeypatch.delenv("LUX_TRACE")
    trace.reconfigure()  # closes the writer

    events = [json.loads(line) for line in open(path)]
    assert all("ph" in e and "name" in e for e in events if e["ph"] != "M")
    b = [e for e in events if e["ph"] == "B"]
    e = [e for e in events if e["ph"] == "E"]
    assert len(b) == len(e) == 3
    # spans nest: inner's B after outer's B, E before outer's E
    by = {(ev["name"], ev["ph"]): ev["ts"] for ev in b + e}
    assert by[("outer", "B")] <= by[("inner", "B")]
    assert by[("inner", "E")] <= by[("outer", "E")]
    retro_b, retro_e = by[("retro", "B")], by[("retro", "E")]
    assert retro_e - retro_b == pytest.approx(1e6)  # 1 s in us


def test_trace_disabled_is_noop(tmp_path):
    assert not trace.enabled()
    with trace.span("nothing"):
        pass
    trace.begin("x")
    trace.end("x")  # must not raise with no writer


# -- gteps definition -----------------------------------------------------


def test_gteps_definition():
    assert obs.gteps(2_000_000_000, 5, 10.0) == pytest.approx(1.0)
    assert obs.gteps(100, 0, 1.0) == 0.0
    assert obs.gteps(100, 5, 0.0) == 0.0


# -- recorder + executors end to end --------------------------------------


def _last_run(path):
    return report.read_last(path)


def test_pull_run_iteration_log(tmp_path, monkeypatch):
    mpath = str(tmp_path / "m.jsonl")
    monkeypatch.setenv("LUX_METRICS", mpath)
    g = generate.rmat(8, 8, seed=1)
    ex = PullExecutor(g, PageRank())
    ex.warmup()
    ex.run(6, flush_every=0)
    run = _last_run(mpath)
    assert run["schema"] == "lux.run_telemetry.v1"
    assert run["engine"] == "pull" and run["program"] == "PageRank"
    assert run["num_iters"] == 6 and len(run["iterations"]) == 6
    cum = [r["t_cum_s"] for r in run["iterations"]]
    assert all(b >= a for a, b in zip(cum, cum[1:]))
    assert run["compile_s"] > 0  # warmup + fused-probe compile
    assert run["execute_s"] > 0
    assert run["gteps"] == pytest.approx(
        obs.gteps(run["ne"], run["num_iters"], run["execute_s"]))
    assert [m for m in run["metrics"] if m["name"] == "lux_iterations_total"]


def test_pull_run_pipelined_flush_windows(tmp_path, monkeypatch):
    mpath = str(tmp_path / "m.jsonl")
    monkeypatch.setenv("LUX_METRICS", mpath)
    g = generate.rmat(8, 8, seed=1)
    ex = PullExecutor(g, PageRank())
    ex.warmup()
    ex.run(7, flush_every=3)  # windows: 3 + 3 + 1
    run = _last_run(mpath)
    assert run["num_iters"] == 7 and len(run["iterations"]) == 7
    assert [r["flush_span"] for r in run["iterations"]] == \
        [1, 1, 1, 2, 2, 2, 3]
    assert [r["iter"] for r in run["iterations"]] == list(range(7))


def test_push_run_frontier_log(tmp_path, monkeypatch):
    mpath = str(tmp_path / "m.jsonl")
    monkeypatch.setenv("LUX_METRICS", mpath)
    g = generate.undirected(generate.rmat(8, 8, seed=3))
    ex = PushExecutor(g, ConnectedComponents())
    ex.warmup()
    state, iters = ex.run(max_iters=32)
    run = _last_run(mpath)
    assert run["engine"] == "push"
    assert run["num_iters"] == iters and len(run["iterations"]) == iters
    frontiers = [r["frontier"] for r in run["iterations"]]
    assert all(isinstance(f, int) and f >= 0 for f in frontiers)
    assert frontiers[-1] == 0  # fixpoint: final frontier is empty


def test_disabled_recorder_is_null():
    g = generate.rmat(6, 8, seed=1)
    rec = obs.recorder_for("pull", g)
    assert rec is obs.NULL_RECORDER and not rec.enabled
    # and a run with telemetry off writes nothing anywhere
    ex = PullExecutor(g, PageRank())
    out = ex.run(2, flush_every=0)
    assert out.shape == (g.nv,)


def test_recorder_runs_append_jsonl(tmp_path, monkeypatch):
    mpath = str(tmp_path / "m.jsonl")
    monkeypatch.setenv("LUX_METRICS", mpath)
    g = generate.rmat(6, 8, seed=1)
    ex = PullExecutor(g, PageRank())
    ex.run(2, flush_every=0)
    ex.run(3, flush_every=0)
    runs = [json.loads(line) for line in open(mpath)]
    assert [r["num_iters"] for r in runs] == [2, 3]


def test_exchange_bytes_sharded(tmp_path, monkeypatch):
    import jax

    from lux_tpu.engine.pull_sharded import ShardedPullExecutor
    from lux_tpu.parallel.mesh import make_mesh

    mpath = str(tmp_path / "m.jsonl")
    monkeypatch.setenv("LUX_METRICS", mpath)
    g = generate.rmat(8, 8, seed=1)
    ex = ShardedPullExecutor(g, PageRank(), mesh=make_mesh(2))
    ex.warmup()
    ex.run(3, flush_every=0)
    run = _last_run(mpath)
    assert run["engine"] == "pull_sharded"
    expected = 2 * 1 * ex.sg.max_nv * 4  # P(P-1) x shard floats
    assert run["exchange_bytes_per_iter"] == expected
    assert run["exchange_bytes_total"] == expected * 3


# -- satellites: Timer sync + logging reconfigure -------------------------


def test_timer_sync_blocks_async_result():
    import jax
    import jax.numpy as jnp

    from lux_tpu.utils.timing import Timer

    x = jnp.arange(1024.0)
    y = None
    with Timer(sync=lambda: y) as t:
        y = jax.jit(lambda v: v * 2)(x)
    assert t.elapsed >= 0 and float(y[0]) == 0.0


def test_timer_sync_callable_and_format(capsys):
    from lux_tpu.utils.timing import Timer

    done = []
    with Timer(sync=lambda: done.append(1)) as t:
        pass
    assert done == [1]  # the callable ran at exit
    t.print_elapsed()
    out = capsys.readouterr().out
    assert out.startswith("ELAPSED TIME = ") and out.endswith(" s\n")


def test_logging_reconfigure(monkeypatch):
    import logging as py_logging

    from lux_tpu.utils import logging as lux_logging

    lux_logging.get_logger("test")
    root = py_logging.getLogger("lux")
    monkeypatch.setenv("LUX_LOG", "DEBUG")
    lux_logging.reconfigure()
    assert root.level == py_logging.DEBUG
    monkeypatch.setenv("LUX_LOG", "WARNING")
    lux_logging.reconfigure()
    assert root.level == py_logging.WARNING
    # single handler no matter how often reconfigure runs
    lux_logging.reconfigure()
    assert len(root.handlers) == 1
    assert lux_logging.perf_logger().name == "lux.perf"


def test_report_read_last_empty(tmp_path):
    p = tmp_path / "empty.jsonl"
    p.write_text("\n")
    with pytest.raises(ValueError):
        report.read_last(str(p))

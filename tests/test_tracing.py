"""The program's spans and engine phases in the profiler's trace.

- every phase scope of the tiled, pull and push steps reaches the
  lowered HLO (a ``jax.named_scope`` is op metadata: the device trace
  names an op's phase through it);
- a served query's request spans land in a live ``jax.profiler``
  capture as ``lux.*`` host spans, on the device trace's clock, where
  ``perfbench/trace_reduce.py`` puts an idle gap down to them;
- the engine counters, the compile-seconds listener and the per-layer
  readers that read them;
- ``perfbench/scopes.py`` on a trace recorded on a TPU v5e.
"""

import json
import math
import os
import re
import shutil
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lux_tpu.analysis.sentinel import compile_phase
from lux_tpu.engine.pull import PullExecutor
from lux_tpu.engine.push import PushExecutor
from lux_tpu.engine.tiled import TiledPullExecutor
from lux_tpu.graph.generate import bipartite_ratings, rmat
from lux_tpu.models.colfilter import CollaborativeFiltering
from lux_tpu.models.pagerank import PageRank
from lux_tpu.models.sssp import SSSP
from lux_tpu.obs import metrics, spans
from lux_tpu.ops.segment import cumsum0
from perfbench import harness, scopes, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "perfbench", "tests", "data")


def counter(name, **labels):
    for m in metrics.snapshot():
        if m["name"] == name and m["labels"] == labels:
            return m["value"]
    return 0.0


# -- phase scopes in the lowered step ---------------------------------------


def _tiled():
    return TiledPullExecutor(rmat(9, seed=1), PageRank())


def _pull(edge_chunk):
    return lambda: PullExecutor(bipartite_ratings(200, 40, 2000, seed=5),
                                CollaborativeFiltering(),
                                edge_chunk=edge_chunk)


def _push():
    return PushExecutor(rmat(10, seed=2, weighted=True), SSSP())


PHASES = {
    "tiled": (_tiled, ["strip_scan", "strip_boundary", "tail_gather",
                       "tail_zstream", "tail_boundary", "apply"]),
    "pull-chunked": (_pull(128), ["gather", "reduce", "apply"]),
    "pull-flat": (_pull(0), ["gather", "reduce", "apply"]),
    "push": (_push, ["decide", "dense", "sparse", "update"]),
}


@pytest.mark.parametrize("engine", sorted(PHASES))
def test_step_hlo_carries_every_phase_scope(engine):
    build, phases = PHASES[engine]
    step = build().trace_step()
    text = step["fn"].lower(*step["args"]).as_text(debug_info=True)
    prefix = "lux." + engine.split("-")[0] + "."
    assert {prefix + p for p in phases} <= set(re.findall(
        r"lux\.[a-z0-9_.]+", text))


@pytest.mark.parametrize("shape,dtype", [((1000,), jnp.int32),
                                         ((1024, 128), jnp.float32)])
def test_cumsum0_is_jnp_cumsum(shape, dtype):
    x = jnp.asarray(np.random.default_rng(0).integers(0, 9, shape), dtype)
    np.testing.assert_array_equal(cumsum0(x), jnp.cumsum(x, axis=0))

    def ops(f):
        # opcode and type of every instruction: the same executable but
        # for instruction names and metadata
        text = jax.jit(f).lower(x).compile().as_text()
        return sorted(re.findall(r"= (\S+) ([a-z][\w-]*)\(", text))

    assert ops(cumsum0) == ops(lambda v: jnp.cumsum(v, axis=0))


# -- request spans in a live profiler capture --------------------------------


def test_served_query_spans_land_in_the_profiler_trace(tmp_path):
    from lux_tpu.serve.http import serve_in_thread
    from lux_tpu.serve.session import ServeConfig, Session

    session = Session(rmat(10, seed=3), ServeConfig(), warm=False)
    server, thread = serve_in_thread(session)
    url = f"http://127.0.0.1:{server.server_address[1]}/query"

    def query(root):
        req = urllib.request.Request(
            url, data=json.dumps({"app": "sssp", "start": root}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            return json.loads(r.read())

    try:
        query(1)                                  # build and compile
        trace_dir = str(tmp_path / "trace")
        jax.profiler.start_trace(trace_dir)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                query(2)
        finally:
            jax.profiler.stop_trace()
    finally:
        server.shutdown()
        thread.join(timeout=60)
        server.server_close()
        session.close()
    events = trace_reduce.load_xplane(trace_dir)
    w0, w1 = trace_reduce._window(events, trace_reduce.WINDOW)
    inside = {e["name"]: e for e in events
              if e["name"].startswith("lux.")
              and w0 <= e["start_ns"] <= e["start_ns"] + e["dur_ns"] <= w1}
    assert {"lux.http.request", "lux.serve.admit", "lux.serve.batch",
            "lux.serve.engine", "lux.engine.run", "lux.engine.init",
            "lux.push.chunk", "lux.push.readback", "lux.serve.host_values",
            "lux.serve.cache.put"} <= set(inside)
    # A device that waits while the host copies the answer out: the gap
    # is put down to the innermost program span, not the request.
    hv = inside["lux.serve.host_values"]
    dev = "/device:TPU:0"
    ops = [{"plane": dev, "line": "XLA Ops", "name": "%a = f32[] a()",
            "start_ns": w0, "dur_ns": hv["start_ns"] - w0},
           {"plane": dev, "line": "XLA Ops", "name": "%b = f32[] b()",
            "start_ns": hv["start_ns"] + hv["dur_ns"],
            "dur_ns": w1 - hv["start_ns"] - hv["dur_ns"]}]
    gaps = trace_reduce.reduce(events + ops)["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "lux.serve.host_values"


def test_open_trace_is_one_profiler_span_across_threads(tmp_path):
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        tid, finish = spans.open_trace()
        t = threading.Thread(target=lambda: (finish(), finish()))
        t.start()
        t.join()
    finally:
        jax.profiler.stop_trace()
    names = [e["name"] for e in trace_reduce.load_xplane(trace_dir)]
    assert names.count("lux.request") == 1
    shutil.rmtree(trace_dir)


def test_set_attrs_lands_on_the_innermost_span():
    records = []
    spans.add_sink(records.append)
    try:
        with spans.span("outer"):
            with spans.span("inner", app="t"):
                spans.set_attrs(iters=7)
        spans.set_attrs(ignored=1)            # outside any span: no-op
    finally:
        spans.remove_sink(records.append)
    by = {s["name"]: s for s in records[0]["spans"]}
    assert by["inner"]["attrs"] == {"app": "t", "iters": 7}
    assert "attrs" not in by["outer"]


# -- counters ---------------------------------------------------------------


def test_push_counters_equal_the_runs_iterations():
    ex = PushExecutor(rmat(12, seed=4, weighted=True), SSSP())
    ex.warmup()
    metrics.reset()
    _, total = ex.run(start=int(np.argmax(ex.graph.out_degrees)))
    dense = counter("lux_engine_iterations_total", engine="push",
                    branch="dense")
    sparse = counter("lux_engine_iterations_total", engine="push",
                     branch="sparse")
    assert sparse == ex.sparse_iters and dense + sparse == total
    assert dense > 0 and sparse > 0
    assert counter("lux_engine_chunks_total",
                   engine="push") >= math.ceil(total / 16)


def test_pull_engines_count_all_iterations():
    metrics.reset()
    _tiled().run(3)
    _pull(128)().run(2)
    assert counter("lux_engine_iterations_total", engine="tiled",
                   branch="all") == 3
    assert counter("lux_engine_iterations_total", engine="pull",
                   branch="all") == 2


def test_compile_seconds_counted_only_inside_a_phase():
    metrics.reset()
    x = jnp.arange(7.0)
    jax.jit(lambda v: v * 3 + 1)(x)                  # outside any phase
    assert counter("lux_xla_compile_seconds_total", phase="warmup") == 0
    with compile_phase("warmup"):
        jax.jit(lambda v: v * 5 - 2)(x)
    assert counter("lux_xla_compile_seconds_total", phase="warmup") > 0


# -- the per-layer readers ---------------------------------------------------


READERS = ("compile_s", "plan_s", "push_dense_iters_per_query")


@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_without_its_inputs(name):
    metrics.reset()
    assert harness.load_reader(name).read({}) is None


def test_readers_read_what_the_program_counted():
    metrics.reset()
    with compile_phase("warmup"):
        jax.jit(lambda v: v * 7)(jnp.arange(5.0))
    with spans.span("build.plan"):
        pass
    metrics.counter("lux_engine_iterations_total",
                    {"engine": "push", "branch": "dense"}).inc(6)
    metrics.counter("lux_serve_requests_total", {"app": "sssp"}).inc(3)
    read = {n: harness.load_reader(n).read({}) for n in READERS}
    assert read["compile_s"] > 0 and read["plan_s"] >= 0
    assert read["push_dense_iters_per_query"] == 2.0


# -- device time by scope ----------------------------------------------------


def test_scope_seconds_on_a_recorded_v5e_trace():
    events = scopes.load_trace_json(os.path.join(
        DATA, "trace_v5e_scopes.trace.json.gz"))
    busy = trace_reduce.reduce(events)["busy_s"]
    raw = scopes.scope_seconds(events, inherit=False)
    sec = scopes.scope_seconds(events)
    for got in (raw, sec):
        assert sum(got.values()) == pytest.approx(busy, rel=1e-9)
    for phases, engine in ((PHASES["tiled"][1], "tiled"),
                           (PHASES["pull-chunked"][1], "pull"),
                           (PHASES["push"][1], "push")):
        for p in phases:
            assert raw[f"lux.{engine}.{p}"] > 0, (engine, p)
            assert sec[f"lux.{engine}.{p}"] >= raw[f"lux.{engine}.{p}"]
    # the op text -> scope map gives each op the scope it carries
    smap = scopes.scope_map(events)
    again = scopes.with_scopes(
        [{k: v for k, v in e.items() if k != "scope"} for e in events], smap)
    assert scopes.scope_seconds(again) == pytest.approx(sec, abs=1e-4)


def test_scope_seconds_attributes_each_busy_instant_once():
    dev = "/device:TPU:0"

    def op(scope, start, dur):
        return {"plane": dev, "line": "XLA Ops", "name": "%x = f32[] x()",
                "start_ns": start, "dur_ns": dur, "scope": scope}

    events = [
        {"plane": "/host:CPU", "line": "python", "name": "perfbench.window",
         "start_ns": 100, "dur_ns": 1000},
        op(scopes.NO_SCOPE, 150, 600),       # a while loop ...
        op("lux.push.dense", 200, 300),      # ... around its body's ops
        op("lux.push.update", 500, 100),
        op("lux.tiled.apply", 1000, 400),    # clipped at the window's end
        op("lux.tiled.apply", 0, 50),        # before the window
    ]
    # the loop's own time: 50 ns before its first op, 150 after its last
    assert scopes.scope_seconds(events, inherit=False) == pytest.approx({
        scopes.NO_SCOPE: 200e-9, "lux.push.dense": 300e-9,
        "lux.push.update": 100e-9, "lux.tiled.apply": 100e-9})
    # ... the 150 ns go to the scope that ran last, update; nothing had
    # run before the first 50
    assert scopes.scope_seconds(events) == pytest.approx({
        scopes.NO_SCOPE: 50e-9, "lux.push.dense": 300e-9,
        "lux.push.update": 250e-9, "lux.tiled.apply": 100e-9})
    assert scopes.scope_of(
        "jit(_step)/lux.push.dense/while/body/lux.push.update/min:"
    ) == "lux.push.update"
    assert scopes.scope_of("reduce_window_sum:") == scopes.NO_SCOPE
    smap = scopes.scope_map([op("lux.a", 0, 1), op("lux.b", 2, 1)])
    assert smap == {"%x = f32[] x()": scopes.NO_SCOPE}   # two scopes

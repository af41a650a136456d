"""One closed-loop client against the HTTP front end.

The program's ``Session`` serves the generated graph through
``serve_in_thread`` on ``ServeConfig``'s defaults, in this process; the
harness is the client. It sends ``POST /query`` (the app, a root and
``targets`` seeded vertex ids), waits for the answer and sends the next,
until the window's time is up. With one client in flight every batch
holds one query, so the single-root engine answers. Roots are drawn from
the seed without replacement among vertices with out-edges (the Graph500
root rule), so the result cache never answers. The first
``warm_queries`` roots warm the engine before the window.

Traffic keys: ``app``, ``program`` (its name under ``perfbench/work`` and
``perfbench/reference``), ``targets``, ``warm_queries``.
"""

from __future__ import annotations

import importlib
import json
import math
import threading
import time
import urllib.error
import urllib.request

import numpy as np

from perfbench.drivers import Base, free_device, limit, lux_graph
from perfbench.harness import annotate, say
from perfbench.reference import dtype

TIMEOUT_S = 300.0


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile of ``values`` as one of the values (nearest rank)."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


class Driver(Base):
    def setup(self) -> None:
        from lux_tpu.obs import spans
        from lux_tpu.serve.http import serve_in_thread
        from lux_tpu.serve.session import ServeConfig, Session

        self._draw()
        self._engine_s = []
        self._lock = threading.Lock()
        self._spans = spans
        spans.add_sink(self._on_trace)
        with annotate("build"):
            t = time.perf_counter()
            self.session = Session(lux_graph(self.graph), ServeConfig(),
                                   warm=False)
            self.server, self.thread = serve_in_thread(self.session)
        say(f"perfbench: session up in {time.perf_counter() - t:.3f} s")
        self.url = (f"http://127.0.0.1:{self.server.server_address[1]}"
                    "/query")
        with annotate("warmup"):
            for _ in range(int(self.traffic["warm_queries"])):
                r = self._query(self._next())
                say(f"perfbench: warm query in {r['done'] - r['sent']:.3f} s")

    def _draw(self) -> None:
        """The roots in the seed's order; ``_next`` takes them in turn."""
        self.rng = np.random.default_rng(self.seed)
        has_out = np.flatnonzero(self.graph.out_degrees > 0)
        self.roots = has_out[self.rng.permutation(has_out.size)]
        self.sent = 0

    def _next(self) -> dict:
        root = self.roots[self.sent]
        self.sent += 1
        return {"app": self.traffic["app"], "start": int(root),
                "targets": [int(t) for t in self.rng.integers(
                    0, self.graph.nv, int(self.traffic["targets"]))]}

    def _on_trace(self, rec: dict) -> None:
        engine = sum(s["dur_s"] for s in rec.get("spans", ())
                     if s["name"] == "serve.engine")
        with self._lock:
            self._engine_s.append(engine)

    def _query(self, body: dict) -> dict:
        req = urllib.request.Request(
            self.url, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        out = {"body": body, "sent": time.perf_counter()}
        try:
            with annotate("http"):
                with urllib.request.urlopen(req, timeout=TIMEOUT_S) as r:
                    out["answer"] = json.loads(r.read())
        except (urllib.error.URLError, OSError, ValueError) as e:
            out["error"] = repr(e)
        out["done"] = time.perf_counter()
        return out

    def window(self, seconds: float) -> dict:
        with self._lock:
            self._engine_s.clear()
        self.results = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self.results.append(self._query(self._next()))
        ok = [r for r in self.results if "answer" in r]
        self.attempted = len(self.results)
        self.failed = self.attempted - len(ok)
        with self._lock:
            engine = sum(self._engine_s)
        self.layer["engine_s"] = engine
        self.layer["client_s"] = sum(r["done"] - r["sent"] for r in ok)
        say(f"perfbench: {len(ok)} of {self.attempted} queries answered, "
            f"engine {engine:.4f} s of client {self.layer['client_s']:.4f} s")
        if not ok:
            return {"queries_per_s": 0.0, "query_p95_s": TIMEOUT_S}
        return {
            "queries_per_s": len(ok) / (self.results[-1]["done"] - t0),
            "query_p95_s": nearest_rank([r["done"] - r["sent"] for r in ok],
                                        0.95),
        }

    def release(self) -> None:
        self.close()
        free_device()

    def close(self) -> None:
        if getattr(self, "server", None) is not None:
            self.server.shutdown()
            self.thread.join(timeout=60)
            self.server.server_close()
            self.session.close()
            self.server = None
        if getattr(self, "_spans", None) is not None:
            self._spans.remove_sink(self._on_trace)
            self._spans = None

    def load_control(self, seconds: float) -> None:
        """The answers of the queries a window sends, one a second, from
        the reference in the control's type, in the program's place
        (``perfbench/controls.py``)."""
        ref = importlib.import_module(
            f"perfbench.reference.{self.traffic['program']}")
        self._draw()
        for _ in range(int(self.traffic["warm_queries"])):
            self._next()
        n = max(1, math.ceil(seconds))
        bodies = [self._next() for _ in range(n)]
        self.results = []
        for i in range(0, n, ref.LANES):
            group = bodies[i:i + ref.LANES]
            dist = ref.answers(self.graph, [b["start"] for b in group],
                               dtype(ref.CONTROL_DTYPE))
            for j, b in enumerate(group):
                values = dist[np.asarray(b["targets"]), j].tolist()
                self.results.append({"body": b, "answer": {"values": values}})
        self.attempted, self.failed = n, 0

    def check(self) -> dict:
        """Every answered query's values against the reference, exactly;
        also the window's algorithmic traversal bytes."""
        ref = importlib.import_module(
            f"perfbench.reference.{self.traffic['program']}")
        from perfbench.work import module as work_module

        w = work_module(self.traffic["program"])
        g = self.graph
        ok = [r for r in self.results if "answer" in r]
        bad = 0
        self.work["traversal_bytes"] = 0
        for i in range(0, len(ok), ref.LANES):
            group = ok[i:i + ref.LANES]
            dist = ref.answers(g, [r["body"]["start"] for r in group])
            reached = ref.out_edges_reached(dist, g.out_degrees)
            for j, r in enumerate(group):
                t = np.asarray(r["body"]["targets"])
                bad += ref.compare(r["answer"]["values"], dist[t, j])
            self.work["traversal_bytes"] += sum(
                w.bytes_per_query(g.nv, int(e)) for e in reached)
        name = f"{self.traffic['program']}_{ref.CHECK}"
        return {name: (bad + self.failed, limit(name))}

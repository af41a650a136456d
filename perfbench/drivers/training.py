"""One training run of a pull program carried on through the window, as
``run_pull_app`` dispatches it: ``cli.make_executor``, then the
executor's ``run()``, which returns once the device is done. Each call
runs ``sync_every`` iterations. Set-up makes the first such call from
the program's initial state, exactly as the window makes the others, and
keeps the initial state and the state it returns for the check; the
window then continues from there.

Traffic keys: ``entry`` (``module:Class`` of the program), ``program``
(its name under ``perfbench/work`` and ``perfbench/reference``),
``sync_every``.
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from perfbench.drivers import Base, free_device, limit, lux_graph, program
from perfbench.harness import annotate, say
from perfbench.reference import dtype
from perfbench.work import module as work_module


class Driver(Base):
    def setup(self) -> None:
        from lux_tpu.models import cli

        self.prog = program(self.traffic["entry"])
        args = cli.build_parser(self.prog.name, push=False).parse_args(
            ["-file", "generated", "-ni", "1"])
        g = lux_graph(self.graph)
        with annotate("build"):
            t = time.perf_counter()
            self.ex = cli.make_executor(g, self.prog, args)
            self.layer["build_s"] = time.perf_counter() - t
        say(f"perfbench: built {type(self.ex).__name__} in "
            f"{self.layer['build_s']:.3f} s")
        with annotate("warmup"):
            t = time.perf_counter()
            self.ex.warmup()
            say(f"perfbench: warm-up (compile) in "
                f"{time.perf_counter() - t:.3f} s")
            vals = self.ex.init_values()
            self.states = [np.asarray(vals)]
            vals = self.ex.run(int(self.traffic["sync_every"]), vals=vals)
            self.states.append(np.asarray(vals))
        self.vals = vals

    def window(self, seconds: float) -> dict:
        k = int(self.traffic["sync_every"])
        iters = 0
        vals = self.vals
        t0 = time.perf_counter()
        while True:
            with annotate("run"):
                vals = self.ex.run(k, vals=vals)
            iters += k
            t = time.perf_counter()
            if t - t0 >= seconds:
                break
        self.vals = vals
        elapsed = t - t0
        w = work_module(self.traffic["program"])
        nv, ne = self.graph.nv, self.graph.ne
        self.attempted = iters
        self.work["edge_bytes"] = iters * w.bytes_per_iteration(nv, ne)
        say(f"perfbench: {iters} iterations in {elapsed:.4f} s")
        return {"gteps": iters * w.edges_per_iteration(nv, ne)
                / elapsed / 1e9}

    def release(self) -> None:
        self.ex = None
        self.vals = None
        free_device()

    def load_control(self, seconds: float) -> None:
        """The reference in the control's precision, in the program's
        place (``perfbench/controls.py``)."""
        ref = importlib.import_module(
            f"perfbench.reference.{self.traffic['program']}")
        want = ref.answers(self.graph, self.config,
                           int(self.traffic["sync_every"]),
                           dtype(ref.CONTROL_DTYPE))
        self.states = [want[0], want[-1]]

    def check(self) -> dict:
        """The state after set-up's ``run()`` call against the reference's
        after as many steps, by leaf (the two sides of a bipartite graph
        are leaves of their own)."""
        ref = importlib.import_module(
            f"perfbench.reference.{self.traffic['program']}")
        want = ref.answers(self.graph, self.config,
                           int(self.traffic["sync_every"]))
        (got0, got), (want0, want) = self.states, (want[0], want[-1])
        err = max(ref.compare(got[a:b], want[a:b], got0[a:b], want0[a:b])
                  for a, b in ref.leaves(self.config))
        name = f"{self.traffic['program']}_{ref.CHECK}"
        return {name: (err, limit(name))}

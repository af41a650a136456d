"""Closed loop of batch jobs, one at a time: each job runs
``iterations_per_job`` iterations of a pull program from the program's
own init, through ``cli.make_executor`` and the executor's ``run()``, as
``run_pull_app`` calls them; ``run()`` returns once the device is done,
so each job is synced before the next starts.

Traffic keys: ``entry`` (``module:Class`` of the program), ``program``
(its name under ``perfbench/work`` and ``perfbench/reference``),
``iterations_per_job``, ``layout`` (the CLI's ``-layout``),
``checked_jobs`` (how many of the window's jobs the check compares).
"""

from __future__ import annotations

import importlib
import os
import time

import numpy as np

from perfbench.drivers import Base, free_device, limit, lux_graph, program
from perfbench.harness import annotate, say
from perfbench.reference import dtype
from perfbench.work import module as work_module

# make_executor caches the host tiled plan next to the path it is given
# and ignores a save that fails. The directory below is never created, so
# no run writes its multi-GB plan, and no run can load a plan made for
# another seed's graph (a cached plan is matched by nv and ne only).
UNSAVED_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".bench_cache", "perfbench", "never-created")


class Driver(Base):
    def setup(self) -> None:
        from lux_tpu.models import cli

        self.prog = program(self.traffic["entry"])
        self.iters = int(self.traffic["iterations_per_job"])
        if os.path.isdir(UNSAVED_DIR):
            raise RuntimeError(f"{UNSAVED_DIR} must not exist")
        plan_path = os.path.join(UNSAVED_DIR, "plan.luxplan")
        args = cli.build_parser(self.prog.name, push=False).parse_args([
            "-file", os.path.join(UNSAVED_DIR, "graph.lux"),
            "-ni", str(self.iters), "-layout", self.traffic["layout"],
            "-plan-cache", plan_path])
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
            self._job()
        say(f"perfbench: warm-up (compile and one job) in "
            f"{time.perf_counter() - t:.3f} s")
        self.kept = []

    def _job(self):
        with annotate("init"):
            vals = self.ex.init_values()
        with annotate("run"):
            return self.ex.run(self.iters, vals=vals)

    def window(self, seconds: float) -> dict:
        rng = np.random.default_rng(self.seed)
        n_keep = max(1, int(self.traffic["checked_jobs"]) - 1)
        sample = []   # reservoir sample of the jobs before the last
        jobs = 0
        last = None
        t0 = time.perf_counter()
        while True:
            out = self._job()
            t = time.perf_counter()
            if last is not None:
                if len(sample) < n_keep:
                    sample.append(last)
                else:
                    j = int(rng.integers(0, jobs))
                    if j < n_keep:
                        sample[j] = last
            last = out
            jobs += 1
            if t - t0 >= seconds:
                break
        self.kept = sample + [last]
        elapsed = t - t0
        w = work_module(self.traffic["program"])
        nv, ne = self.graph.nv, self.graph.ne
        self.attempted = jobs
        self.work["edge_bytes"] = (jobs * self.iters
                                   * w.bytes_per_iteration(nv, ne))
        say(f"perfbench: {jobs} jobs of {self.iters} iterations in "
            f"{elapsed:.4f} s")
        return {"gteps": jobs * self.iters * w.edges_per_iteration(nv, ne)
                / elapsed / 1e9}

    def release(self) -> None:
        self.kept = [np.asarray(v) for v in self.kept]
        self.ex = None
        free_device()

    def load_control(self, seconds: float) -> None:
        """The reference in the control's precision, in the program's
        place (``perfbench/controls.py``)."""
        ref = importlib.import_module(
            f"perfbench.reference.{self.traffic['program']}")
        self.iters = int(self.traffic["iterations_per_job"])
        self.kept = [ref.answer(self.graph, self.iters,
                                dtype(ref.CONTROL_DTYPE))]

    def check(self) -> dict:
        ref = importlib.import_module(
            f"perfbench.reference.{self.traffic['program']}")
        want = ref.answer(self.graph, self.iters)
        err = max(ref.compare(v, want) for v in self.kept)
        name = f"{self.traffic['program']}_{ref.CHECK}"
        return {name: (err, limit(name))}

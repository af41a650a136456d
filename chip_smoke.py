#!/usr/bin/env python3
"""Smoke run of lux_tpu's main path on a TPU, in one process.

    python chip_smoke.py              # one chip: PageRank, SSSP, BFS, HTTP
    python chip_smoke.py --chips 4    # four chips: the -parts 4 engines only

The graph is the old headline one: R-MAT scale 22, edge factor 16,
seed 42 (4,194,304 vertices, 67,108,864 edges), generated here from the
seed into a directory this run creates and removes. Every phase goes
through the entry point a user runs and is checked against the host
oracle:

- (a) PageRank through ``cli.run_pull_app`` (``-layout auto`` picks the
  tiled hybrid), ``-ni 10 -check``;
- (b) SSSP from root 0 through ``cli.run_push_app`` to fixpoint, with
  ``-check`` and the saved values against ``reference_sssp``;
- (c) BFS through ``lux_tpu.models.bfs.main`` (GAS ``AdaptiveExecutor``);
- (d) the HTTP front end (``serve_in_thread``): concurrent ``POST
  /query`` for sssp and bfs from different roots against the oracle,
  and ``/statusz`` reporting the platform.

``--chips 4`` runs (a)-(c) with ``-parts 4`` (``ShardedTiledExecutor``,
``ShardedPushExecutor``, ``ShardedAdaptiveExecutor``) and checks that
every sharded operand has shards on 4 distinct devices.

Earlier lines report each phase; the last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
The script refuses to run (exit 2) when JAX's first device is not a
TPU, and fails when any phase does.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import os
import shutil
import sys
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.abspath(__file__))
WORKDIR = os.path.join(REPO, ".bench_cache", "chip_smoke")
SERVE_TARGETS = 2048   # vertices compared per served answer


def say(msg: str) -> None:
    print(msg, flush=True)


def require(ok, what) -> None:
    """A failed check fails the run (unlike ``assert``, also under -O)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


class _Tee(io.TextIOBase):
    def __init__(self, *streams):
        self.streams = streams

    def write(self, s):
        for st in self.streams:
            st.write(s)
        return len(s)

    def flush(self):
        for st in self.streams:
            st.flush()


def live_device_bytes() -> int:
    import jax

    return sum(a.nbytes for a in jax.live_arrays())


def release_device() -> int:
    """Drop what a finished phase left on the device; returns the bytes
    still live."""
    import jax

    gc.collect()
    jax.clear_caches()
    return live_device_bytes()


def check_sharded(ex, parts: int) -> int:
    """Assert the executor's mesh holds ``parts`` distinct devices and
    every parts-sharded operand has shards on all of them; returns how
    many sharded operands were checked."""
    import jax
    from jax.sharding import NamedSharding

    mesh_devs = list(ex.mesh.devices.flat)
    require(len(set(mesh_devs)) == parts == len(mesh_devs), mesh_devs)
    leaves = jax.tree_util.tree_leaves(
        [v for v in vars(ex).values()
         if isinstance(v, (jax.Array, dict, list, tuple))
         or hasattr(v, "__dataclass_fields__")])
    checked = 0
    for a in leaves:
        if not isinstance(a, jax.Array):
            continue
        sh = a.sharding
        if not isinstance(sh, NamedSharding) or sh.is_fully_replicated:
            continue
        devs = {s.device for s in a.addressable_shards}
        require(len(devs) == parts, (a.shape, sh, devs))
        checked += 1
    require(checked > 0, "a parts-sharded operand on the executor")
    return checked


def run_cli(name, entry, argv, workdir):
    """Run one app CLI entry point in-process. Returns (stdout text,
    telemetry record, executor)."""
    from lux_tpu import obs
    from lux_tpu.models import cli

    metrics_path = os.path.join(workdir, f"{name}.metrics.jsonl")
    built = []
    make_executor = cli.make_executor

    def capture(*a, **kw):
        ex = make_executor(*a, **kw)
        built.append(ex)
        return ex

    buf = io.StringIO()
    cli.make_executor = capture
    try:
        with contextlib.redirect_stdout(_Tee(sys.stdout, buf)):
            rc = entry(argv + ["-metrics", metrics_path])
    finally:
        cli.make_executor = make_executor
        os.environ.pop("LUX_METRICS", None)
        obs.reconfigure()
    out = buf.getvalue()
    require(rc == 0 and "[PASS]" in out,
            f"{name}: rc={rc}, [PASS] from -check")
    with open(metrics_path) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    rec = [r for r in recs if r.get("num_iters")][-1]
    return out, rec, built[0]


def report(name, ex, rec, check):
    say(f"phase {name}: engine={rec['engine']} "
        f"executor={type(ex).__name__} nv={rec['nv']} ne={rec['ne']} "
        f"compile_s={rec['compile_s']} run_s={rec['execute_s']} "
        f"iters={rec['num_iters']} check={check}")


def pick_roots(g, seed: int, n: int):
    """Root 0 plus ``n - 1`` seeded vertices that have out-edges."""
    import numpy as np

    rng = np.random.default_rng(seed)
    has_out = np.flatnonzero(g.out_degrees[1:] > 0) + 1
    return [0] + [int(v) for v in rng.choice(has_out, n - 1, replace=False)]


def phase_pagerank(gpath, workdir, parts):
    from lux_tpu.models import PageRank
    from lux_tpu.models.cli import run_pull_app
    from lux_tpu.models.pagerank import reference_pagerank

    # The plan lands next to the graph file, where the server finds it.
    argv = ["-file", gpath, "-ni", "10", "-layout", "auto", "-check",
            "-parts", str(parts)]
    _, rec, ex = run_cli(
        "pagerank", lambda a: run_pull_app(
            PageRank(), a, oracle=lambda g, ni: reference_pagerank(g, ni)),
        argv, workdir)
    want = "ShardedTiledExecutor" if parts > 1 else "TiledPullExecutor"
    require(type(ex).__name__ == want, type(ex).__name__)
    sharded = check_sharded(ex, parts) if parts > 1 else 0
    report("pagerank", ex, rec, "PASS (host oracle, rtol 1e-3)"
           + (f", {sharded} operands on {parts} devices" if sharded else ""))


def phase_rooted(name, entry, g, gpath, workdir, parts, root, want_cls):
    """SSSP / BFS to fixpoint from ``root``: the app's ``-check`` plus
    the saved values against the host oracle, exactly."""
    import numpy as np

    from lux_tpu.models.sssp import reference_sssp
    from lux_tpu.utils import checkpoint

    save = os.path.join(workdir, f"{name}.npz")
    argv = ["-file", gpath, "-start", str(root), "-check", "-save", save]
    if parts > 1:
        argv += ["-parts", str(parts)]
    _, rec, ex = run_cli(name, entry, argv, workdir)
    require(type(ex).__name__ == want_cls, type(ex).__name__)
    sharded = check_sharded(ex, parts) if parts > 1 else 0
    got, _, _ = checkpoint.load(save, g)
    want = reference_sssp(g, root)
    mism = int(np.count_nonzero(got != want))
    require(mism == 0, f"{name}: {mism} vertices differ from the host oracle")
    report(name, ex, rec, f"PASS (-check + exact vs host oracle, root "
           f"{root}, {int((want < g.nv).sum())} reached)"
           + (f", {sharded} operands on {parts} devices" if sharded else ""))


def phase_serve(g, gpath, roots, seed, platform):
    import numpy as np

    from lux_tpu.models.bfs import bfs_parents
    from lux_tpu.models.sssp import reference_sssp
    from lux_tpu.serve.http import serve_in_thread
    from lux_tpu.serve.session import ServeConfig, Session

    t0 = time.perf_counter()
    session = Session(gpath, ServeConfig(), warm=False)
    # Warm only the engines the queries route to. The server's own
    # warm-up builds every registry app, more than one chip's HBM budget
    # keeps resident at this scale, so a query could rebuild an evicted
    # engine; the pool counters below prove none is built or evicted.
    session._sssp_single()
    session._sssp_multi()
    session._gas_single("bfs")
    session._gas_multi("bfs")
    warm_s = time.perf_counter() - t0
    warm = session.pool.stats()
    server, thread = serve_in_thread(session)
    base = f"http://127.0.0.1:{server.server_address[1]}"

    def call(path, body=None):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            base + path, data=data,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=600) as r:
            return json.loads(r.read())

    rng = np.random.default_rng(seed)
    queries = [("sssp", roots[0]), ("sssp", roots[1]), ("bfs", roots[2])]
    try:
        bodies = []
        for app, root in queries:
            targets = np.unique(np.concatenate([
                [root], rng.integers(0, g.nv, SERVE_TARGETS)]))
            bodies.append({"app": app, "start": int(root),
                           "targets": [int(t) for t in targets]})
        t1 = time.perf_counter()
        with ThreadPoolExecutor(len(bodies)) as pool:
            answers = list(pool.map(lambda b: call("/query", b), bodies))
        query_s = time.perf_counter() - t1
        for (app, root), body, ans in zip(queries, bodies, answers):
            depth = reference_sssp(g, root)
            t = np.asarray(body["targets"])
            require(ans["values"] == depth[t].tolist(), (app, root))
            if app == "bfs":
                parent = bfs_parents(g, depth)
                require(ans["parent"] == parent[t].tolist(), (app, root))
            summ = call("/query", {"app": app, "start": int(root)})
            s = summ["summary"]
            require((s["min"], s["max"]) == (int(depth.min()),
                                             int(depth.max())), (app, s))
            require(s["mean"] == float(depth.astype(np.float64).mean()),
                    (app, s))
        status = call("/statusz")
        require(status["device"]["platform"] == platform, status["device"])
        require(status["counters"]["recompiles"] == 0, status["counters"])
        pool = session.pool.stats()
        for k in ("engines", "misses", "hbm_evictions", "retired"):
            require(pool[k] == warm[k], (k, warm, pool))
    finally:
        server.shutdown()
        thread.join(timeout=60)
        session.close()
    say(f"phase serve: engines={pool['engines']} misses={pool['misses']} "
        f"hits={pool['hits'] - warm['hits']} "
        f"hbm_evictions={pool['hbm_evictions']} "
        f"nv={g.nv} ne={g.ne} warm_s={warm_s} query_s={query_s} "
        f"queries={len(queries)} concurrent + {len(queries)} summary "
        f"check=PASS (values, bfs parents and summaries vs host oracle; "
        f"/statusz platform={status['device']['platform']} "
        f"kind={status['device']['kind']})")


def run(scale: int, ef: int, seed: int, parts: int, workdir: str,
        platform: str) -> None:
    """Every phase for ``parts`` chips on the current backend."""
    from lux_tpu.graph import generate, write_lux
    from lux_tpu.models.bfs import main as bfs_main
    from lux_tpu.models.cli import run_push_app
    from lux_tpu.models.sssp import SSSP

    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    try:
        t0 = time.perf_counter()
        g = generate.rmat(scale, ef, seed=seed)
        gpath = os.path.join(workdir, f"rmat{scale}_{ef}.lux")
        write_lux(gpath, g)
        say(f"graph: R-MAT scale={scale} ef={ef} seed={seed} nv={g.nv} "
            f"ne={g.ne} gen_s={time.perf_counter() - t0}")
        roots = pick_roots(g, seed, 3)
        phases = [
            lambda: phase_pagerank(gpath, workdir, parts),
            lambda: phase_rooted(
                "sssp", lambda a: run_push_app(SSSP(), a, True), g, gpath,
                workdir, parts, roots[0],
                "ShardedPushExecutor" if parts > 1 else "PushExecutor"),
            lambda: phase_rooted(
                "bfs", bfs_main, g, gpath, workdir, parts, roots[2],
                "ShardedAdaptiveExecutor" if parts > 1
                else "AdaptiveExecutor"),
        ]
        if parts == 1:
            phases.append(
                lambda: phase_serve(g, gpath, roots, seed, platform))
        for phase in phases:
            t = time.perf_counter()
            phase()
            left = release_device()
            say(f"  wall_s={time.perf_counter() - t} "
                f"device_bytes_live_after={left}")
            require(left < 256 << 20, f"{left} device bytes outlived a phase")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=int, default=22)
    ap.add_argument("--ef", type=int, default=16)
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is "
              f"{devs[0].platform}); refusing to run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)
    from lux_tpu.obs import report as obs_report
    from lux_tpu.utils.platform import enable_compile_cache

    prof = obs_report.device_profile()
    say(f"jax {jax.__version__} platform={devs[0].platform} "
        f"device_kind={devs[0].device_kind} count={len(devs)} "
        f"device_profile_known={prof['known']} "
        f"compile_cache={enable_compile_cache()}")
    t0 = time.perf_counter()
    run(args.scale, args.ef, args.seed, args.chips, WORKDIR, "tpu")
    say(f"total_s={time.perf_counter() - t0}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

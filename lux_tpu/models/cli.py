"""Shared CLI driver for the four applications.

Reproduces the reference CLI surface (README.md:41-54, parse_input_args in
each app driver): ``-file`` ``-ni`` ``-start`` ``-check`` ``-verbose``,
prints the memory advisory and ``ELAPSED TIME`` the same way
(pagerank/pagerank.cc:60-118). The ``-ll:gpu/-ll:fsize/-ll:zsize`` runtime
flags have no TPU meaning; their replacement is ``-parts N`` (how many mesh
devices to shard over; default 1 device) — the reference folds GPU and
node counts into a partition count the same way (pagerank.cc:51-53).

Additions over the reference: ``-gteps`` summary line, ``-save/-resume``
checkpointing, ``-profile DIR`` (jax.profiler trace).
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Optional

import numpy as np

from lux_tpu import obs
from lux_tpu.utils.logging import get_logger
from lux_tpu.utils.timing import Timer


def build_parser(name: str, push: bool) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog=name, prefix_chars="-")
    p.add_argument("-file", required=True, help="input .lux graph")
    if push:
        p.add_argument(
            "-ni", type=int, default=0,
            help="max iterations (0 = run to fixpoint)",
        )
    else:
        p.add_argument("-ni", type=int, required=True, help="iterations")
    p.add_argument("-start", type=int, default=0, help="SSSP root vertex")
    p.add_argument("-check", action="store_true")
    p.add_argument("-verbose", action="store_true")
    p.add_argument(
        "-parts", "-ng", "-ll:gpu", type=int, default=1, dest="parts",
        help="mesh devices to shard over (1 = single device); -ng and "
        "-ll:gpu are the reference's aliases for its GPU count "
        "(pagerank.cc:127, README.md:47)",
    )
    # Accepted for drop-in compatibility with the reference's documented
    # invocations (README.md:43-49); Legion memory sizing has no TPU
    # equivalent — XLA owns HBM, and the advisory prints what is needed.
    p.add_argument("-ll:fsize", type=int, dest="ll_fsize",
                   help=argparse.SUPPRESS)
    p.add_argument("-ll:zsize", type=int, dest="ll_zsize",
                   help=argparse.SUPPRESS)
    p.add_argument(
        "-strategy", choices=["rowptr", "segment"], default="rowptr",
        help="sum-combiner reduction strategy (flat pull apps)",
    )
    p.add_argument(
        "-layout", choices=["auto", "flat", "tiled"], default="auto",
        help="pull engine: 'tiled' = strip/lane-select hybrid (the fast "
        "path for SpMV-shaped programs like PageRank), 'flat' = plain "
        "gather engine, 'auto' = tiled when the program supports it",
    )
    p.add_argument(
        "-levels", default="8/2",
        help="tiled layout strip cascade, e.g. '8/2' or '32/8,8/3,2/2'",
    )
    p.add_argument(
        "-tile-mb", type=int, default=8192, dest="tile_mb",
        help="tiled layout strip memory budget (MB)",
    )
    p.add_argument(
        "-plan-cache", dest="plan_cache",
        help="hybrid plan cache path (default: next to the graph file)",
    )
    p.add_argument("-save", help="write checkpoint npz after the run")
    p.add_argument("-resume", help="resume vertex state from checkpoint npz")
    p.add_argument("-profile", help="capture a device-timeline trace to "
                   "DIR (obs/prof.py; parse with tools/prof_summary.py)")
    p.add_argument(
        "-metrics", "--metrics", dest="metrics",
        help="append the run's telemetry (per-iteration records, "
        "compile/execute split) as one JSON line to PATH "
        "(equivalent to LUX_METRICS=PATH)",
    )
    p.add_argument(
        "-trace", "--trace", dest="trace",
        help="stream Chrome trace_event JSON-lines to PATH for Perfetto "
        "(equivalent to LUX_TRACE=PATH)",
    )
    return p


def setup_telemetry(args):
    """Map the -metrics/-trace flags onto the LUX_* env vars the obs
    subsystem is gated by, then re-read them."""
    if getattr(args, "metrics", None):
        os.environ["LUX_METRICS"] = args.metrics
    if getattr(args, "trace", None):
        os.environ["LUX_TRACE"] = args.trace
    obs.reconfigure()


def load_graph(path: str, program, log):
    import jax

    from lux_tpu.native import io as native_io
    from lux_tpu.utils.platform import enable_compile_cache

    log.info("jax platform: %s (compile cache %s)",
             jax.devices()[0].platform, enable_compile_cache())
    with Timer() as t:
        g = native_io.read_lux(path)
    log.info("loaded %s: nv=%d ne=%d (%.2fs)", path, g.nv, g.ne, t.elapsed)
    return g


def memory_advisory(g, parts: int, value_bytes: int, push: bool):
    """The reference prints minimum FB/ZC sizes per GPU/node
    (pagerank.cc:60-85, sssp.cc:59-90); here: estimated HBM per device."""
    edge_bytes = 8 + (4 if g.weights is not None else 0)  # src idx + seg/ptr
    per_dev = (
        g.ne // max(parts, 1) * edge_bytes
        + g.nv // max(parts, 1) * (value_bytes * 2 + 8)
        + (g.nv * value_bytes * parts if parts > 1 else 0)  # gathered ghosts
    )
    print(
        f"memory advisory: ~{per_dev / 1e6:.0f} MB HBM per device "
        f"({parts} part{'s' if parts != 1 else ''})"
    )


def _parse_levels(spec: str):
    try:
        levels = tuple(
            tuple(int(v) for v in part.split("/"))
            for part in spec.split(",")
        )
        if not all(len(lv) == 2 for lv in levels):
            raise ValueError
        return levels
    except ValueError:
        raise SystemExit(
            f"error: -levels {spec!r} is malformed; expected "
            "'r/thr[,r/thr...]', e.g. '8/2' or '32/8,8/3,2/2'"
        )


def _tiled_plan(g, program, args, log):
    """Resolve the hybrid plan for a tiled run (cached next to the graph
    file, keyed by cascade + budget so different configs coexist)."""
    from lux_tpu.engine.tiled import get_cached_plan

    levels = _parse_levels(args.levels)
    budget = args.tile_mb << 20
    path = args.plan_cache or (
        args.file
        + ".plan_"
        + "_".join(f"{r}x{t}" for r, t in levels)
        + f"_{args.tile_mb}.luxplan"
    )
    with obs.spans.span("build.plan"), Timer() as t:
        plan = get_cached_plan(
            g, path, levels=levels, budget_bytes=budget, log=log.info
        )
    log.info(
        "hybrid plan: %d strips (%.2f GB), coverage=%.1f%% (%.1fs)",
        plan.num_strips, plan.strip_bytes / 1e9, plan.coverage * 100,
        t.elapsed,
    )
    return plan


def make_executor(g, program, args, log=None):
    """Pick the engine. Pull programs default to the tiled (strip/
    lane-select hybrid) executor when the program is SpMV-shaped — the
    reference likewise has exactly one entry point per app
    (pagerank.cc:32-119) with the fast kernel behind it; ``-layout flat``
    forces the plain gather engine."""
    if log is None:
        log = get_logger(program.name)
    from lux_tpu.engine.gas import AdaptiveExecutor, GasProgram

    if isinstance(program, GasProgram):
        # The adaptive executor owns its direction choice (LUX_GAS pins
        # it); the layout knob belongs to the legacy engines.
        if args.layout != "auto":
            raise SystemExit(
                f"error: -layout {args.layout} has no effect on "
                f"{program.name} (a GAS app); use LUX_GAS=pull|push|adaptive"
            )
        if args.parts > 1:
            from lux_tpu.engine.gas_sharded import ShardedAdaptiveExecutor
            from lux_tpu.parallel.mesh import make_mesh

            return ShardedAdaptiveExecutor(
                g, program, mesh=make_mesh(args.parts))
        return AdaptiveExecutor(g, program)
    is_push = hasattr(program, "init_frontier")
    use_tiled = False
    if is_push and args.layout != "auto":
        raise SystemExit(
            f"error: -layout {args.layout} has no effect on "
            f"{program.name} (a push-model app); drop the flag"
        )
    if not is_push:
        from lux_tpu.engine.tiled import spmv_capable

        if args.layout == "tiled":
            if not spmv_capable(program):
                raise SystemExit(
                    f"-layout tiled: {program.name} is not SpMV-shaped "
                    "(needs sum combiner + identity contribution)"
                )
            use_tiled = True
        elif args.layout == "auto":
            use_tiled = spmv_capable(program)

    if args.parts > 1:
        from lux_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(args.parts)
        if is_push:
            from lux_tpu.engine.push import ShardedPushExecutor

            return ShardedPushExecutor(g, program, mesh=mesh)
        if use_tiled:
            from lux_tpu.engine.tiled_sharded import ShardedTiledExecutor

            return ShardedTiledExecutor(
                g, program, mesh=mesh, plan=_tiled_plan(g, program, args, log)
            )
        from lux_tpu.engine.pull_sharded import ShardedPullExecutor

        return ShardedPullExecutor(
            g, program, mesh=mesh, sum_strategy=args.strategy
        )
    if is_push:
        from lux_tpu.engine.push import PushExecutor

        return PushExecutor(g, program)
    if use_tiled:
        from lux_tpu.engine.tiled import TiledPullExecutor

        return TiledPullExecutor(
            g, program, plan=_tiled_plan(g, program, args, log)
        )
    from lux_tpu.engine.pull import PullExecutor

    return PullExecutor(g, program, sum_strategy=args.strategy)


def _profiler(dirname: Optional[str]):
    """``-profile DIR`` capture window: obs/prof.py owns the arming
    semantics (nullcontext when unarmed, makedirs + jax.profiler.trace
    when armed), so the CLI, bench --profile, and POST /profilez all
    write identical artifacts."""
    from lux_tpu.obs import prof

    return prof.trace(dirname)


def final_values(ex, result) -> np.ndarray:
    if hasattr(ex, "gather_values"):
        return ex.gather_values(result)
    vals = result.values if hasattr(result, "values") else result
    return np.asarray(vals)


def print_gteps(g, iters: int, elapsed: float):
    if elapsed > 0 and iters > 0:
        # obs.gteps is THE definition (edges traversed / iteration time);
        # bench.py and every engine report through the same helper.
        print(
            f"GTEPS = {obs.gteps(g.ne, iters, elapsed):.4f} "
            f"({iters} iters x {g.ne} edges / {elapsed:.4f}s)"
        )


def run_pull_app(program, argv, oracle=None):
    """Driver for PageRank/CF. ``oracle(graph, ni) -> values`` enables
    ``-check`` (the reference has no pull-side checker; we add one)."""
    log = get_logger(program.name)
    args = build_parser(program.name, push=False).parse_args(argv)
    setup_telemetry(args)
    g = load_graph(args.file, program, log)
    if program.needs_weights and g.weights is None:
        print(f"error: {program.name} needs a weighted graph", file=sys.stderr)
        return 1
    # Advisory sizes use the LANE-PADDED width: K-vector executors store
    # and gather 128-lane-padded rows on device, so the unpadded size
    # would understate HBM by the pad factor (6.4x for K=20).
    from lux_tpu.engine.pull import lane_pad_width

    kreal, kpad = lane_pad_width(getattr(program, "value_shape", ()))
    value_bytes = int(np.dtype(np.float32).itemsize) * max(kpad or kreal, 1)
    memory_advisory(g, args.parts, value_bytes, push=False)
    ex = make_executor(g, program, args)

    vals = ex.init_values()
    start_iter = 0
    if args.resume:
        from lux_tpu.utils import checkpoint

        host_vals, start_iter, _ = checkpoint.load(args.resume, g)
        vals = _host_to_device(ex, host_vals)
        log.info("resumed at iteration %d", start_iter)
    remaining = max(args.ni - start_iter, 0)

    # Warm-up compile outside the timed region (the reference's CUDA
    # kernels are compiled at build time).
    ex.warmup()

    with _profiler(args.profile):
        if args.verbose:
            # Per-iteration timing (the reference's -verbose per-part
            # breakdown, sssp_gpu.cu:516-518). Disables pipelining: each
            # iteration is synced to be measurable; executors with a
            # phase_step additionally attribute the time to pipeline
            # phases (separately dispatched, so the sum runs slower than
            # the fused step).
            from lux_tpu.engine.pull import hard_sync

            has_phases = hasattr(ex, "phase_step")
            if has_phases and remaining:
                # Compile the phase jits outside the timed region (the
                # phase dispatches are separate executables from the
                # fused step that warmup() compiled).
                ex.phase_step(vals)
            # The verbose loop bypasses ex.run(), so it drives its own
            # recorder; every iteration is already host-synced here.
            rec = obs.recorder_for(obs.engine_label(ex), g, program)
            rec.start()
            if rec.enabled:
                rec.record_compile(obs.consume_compile_seconds(ex))
            with Timer() as t:
                for i in range(remaining):
                    if has_phases:
                        with Timer() as ti:
                            vals, ph = ex.phase_step(vals)
                        detail = " ".join(
                            f"{k} {v*1e6:.0f}us" for k, v in ph.items()
                        )
                        print(
                            f"iter {start_iter + i}: {detail} "
                            f"(total {ti.elapsed*1e3:.3f} ms)"
                        )
                    else:
                        with Timer() as ti:
                            vals = hard_sync(ex.step(vals))
                        print(
                            f"iter {start_iter + i}: {ti.elapsed*1e3:.3f} ms"
                        )
                    rec.flush(i + 1)
            rec.finish()
        else:
            with Timer() as t:
                vals = ex.run(remaining, vals=vals)
    t.print_elapsed()
    print_gteps(g, remaining, t.elapsed)

    host_vals = final_values(ex, vals)
    if args.save:
        from lux_tpu.utils import checkpoint

        checkpoint.save(args.save, g, host_vals, args.ni)
        log.info("checkpoint written to %s", args.save)
    if args.check:
        if oracle is None:
            print("[SKIP] no checker for this app")
        else:
            want = oracle(g, args.ni)
            ok = np.allclose(host_vals, want, rtol=1e-3, atol=1e-7)
            print(
                "[PASS] Check task passed!"
                if ok
                else "[FAIL] Check task failed!"
            )
            if not ok:
                return 1
    return 0


def _host_to_push_state(ex, host_vals, host_frontier):
    import jax
    import jax.numpy as jnp

    from lux_tpu.engine.push import PushState

    if hasattr(ex, "sg"):
        from lux_tpu.parallel.mesh import parts_sharding

        sh = parts_sharding(ex.mesh)
        return PushState(
            jax.device_put(jnp.asarray(ex.sg.to_padded(host_vals)), sh),
            jax.device_put(jnp.asarray(ex.sg.to_padded(host_frontier)), sh),
        )
    import jax.numpy as jnp

    return PushState(jnp.asarray(host_vals), jnp.asarray(host_frontier))


def _push_frontier_host(ex, state):
    import jax
    import numpy as np

    fr = np.asarray(jax.device_get(state.frontier))
    if hasattr(ex, "sg"):
        return ex.sg.from_padded(fr)
    return fr


def _host_to_device(ex, host_vals):
    import jax
    import jax.numpy as jnp

    if hasattr(ex, "host_to_device"):
        # One protocol: executors owning a custom device layout (padded
        # shard stacks, degree-sorted internal order, lane padding)
        # provide the converter themselves.
        return ex.host_to_device(host_vals)
    return jax.device_put(jnp.asarray(host_vals))


def _run_push_verbose(ex, state, max_iters, start_iter, init_kw):
    """Per-iteration `-verbose` loop for push apps, reproducing the
    reference's per-GPU breakdown (sssp/sssp_gpu.cu:516-518):

    - single device: `activeNodes, loadTime, compTime, updateTime` per
      iteration via the executor's separately-dispatched phase_step;
    - sharded: one `part p: activeNodes ... edges ...` line per part
      per iteration with the phase walls on each line. SPMD phases run
      in lockstep across the mesh, so the loadTime/compTime/updateTime
      walls are mesh-wide (unlike the reference's per-GPU kernels);
      per-shard skew shows in the activeNodes/edges counters.
    Disables chunked pipelining; timing is per-iteration synced."""
    import jax

    if state is None:
        state = ex.init_state(**init_kw)
    iters = 0
    # Compile outside the timed loop (warmup() only built the fused
    # chunk executable; the phase jits are separate executables). The
    # throwaway state absorbs any donation.
    ex.warmup_phases(ex.init_state(**init_kw))
    # The verbose loop bypasses ex.run(), so it drives its own recorder;
    # phase_step syncs every iteration.
    rec = obs.recorder_for(obs.engine_label(ex), ex.graph, ex.program)
    rec.start()
    if rec.enabled:
        rec.record_compile(obs.consume_compile_seconds(ex))
    with Timer() as t:
        while max_iters is None or iters < max_iters:
            state, cnt, ph = ex.phase_step(state)
            detail = (
                f"loadTime {ph['loadTime']*1e6:.0f}us "
                f"compTime {ph['compTime']*1e6:.0f}us "
                f"updateTime {ph['updateTime']*1e6:.0f}us"
            )
            for s in ph.get("shards", ()):
                print(
                    f"iter {start_iter + iters} part {s['part']}: "
                    f"activeNodes {s['activeNodes']} "
                    f"edges {s['edges']} {detail} [{ph['branch']}]"
                )
            print(
                f"iter {start_iter + iters}: activeNodes {cnt} "
                f"{detail} [{ph['branch']}]"
            )
            total = cnt
            iters += 1
            rec.flush(iters, frontier_sizes=[cnt])
            if total == 0:
                break
    rec.finish()
    return state, iters, t


def run_push_app(program, argv, supports_start: bool):
    from lux_tpu.engine.check import check as run_check

    log = get_logger(program.name)
    args = build_parser(program.name, push=True).parse_args(argv)
    setup_telemetry(args)
    g = load_graph(args.file, program, log)
    memory_advisory(g, args.parts, 4, push=True)
    ex = make_executor(g, program, args)
    init_kw = {"start": args.start} if supports_start else {}
    max_iters = args.ni if args.ni > 0 else None

    state = None
    start_iter = 0
    if args.resume:
        from lux_tpu.utils import checkpoint

        host_vals, start_iter, host_frontier = checkpoint.load(args.resume, g)
        if host_frontier is None:
            print(
                "error: push checkpoint has no frontier; cannot resume",
                file=sys.stderr,
            )
            return 1
        state = _host_to_push_state(ex, host_vals, host_frontier)
        log.info("resumed at iteration %d", start_iter)
        if max_iters is not None:
            max_iters = max(max_iters - start_iter, 0)

    # Warm-up (compile) outside the timed region.
    ex.warmup(**init_kw)

    with _profiler(args.profile):
        if args.verbose and hasattr(ex, "phase_step"):
            state, iters, t = _run_push_verbose(
                ex, state, max_iters, start_iter, init_kw
            )
        else:
            if args.verbose:
                log.info(
                    "per-phase -verbose breakdown is push-engine only; "
                    "running the fused loop (direction split lands in "
                    "telemetry/engobs)"
                )
            with Timer() as t:
                state, iters = ex.run(
                    max_iters=max_iters, state=state, **init_kw
                )
    t.print_elapsed()
    print(f"iterations = {iters}")
    print_gteps(g, iters, t.elapsed)

    host_vals = final_values(ex, state)
    if args.save:
        from lux_tpu.utils import checkpoint

        host_frontier = _push_frontier_host(ex, state)
        checkpoint.save(
            args.save, g, host_vals, start_iter + iters,
            frontier=host_frontier,
        )
        log.info("checkpoint written to %s", args.save)
    if args.check:
        if not run_check(g, host_vals, program):
            return 1
    return 0

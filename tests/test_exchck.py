"""luxlint exchange tier: the LUX401-403 plan verifier (exchck), the
LUX404-406 dataflow rules, artifact save/load round-trips, the registry
matrix gate, the serve-pool audit hook, the --exchange CLI, and the
span-hash --baseline ratchet.

Seeded-violation convention (tests/exch_fixtures/): each ``lux4NN_*.py``
module exposes ``PLANS`` or ``TRACES`` and must make
``luxlint --exchange`` exit 1 with exactly its own rule firing.
"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from lux_tpu.analysis import exchck, ir  # noqa: E402
from lux_tpu.engine.program import EdgeCtx  # noqa: E402
from lux_tpu.engine.pull_sharded import ShardedPullExecutor  # noqa: E402
from lux_tpu.graph import generate, partition  # noqa: E402
from lux_tpu.models.pagerank import PageRank  # noqa: E402
from lux_tpu.obs import engobs, metrics  # noqa: E402
from lux_tpu.ops.segment import segment_reduce  # noqa: E402
from lux_tpu.parallel.mesh import PARTS_AXIS, make_mesh  # noqa: E402
from lux_tpu.parallel.shard import ShardedGraph  # noqa: E402
from lux_tpu.serve.pool import EnginePool  # noqa: E402

TESTS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS)
LUXLINT = os.path.join(REPO, "tools", "luxlint.py")
EXCH_FIXTURES = os.path.join(TESTS, "exch_fixtures")


def _run_cli(*argv):
    return subprocess.run(
        [sys.executable, LUXLINT, *argv],
        capture_output=True, text=True, cwd=REPO,
    )


def _summary_line(stdout):
    lines = [l for l in stdout.splitlines() if l.startswith("LUXLINT ")]
    assert lines, stdout
    return json.loads(lines[-1][len("LUXLINT "):])


def _rules(result):
    return sorted({f.rule for f in result.findings})


def _hand_plan():
    """P=2, max_units=4, unit_rows=1, capacity=2: receiver 0 needs rows
    {1, 3} of sender 1, receiver 1 needs row {2} of sender 0."""
    return types.SimpleNamespace(
        num_parts=2, max_units=4, unit_rows=1, capacity=2,
        counts=np.array([[0, 2], [1, 0]], dtype=np.int64),
        send_units=np.array([[4, 4, 2, 4],
                             [1, 3, 4, 4]], dtype=np.int32),
        recv_pos=np.array([[8, 8, 5, 7],
                           [2, 8, 8, 8]], dtype=np.int32),
        profitable=True)


def _hand_view(**kw):
    kw.setdefault("remote_read_counts",
                  np.array([[0, 2], [1, 0]], dtype=np.int64))
    kw.setdefault("row_bytes", 8)
    kw.setdefault("declared_bytes_per_iter", 32)
    plan = kw.pop("plan", None) or _hand_plan()
    return exchck.plan_view(plan, **kw)


def _live_plan():
    g = generate.halo(8, 128, hubs=8)
    sg = ShardedGraph.build(g, 8)
    return sg, sg.exchange_plan()


# -- format mirror -------------------------------------------------------


def test_constants_mirror_partition():
    # exchck must stay loadable in a jax-free interpreter, so it mirrors
    # the artifact format instead of importing graph/partition.
    assert exchck.EXCH_ARRAYS == partition.EXCHANGE_PLAN_ARRAYS
    assert exchck.EXCH_FORMAT == partition.EXCHANGE_PLAN_FORMAT


# -- plan rules over hand-built views ------------------------------------


def test_hand_plan_is_clean():
    res = exchck.verify_exchange_plan(_hand_view(), "unit@clean")
    assert res.findings == [] and res.error is None


def test_structure_pad_zone_leak():
    plan = _hand_plan()
    plan.send_units[0, 3] = 1
    res = exchck.verify_exchange_plan(_hand_view(plan=plan), "unit@leak")
    assert _rules(res) == ["LUX401"]


def test_structure_diagonal_real_entry():
    plan = _hand_plan()
    plan.recv_pos[0, 0] = 3   # own-pair slot carries a real position
    res = exchck.verify_exchange_plan(_hand_view(plan=plan), "unit@diag")
    assert "LUX401" in _rules(res)


def test_structure_capacity_truncated():
    plan = _hand_plan()
    plan.counts[0, 1] = 3     # densest pair now needs 3 > capacity 2
    view = _hand_view(plan=plan, remote_read_counts=None)
    res = exchck.verify_exchange_plan(view, "unit@trunc")
    assert _rules(res) == ["LUX401"]
    assert "capacity" in res.findings[0].message


def test_coverage_misrouted_row():
    plan = _hand_plan()
    plan.recv_pos[0, 2] = 6   # sender 1 row 1 should land at 4 + 1 = 5
    res = exchck.verify_exchange_plan(_hand_view(plan=plan), "unit@misroute")
    assert _rules(res) == ["LUX402"]


def test_coverage_duplicate_send_row():
    plan = _hand_plan()
    plan.send_units[0 + 1, 0:2] = [1, 1]   # row 1 sent twice, row 3 never
    plan.recv_pos[0, 2:4] = [5, 5]
    res = exchck.verify_exchange_plan(_hand_view(plan=plan), "unit@dup")
    assert "LUX402" in _rules(res)


def test_coverage_conservation_against_remote_reads():
    view = _hand_view(
        remote_read_counts=np.array([[0, 2], [2, 0]], dtype=np.int64))
    res = exchck.verify_exchange_plan(view, "unit@conservation")
    assert _rules(res) == ["LUX402"]
    assert "remote-read index" in res.findings[0].message


def test_profitability_declared_drift():
    res = exchck.verify_exchange_plan(
        _hand_view(declared_bytes_per_iter=48), "unit@declared")
    assert _rules(res) == ["LUX403"]


def test_profitability_false_claim():
    plan = _hand_plan()
    plan.capacity = 4         # == max_units, yet still claims profitable
    plan.send_units = np.full((2, 8), 4, np.int32)
    plan.recv_pos = np.full((2, 8), 8, np.int32)
    plan.send_units[0, 4] = 2
    plan.send_units[1, 0:2] = [1, 3]
    plan.recv_pos[0, 4:6] = [5, 7]
    plan.recv_pos[1, 0] = 2
    view = exchck.plan_view(plan)
    res = exchck.verify_exchange_plan(view, "unit@claim")
    assert _rules(res) == ["LUX403"]
    assert "profitable" in res.findings[0].message


def test_profitability_ledger_drift():
    ledger = {"useful_rows": 3, "exchanged_rows": 4,
              "useful_bytes_per_iter": 999, "ratio": 0.75}
    res = exchck.verify_exchange_plan(
        _hand_view(ledger=ledger), "unit@ledger")
    assert _rules(res) == ["LUX403"]


# -- artifact round-trip -------------------------------------------------


def test_artifact_roundtrip_clean(tmp_path):
    sg, plan = _live_plan()
    rb = 8
    ledger = engobs.useful_exchange(
        sg, rb, exchanged_rows=plan.exchanged_units_per_iter)
    d = str(tmp_path / "xplan")
    partition.save_exchange_artifact(
        plan, d, remote_read_counts=sg.remote_read_counts(),
        row_bytes=rb, ledger=ledger)
    view = exchck.load_exchange_artifact(d)
    assert view.declared_bytes_per_iter == plan.exchange_bytes_per_iter(rb)
    res = exchck.verify_exchange_plan(view, d)
    assert res.findings == [] and res.error is None
    # The dir-level entry point agrees and a corrupted copy fails.
    report = exchck.verify_exchange_dirs([d])
    assert report.ok
    arr = np.load(os.path.join(d, "recv_pos.npy"))
    arr[0, -1] = 0
    np.save(os.path.join(d, "recv_pos.npy"), arr)
    report = exchck.verify_exchange_dirs([d])
    assert not report.ok


def test_artifact_unknown_format_rejected(tmp_path):
    _, plan = _live_plan()
    d = str(tmp_path / "xplan")
    partition.save_exchange_artifact(plan, d)
    meta = json.load(open(os.path.join(d, "meta.json")))
    meta["format"] = 99
    json.dump(meta, open(os.path.join(d, "meta.json"), "w"))
    with pytest.raises(ValueError, match="unknown format"):
        exchck.load_exchange_artifact(d)
    # Through the dir runner: an error result, not a crash.
    report = exchck.verify_exchange_dirs([d])
    assert not report.ok and report.results[0].error


# -- registry matrix gate ------------------------------------------------


def test_exchange_matrix_clean_and_fast():
    # The acceptance gate `make lint-exchange` runs: every full+compact
    # sharded target plus its live plan verifies clean, within the
    # PERF_NOTES.md tier budget.
    report = ir.run_exchange_matrix()
    assert report.ok, report.format_human()
    assert report.summary()["schema"] == "luxlint-exchange.v1"
    names = {r.path for r in report.results}
    # Both halves are present: dataflow targets and their plan twins.
    assert any(n.endswith("+compact") for n in names)
    assert any(n.endswith("/plan") for n in names)
    # Round 17 grew the matrix by the gas_sharded targets plus a third
    # (frontier) exchange mode for every frontier program; the PERF_NOTES.md
    # tier budget moved 2 s -> 4 s with it (~2.5 s measured). On jax
    # 0.9 the parent tree crashed 40 of its 63 targets before tracing
    # (jax.core.ClosedJaxpr moved) and still took 4.09-4.53 s here under
    # the tier-1 xdist command; with every gas_sharded target tracing
    # again it takes 4.64-5.71 s on the same host (PR 21), so 8 s.
    assert report.elapsed_s <= 8.0, f"tier budget blown: {report.elapsed_s}"


# -- the overlap proof catches the flipped body --------------------------


class _FlippedPull(ShardedPullExecutor):
    """The compact pull body with the overlap contract deliberately
    broken: the "local" branch gathers from the exchanged flat table, so
    both sides of the ownership merge depend on the collective."""

    def _comp_block(self, vals_blk, flat, dg):
        prog = self.program
        max_nv = self.sg.max_nv
        sidx = dg["src_pidx"][0]
        dst_vals = vals_blk[0][jnp.minimum(dg["dst_local"][0], max_nv - 1)]
        w = dg["weights"][0] if "weights" in dg else None

        def contrib_from(src_vals):
            return prog.edge_contrib(EdgeCtx(
                src_vals=src_vals, dst_vals=dst_vals, weights=w))

        own = jax.lax.axis_index(PARTS_AXIS)
        base = own * max_nv
        local = (sidx >= base) & (sidx < base + max_nv)
        c_local = contrib_from(flat[jnp.clip(sidx - base, 0, max_nv - 1)])
        c_remote = contrib_from(flat[sidx])
        mask = local.reshape(local.shape + (1,) * (c_local.ndim - 1))
        contrib = jnp.where(mask, c_local, c_remote)
        return segment_reduce(
            contrib, dg["dst_local"][0], num_segments=max_nv + 1,
            kind=prog.combiner)[:max_nv]


def test_flipped_compact_pull_trips_overlap_proof(monkeypatch):
    monkeypatch.setenv("LUX_EXCHANGE", "compact")
    g = generate.halo(8, 128, hubs=8)
    ex = _FlippedPull(g, PageRank(), mesh=make_mesh(8))
    assert ex.exchange_mode == "compact", "compact did not engage"
    t = ir.target_from_spec("flipped@pull_sharded+compact", ex.trace_step())
    res = ir.check_target(t, [ir.OverlapProof()])
    assert _rules(res) == ["LUX404"]
    assert "every data side" in res.findings[0].message
    # The unmodified executor proves clean under the identical setup.
    ok = ShardedPullExecutor(g, PageRank(), mesh=make_mesh(8))
    res = ir.check_target(
        ir.target_from_spec("stock@pull_sharded+compact", ok.trace_step()),
        [ir.OverlapProof()])
    assert res.findings == []


# -- seeded fixtures through the CLI -------------------------------------


@pytest.mark.parametrize("rule,stem", [
    ("LUX401", "lux401_structure"),
    ("LUX402", "lux402_coverage"),
    ("LUX403", "lux403_profitability"),
    ("LUX404", "lux404_overlap"),
    ("LUX405", "lux405_sentinel"),
    ("LUX406", "lux406_bytes"),
    ("LUX407", "lux407_frontier"),
])
def test_cli_fixture_fails_with_exactly_its_rule(rule, stem):
    proc = _run_cli("--exchange", os.path.join(EXCH_FIXTURES, stem + ".py"))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    summary = _summary_line(proc.stdout)
    assert summary["schema"] == "luxlint-exchange.v1"
    assert list(summary["by_rule"]) == [rule], summary


def test_cli_select_filters_exchange_rules():
    fix = os.path.join(EXCH_FIXTURES, "lux401_structure.py")
    proc = _run_cli("--exchange", fix, "--select", "LUX402")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert _summary_line(proc.stdout)["findings"] == 0


def test_cli_rejects_mixed_tiers():
    proc = _run_cli("--exchange", "--ir")
    assert proc.returncode == 2
    assert "separate" in proc.stderr


def test_cli_path_without_plans_or_traces_errors(tmp_path):
    p = tmp_path / "empty_fixture.py"
    p.write_text("X = 1\n")
    proc = _run_cli("--exchange", str(p))
    assert proc.returncode == 1
    assert "neither TRACES nor PLANS" in proc.stdout


# -- serve-pool audit hook -----------------------------------------------


def _corrupt_engine():
    plan = _hand_plan()
    plan.recv_pos[0, 2] = 6
    return types.SimpleNamespace(_xplan=plan)


def test_pool_audit_flags_corrupt_plan(capsys):
    metrics.reset()
    pool = EnginePool("test-exch")
    ex = pool.get("k1", _corrupt_engine)
    assert ex is not None
    assert pool.stats()["exch_findings"] == 1
    assert "LUX402" in capsys.readouterr().out


def test_pool_audit_clean_live_engine(monkeypatch):
    monkeypatch.setenv("LUX_EXCHANGE", "compact")
    metrics.reset()
    g = generate.halo(8, 128, hubs=8)
    pool = EnginePool("test-exch")
    ex = pool.get(
        "k2", lambda: ShardedPullExecutor(g, PageRank(), mesh=make_mesh(8)))
    assert ex._xplan is not None
    assert pool.stats()["exch_findings"] == 0


def test_pool_audit_disabled_by_flag(monkeypatch):
    monkeypatch.setenv("LUX_EXCH_POOL_AUDIT", "0")
    metrics.reset()
    pool = EnginePool("test-exch")
    pool.get("k3", _corrupt_engine)
    assert pool.stats()["exch_findings"] == 0


def test_audit_exchange_survives_garbage():
    ex = types.SimpleNamespace(_xplan=types.SimpleNamespace(garbage=True))
    findings = exchck.audit_exchange(ex, "pool@garbage")
    assert findings and findings[0].rule == "LUX401"
    assert "audit crashed" in findings[0].message


# -- span-hash baseline ratchet ------------------------------------------


def test_baseline_survives_line_shift(tmp_path):
    bad = tmp_path / "engine" / "run_bad.py"
    bad.parent.mkdir()
    bad.write_text(
        "def run(step, vals, n):\n"
        "    for _ in range(n):\n"
        "        vals = step(vals)\n"
        "        done = vals.item()\n"
        "    return vals, done\n"
    )
    base = str(tmp_path / "baseline.json")
    proc = _run_cli(str(tmp_path / "engine"), "--baseline", base)
    assert proc.returncode == 0 and "baseline written" in proc.stdout
    # Shift the finding two lines down: the span-hash key is untouched,
    # so the ratchet still masks it (a line-number key would re-fire).
    bad.write_text(
        "# a comment\n"
        "# another comment\n" + bad.read_text())
    proc = _run_cli(str(tmp_path / "engine"), "--baseline", base)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 new" in proc.stdout
    # Rewriting the flagged line itself re-opens the finding.
    bad.write_text(bad.read_text().replace(
        "done = vals.item()", "done2 = vals.item()"))
    proc = _run_cli(str(tmp_path / "engine"), "--baseline", base)
    assert proc.returncode == 1
    assert "[new]" in proc.stdout


def test_baseline_ratchets_exchange_tier(tmp_path):
    fix = os.path.join(EXCH_FIXTURES, "lux403_profitability.py")
    base = str(tmp_path / "exch_baseline.json")
    p1 = _run_cli("--exchange", fix, "--baseline", base)
    assert p1.returncode == 0 and "baseline written" in p1.stdout
    keys = json.load(open(base))["keys"]
    assert keys and keys[0].startswith("LUX403")
    p2 = _run_cli("--exchange", fix, "--baseline", base)
    assert p2.returncode == 0 and "0 new" in p2.stdout

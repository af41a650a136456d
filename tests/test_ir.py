"""luxlint-IR: the jaxpr-tier rules (LUX101-105), the registry trace
matrix, the serve-pool donation-audit hook, and the CLI tiers (--ir /
--changed / --baseline).

Seeded-violation convention (tests/ir_fixtures/): each ``lux1NN_*.py``
module exposes ``TRACES`` and must make ``luxlint --ir`` exit 1 with
exactly its own rule firing.
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from lux_tpu.analysis import ir  # noqa: E402
from lux_tpu.models import ENGINE_KINDS  # noqa: E402

TESTS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS)
LUXLINT = os.path.join(REPO, "tools", "luxlint.py")
IR_FIXTURES = os.path.join(TESTS, "ir_fixtures")


def _run_cli(*argv):
    return subprocess.run(
        [sys.executable, LUXLINT, *argv],
        capture_output=True, text=True, cwd=REPO,
    )


def _summary_line(stdout):
    lines = [l for l in stdout.splitlines() if l.startswith("LUXLINT ")]
    assert lines, stdout
    return json.loads(lines[-1][len("LUXLINT "):])


def _rules(report):
    return sorted({f.rule for r in report.results for f in r.findings})


def _spec_target(call=None, **spec):
    if call is not None:
        spec.setdefault("call", call)
    spec.setdefault("args", (jnp.zeros(64, jnp.float32),))
    return ir.target_from_spec(spec.pop("name", "unit@test"), spec)


# -- IR rule units ------------------------------------------------------


def _matrix_names():
    """Expected registry-matrix target names: every program x capable
    executor, plus a ``+compact`` variant per sharded kind (the compact
    fixture graphs are chosen so the plan always engages — a fallback
    would shrink collective-audit coverage and fail here), plus a
    ``+frontier`` variant for every frontier program on the adaptive
    sharded GAS engine (frontier-less programs downgrade to compact by
    design and carry no extra target)."""
    from lux_tpu.engine.gas import as_gas
    from lux_tpu.models import get_program

    want = {f"{p}@{k}" for p, kinds in ENGINE_KINDS.items() for k in kinds}
    want |= {f"{p}@{k}+compact" for p, kinds in ENGINE_KINDS.items()
             for k in kinds if k.endswith("sharded")}
    want |= {f"{p}@gas_sharded+frontier"
             for p, kinds in ENGINE_KINDS.items()
             if "gas_sharded" in kinds
             and as_gas(get_program(p)).frontier}
    return want


def test_registry_matrix_is_clean_and_complete():
    # The acceptance gate `make lint-ir` runs: every registered program x
    # capable executor traces, and the shipped tree carries no findings.
    targets = ir.registry_targets()
    assert {t.name for t in targets} == _matrix_names()
    report = ir.run_targets(targets)
    assert report.ok, report.format_human()
    assert report.summary()["schema"] == "luxlint.ir.v1"


def test_dtype_drift_on_carry():
    t = _spec_target(lambda v: (v * 2).astype(jnp.bfloat16))
    report = ir.run_targets([t], [ir.DtypeDrift()])
    assert _rules(report) == ["LUX101"]
    assert "bfloat16" in report.results[0].findings[0].message


def test_dtype_drift_carry_cannot_roundtrip():
    # More carry leaves than step outputs: the carry cannot survive the
    # step at all — one target-level finding, not a crash.
    t = _spec_target(
        lambda a, b: a + b,
        args=(jnp.zeros(8), jnp.zeros(8)), carry=(0, 1),
    )
    report = ir.run_targets([t], [ir.DtypeDrift()])
    assert _rules(report) == ["LUX101"]
    assert "round" in report.results[0].findings[0].message


def test_host_callback_detected_through_jit_nesting():
    def step(v):
        return jax.jit(lambda x: jax.pure_callback(
            lambda y: np.asarray(y) * 2,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x))(v)

    report = ir.run_targets([_spec_target(step)], [ir.HostCallback()])
    assert _rules(report) == ["LUX102"]


def test_footprint_blowup_respects_flag(monkeypatch):
    t = _spec_target(lambda v: jnp.outer(v, v).sum(axis=1),
                     args=(jnp.zeros(512, jnp.float32),))
    report = ir.run_targets([t], [ir.FootprintBlowup()])
    assert _rules(report) == ["LUX103"]
    monkeypatch.setenv("LUX_IR_BLOWUP", "100000")
    report = ir.run_targets([t], [ir.FootprintBlowup()])
    assert report.ok


def test_donation_audit_passes_aliased_flags_unusable():
    good = jax.jit(lambda v: v * 2, donate_argnums=0)
    bad = jax.jit(lambda v: v.sum(), donate_argnums=0)
    x = (jnp.zeros(64, jnp.float32),)
    rep = ir.run_targets(
        [_spec_target(fn=good, args=x, donate=(0,)),
         _spec_target(fn=bad, args=x, donate=(0,), carry=(), name="u@bad")],
        [ir.DonationAudit()],
    )
    assert not rep.results[0].findings
    assert [f.rule for f in rep.results[1].findings] == ["LUX104"]


def test_collective_audit_both_directions():
    psum = _spec_target(lambda v: jax.lax.psum(v, "p"),
                        axis_env=(("p", 4),))
    silent = _spec_target(lambda v: v * 0.5, sharded=True)
    rep = ir.run_targets([psum, silent], [ir.CollectiveAudit()])
    assert [f.rule for r in rep.results for f in r.findings] == \
        ["LUX105", "LUX105"]


def test_trace_failure_is_error_not_crash():
    def boom(v):
        raise RuntimeError("fixture trace bomb")

    report = ir.run_targets([_spec_target(boom)])
    assert not report.ok
    assert "trace failed" in report.results[0].error


@pytest.mark.parametrize("fixture,rule", [
    ("lux101_dtype_drift.py", "LUX101"),
    ("lux102_host_callback.py", "LUX102"),
    ("lux103_blowup.py", "LUX103"),
    ("lux104_donation.py", "LUX104"),
    ("lux105_collective.py", "LUX105"),
])
def test_seeded_fixture_fires_exactly_its_rule(fixture, rule):
    targets = ir.load_fixture_targets(os.path.join(IR_FIXTURES, fixture))
    report = ir.run_targets(targets)
    assert not report.ok
    assert _rules(report) == [rule]
    assert report.summary()["errors"] == 0


# -- serve-pool donation audit ------------------------------------------


class _BadDonationEngine:
    def trace_step(self):
        fn = jax.jit(lambda v: v.sum(), donate_argnums=0)
        return {"kind": "bad", "fn": fn,
                "args": (jnp.zeros(64, jnp.float32),),
                "donate": (0,), "carry": (), "sharded": False}


def test_pool_build_runs_donation_audit(recwarn):
    from lux_tpu.obs import metrics
    from lux_tpu.serve.pool import EnginePool

    before = metrics.counter("lux_ir_findings_total").value
    pool = EnginePool(scope="t_ir_audit")
    try:
        pool.get("bad", _BadDonationEngine)
        assert metrics.counter("lux_ir_findings_total").value == before + 1
        assert pool.stats()["ir_findings"] >= 1
    finally:
        pool.close()


def test_pool_audit_disabled_by_flag(monkeypatch, recwarn):
    from lux_tpu.obs import metrics
    from lux_tpu.serve.pool import EnginePool

    monkeypatch.setenv("LUX_IR_POOL_AUDIT", "0")
    before = metrics.counter("lux_ir_findings_total").value
    pool = EnginePool(scope="t_ir_audit_off")
    try:
        pool.get("bad", _BadDonationEngine)
        assert metrics.counter("lux_ir_findings_total").value == before
    finally:
        pool.close()


def test_pool_concurrent_first_requests_build_once():
    from lux_tpu.serve.pool import EnginePool

    pool = EnginePool(scope="t_ir_race")
    builds = []
    barrier = threading.Barrier(8)

    def factory():
        builds.append(1)
        return object()

    def worker(out, i):
        barrier.wait()
        out[i] = pool.get("k", factory)

    try:
        got = [None] * 8
        threads = [threading.Thread(target=worker, args=(got, i))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(builds) == 1
        assert len({id(g) for g in got}) == 1
        assert len(pool) == 1
    finally:
        pool.close()


# -- CLI tiers ----------------------------------------------------------


def test_cli_ir_matrix_is_green():
    proc = _run_cli("--ir")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    s = _summary_line(proc.stdout)
    assert s["schema"] == "luxlint.ir.v1"
    assert s["files"] == len(_matrix_names())
    assert s["findings"] == 0 and s["errors"] == 0


def test_cli_ir_fixture_exits_nonzero():
    proc = _run_cli("--ir", os.path.join(IR_FIXTURES,
                                         "lux104_donation.py"))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert _summary_line(proc.stdout)["by_rule"] == {"LUX104": 1}


def test_cli_baseline_masks_known_findings(tmp_path):
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
    # First run snapshots the pre-existing finding and passes.
    proc = _run_cli(str(tmp_path / "engine"), "--baseline", base)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "baseline written" in proc.stdout
    # Unchanged tree: the known finding stays masked.
    proc = _run_cli(str(tmp_path / "engine"), "--baseline", base)
    assert proc.returncode == 0
    assert "0 new" in proc.stdout
    # A fresh violation is NOT masked.
    worse = tmp_path / "engine" / "run_worse.py"
    worse.write_text(
        "def run(step, vals, n):\n"
        "    for _ in range(n):\n"
        "        x = float(vals.sum())\n"
        "    return x\n"
    )
    proc = _run_cli(str(tmp_path / "engine"), "--baseline", base)
    assert proc.returncode == 1
    assert "[new]" in proc.stdout


def test_cli_changed_emits_summary():
    # Content depends on git state; the contract is: it runs, restricts
    # to changed files, and still emits the greppable summary line.
    proc = _run_cli("--changed")
    assert proc.returncode in (0, 1), proc.stdout + proc.stderr
    assert _summary_line(proc.stdout)["schema"] == "luxlint.v1"

"""luxlint: rule engine, per-rule fixtures, CLI contract, flag registry,
and the runtime tracing-discipline sentinels.

Fixture convention (tests/lint_fixtures/): `bad_*` files carry
`# expect: LUXNNN[, LUXNNN]` markers on exactly the lines a finding must
anchor to; `good_*` files must produce zero findings. Rules scope by
path fragment, so fixtures live under engine/ / ops/ / lux_tpu/
subdirectories to arm the path-scoped rules.
"""

import json
import os
import re
import subprocess
import sys

import pytest

from lux_tpu.analysis import all_rules, run_paths, run_source
from lux_tpu.analysis.core import load_declared_flags, suppressions_for
from lux_tpu.utils import flags

TESTS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS)
FIXTURES = os.path.join(TESTS, "lint_fixtures")
LUXLINT = os.path.join(REPO, "tools", "luxlint.py")

_EXPECT_RE = re.compile(r"#\s*expect:\s*([A-Z0-9_,\s]+?)\s*$")

BAD_FIXTURES = (
    "engine/bad_host_sync.py",
    "bad_recompile.py",
    "ops/bad_kernel_specs.py",
    "lux_tpu/bad_envflag.py",
    "serve/bad_clock.py",
    "serve/bad_swallow.py",
    "obs/bad_metric_names.py",
    "obs/bad_region_names.py",
    "obs/bad_ledger_dump.py",
)
GOOD_FIXTURES = (
    "engine/good_host_sync.py",
    "good_recompile.py",
    "ops/good_kernel_specs.py",
    "lux_tpu/good_envflag.py",
    "serve/good_clock.py",
    "serve/good_swallow.py",
    "obs/good_metric_names.py",
    "obs/good_region_names.py",
    "obs/good_ledger_dump.py",
)


def _expected(path):
    want = {}
    with open(path) as fh:
        for i, line in enumerate(fh, 1):
            m = _EXPECT_RE.search(line)
            if m:
                want[i] = sorted(
                    s.strip() for s in m.group(1).split(",") if s.strip()
                )
    return want


def _lint(path, rules=None):
    with open(path) as fh:
        src = fh.read()
    return run_source(src, path, rules or all_rules(), load_declared_flags())


def _by_line(findings):
    out = {}
    for f in findings:
        out.setdefault(f.line, []).append(f.rule)
    return {k: sorted(v) for k, v in out.items()}


# -- rules vs fixtures ----------------------------------------------------


@pytest.mark.parametrize("rel", BAD_FIXTURES)
def test_bad_fixture_fires_exactly_where_expected(rel):
    path = os.path.join(FIXTURES, rel)
    res = _lint(path)
    assert res.error is None
    want = _expected(path)
    assert want, f"{rel} has no expect markers"
    assert _by_line(res.findings) == want
    assert res.suppressed == []


@pytest.mark.parametrize("rel", GOOD_FIXTURES)
def test_good_fixture_is_clean(rel):
    res = _lint(os.path.join(FIXTURES, rel))
    assert res.error is None
    assert res.findings == [] and res.suppressed == []


def test_suppression_with_reason_is_counted_not_silent():
    res = _lint(os.path.join(FIXTURES, "engine", "suppressed_host_sync.py"))
    assert res.findings == []
    assert len(res.suppressed) == 2
    assert {f.rule for f in res.suppressed} == {"LUX001"}


def test_suppressions_for_ids_reasons_and_comment_lines():
    supp = suppressions_for([
        "x = 1  # luxlint: disable=LUX001,LUX002 -- reason text",
        "# luxlint: disable=all",
        "y = 2",
    ])
    assert supp[1] == {"LUX001", "LUX002"}
    assert supp[2] == {"all"}
    assert supp[3] == {"all"}      # comment-only line covers the next line
    assert 4 not in supp


def test_rule_selection_runs_subset():
    path = os.path.join(FIXTURES, "lux_tpu", "bad_envflag.py")
    rules = [r for r in all_rules() if r.id == "LUX004"]
    res = _lint(path, rules)
    assert {f.rule for f in res.findings} == {"LUX004"}
    assert len(res.findings) == 2


def test_report_json_and_summary_schema():
    report = run_paths([FIXTURES], all_rules())
    expected_total = sum(
        len(ids)
        for rel in BAD_FIXTURES
        for ids in _expected(os.path.join(FIXTURES, rel)).values()
    )
    payload = json.loads(report.to_json())
    s = payload["summary"]
    assert s["schema"] == "luxlint.v1"
    assert s["findings"] == expected_total
    assert s["suppressed"] == 2
    assert s["ok"] is False
    assert sum(s["by_rule"].values()) == expected_total
    for f in payload["findings"]:
        assert set(f) == {"rule", "path", "line", "col", "message"}


def test_syntax_error_is_reported_not_crashed():
    res = run_source("def broken(:\n", "engine/x.py", all_rules(), set())
    assert res.error and "x.py" in res.error


# -- CLI contract ---------------------------------------------------------


def _run_cli(*args):
    return subprocess.run(
        [sys.executable, LUXLINT, *args],
        capture_output=True, text=True, cwd=REPO,
    )


def _summary_line(stdout):
    lines = [l for l in stdout.splitlines() if l.startswith("LUXLINT ")]
    assert lines, stdout
    return json.loads(lines[-1][len("LUXLINT "):])


def test_cli_full_tree_is_green():
    # The gate `make lint` runs: the shipped tree must lint clean (every
    # intentional sync point suppressed with a reason, every flag
    # declared), and the last stdout line must be the greppable summary.
    proc = _run_cli()
    assert proc.returncode == 0, proc.stdout + proc.stderr
    s = _summary_line(proc.stdout)
    assert s["ok"] is True and s["findings"] == 0 and s["errors"] == 0
    assert s["files"] > 50
    assert s["suppressed"] >= 2    # pull flush + push chunk fetch


def test_cli_nonzero_on_seeded_violation(tmp_path):
    bad = tmp_path / "engine" / "run_bad.py"
    bad.parent.mkdir()
    bad.write_text(
        "def run(step, vals, n):\n"
        "    for _ in range(n):\n"
        "        vals = step(vals)\n"
        "        done = vals.item()\n"
        "    return vals, done\n"
    )
    proc = _run_cli(str(tmp_path))
    assert proc.returncode == 1
    s = _summary_line(proc.stdout)
    assert s["by_rule"] == {"LUX001": 1}
    assert f"{bad}:4" in proc.stdout


def test_cli_json_output_parses():
    proc = _run_cli("--json", os.path.join(FIXTURES, "lux_tpu"))
    assert proc.returncode == 1
    doc = json.loads(proc.stdout.rsplit("LUXLINT ", 1)[0])
    assert doc["summary"]["findings"] == 4
    assert {f["rule"] for f in doc["findings"]} == {"LUX004", "LUX005"}


def test_cli_rejects_unknown_rule_id():
    proc = _run_cli("--select", "LUX999")
    assert proc.returncode == 2
    assert "LUX999" in proc.stderr


# -- flag registry --------------------------------------------------------


def test_flags_accessors(monkeypatch):
    assert "LUX_LOG" in flags.names()
    with pytest.raises(KeyError):
        flags.get("LUX_NOT_A_FLAG")
    assert flags.default("LUX_EDGE_CHUNK_BYTES") == 2 << 30

    monkeypatch.delenv("LUX_BENCH_SCALE", raising=False)
    assert flags.get_int("LUX_BENCH_SCALE") == 22

    monkeypatch.delenv("LUX_LOCKWATCH", raising=False)
    assert flags.get_bool("LUX_LOCKWATCH") is False
    monkeypatch.setenv("LUX_LOCKWATCH", "1")
    assert flags.get_bool("LUX_LOCKWATCH") is True
    monkeypatch.setenv("LUX_LOCKWATCH", "off")
    assert flags.get_bool("LUX_LOCKWATCH") is False

    monkeypatch.delenv("LUX_PLAN_BANDED", raising=False)
    assert flags.tristate("LUX_PLAN_BANDED") is None
    monkeypatch.setenv("LUX_PLAN_BANDED", "1")
    assert flags.tristate("LUX_PLAN_BANDED") is True
    monkeypatch.setenv("LUX_PLAN_BANDED", "0")
    assert flags.tristate("LUX_PLAN_BANDED") is False
    monkeypatch.setenv("LUX_PLAN_BANDED", "yes")
    with pytest.raises(ValueError):
        flags.tristate("LUX_PLAN_BANDED")
    assert flags.tristate("LUX_PLAN_BANDED", strict=False) is None


def test_flags_define_guards():
    with pytest.raises(ValueError):
        flags.define("LUX_LOG", "DEBUG", "conflicting redefinition")
    with pytest.raises(ValueError):
        flags.define("NOT_LUX_PREFIXED", 1, "bad prefix")
    # Identical redefinition is a no-op (idempotent re-imports).
    f = flags.define(
        "LUX_LOG", "INFO",
        "log level for the lux.* logger categories (DEBUG..CRITICAL)",
    )
    assert f.name == "LUX_LOG"


def test_flags_module_prints_table():
    proc = subprocess.run(
        [sys.executable, "-m", "lux_tpu.utils.flags"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr
    assert "LUX_LOG" in proc.stdout
    assert "LUX_EDGE_CHUNK_BYTES" in proc.stdout
    # every declared flag appears
    for name in flags.names():
        assert name in proc.stdout


# -- runtime sentinels ----------------------------------------------------


def test_recompile_sentinel_counts_compiles_not_cache_hits():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from lux_tpu.analysis.sentinel import RecompileError, RecompileSentinel
    from lux_tpu.obs import metrics

    sent = RecompileSentinel("test")
    if not sent.available:
        sent.close()
        pytest.skip("jax monitoring hook unavailable in this jax")
    try:
        @jax.jit
        def f(x):
            return x * 2 + 1

        # Inputs built OUTSIDE the regions: jnp.arange dispatches its
        # own compiled executable, which must not pollute the counts.
        x8, x16 = jnp.arange(8), jnp.arange(16)

        with sent.expect("k"):
            f(x8).block_until_ready()
        warm = sent.compiles("k")
        assert warm >= 1

        with sent.watch("k"):
            f(x8).block_until_ready()              # executable cache hit
        assert sent.recompiles("k") == 0
        sent.assert_zero_recompiles()

        jax.jit(lambda x: x - 3)(x8)               # outside any region
        assert sent.compiles("k") == warm
        assert sent.recompiles() == 0

        with sent.watch("k"):
            f(x16).block_until_ready()             # new shape: recompile
        assert sent.recompiles("k") == 1
        with pytest.raises(RecompileError):
            sent.assert_zero_recompiles()
        st = sent.stats()
        assert st["per_key"]["k"]["serve"] == 1

        # Mirrored onto the obs registry for LUX_METRICS dumps.
        hits = [
            m for m in metrics.snapshot()
            if m["name"] == "lux_xla_compiles_total"
            and m["labels"].get("key") == "k"
            and m["labels"].get("phase") == "serve"
        ]
        assert hits and hits[0]["value"] >= 1
    finally:
        sent.close()


def test_host_transfer_guard_blocks_and_allows():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from lux_tpu.analysis.sentinel import HostTransferError, HostTransferGuard

    x = jnp.arange(8)
    with HostTransferGuard("unit") as g:
        with pytest.raises(HostTransferError):
            jax.device_get(x)
        with pytest.raises(HostTransferError):
            jax.block_until_ready(x)
        with g.allow():               # intended sync point
            assert int(jax.device_get(x)[3]) == 3
    # Entry points restored on exit.
    assert int(jax.device_get(x)[0]) == 0
    assert jax.block_until_ready(x) is x


def test_host_transfer_guard_around_engine_loop():
    # The discipline LUX001 checks statically, enforced at runtime: a
    # pull fused-step loop body must issue no device->host transfer
    # between intended sync points.
    jax = pytest.importorskip("jax")

    from lux_tpu.analysis.sentinel import HostTransferGuard
    from lux_tpu.engine.pull import PullExecutor
    from lux_tpu.graph import generate
    from lux_tpu.models.pagerank import PageRank

    g = generate.gnp(300, 1800, seed=77)
    ex = PullExecutor(g, PageRank())
    vals = ex.init_values()
    with HostTransferGuard("pull-loop") as guard:
        for _ in range(4):
            vals = ex.step(vals)      # stays on device
        with guard.allow():
            jax.block_until_ready(vals)
    assert vals.shape[0] == g.nv


def test_flags_define_outside_registry_is_lux004():
    # Satellite of the registry-drift contract: LUX004's allowed-key set
    # is generated from utils/flags.py, so a define() anywhere else is
    # registry drift by construction — including via an import alias.
    direct = (
        "from lux_tpu.utils import flags\n"
        "flags.define('LUX_ROGUE', 1, 'drift', kind='int')\n"
    )
    aliased = (
        "from lux_tpu.utils.flags import define\n"
        "define('LUX_ROGUE', 1, 'drift', kind='int')\n"
    )
    for src in (direct, aliased):
        res = run_source(
            src, "lux_tpu/engine/rogue.py", all_rules(),
            load_declared_flags())
        assert any(
            f.rule == "LUX004" and "declaration site" in f.message
            for f in res.findings
        ), (src, res.findings)
    # The registry itself is the one legitimate declaration site.
    res = run_source(
        direct, "lux_tpu/utils/flags.py", all_rules(),
        load_declared_flags())
    assert not any(
        "declaration site" in f.message for f in res.findings)


def test_ir_flags_are_registered():
    # The IR tier's knobs went through the registry (LUX004 would flag
    # their use otherwise).
    assert flags.get_float("LUX_IR_BLOWUP") == 16.0
    assert flags.get_bool("LUX_IR_POOL_AUDIT") is True


def test_recompile_sentinel_thread_safe_under_concurrent_warmups():
    # EnginePool serializes builds per pool, but nothing stops several
    # pools (or a pool and test traffic) compiling at once: concurrent
    # expect() regions on distinct threads must not lose counts, and
    # attribution must stay per-thread (TLS region stack).
    import threading

    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from lux_tpu.analysis.sentinel import RecompileSentinel

    sent = RecompileSentinel("race")
    if not sent.available:
        sent.close()
        pytest.skip("jax monitoring hook unavailable in this jax")
    n = 8
    barrier = threading.Barrier(n)
    errors = []

    def warm(i):
        try:
            barrier.wait()
            with sent.expect(f"k{i}"):
                # Distinct shape per thread: each warmup really compiles.
                jax.jit(lambda x: x * 2 + i)(
                    jnp.arange(8 + i)).block_until_ready()
        except Exception as e:   # pragma: no cover - surfaced below
            errors.append(e)

    try:
        threads = [threading.Thread(target=warm, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        per_key = sent.stats()["per_key"]
        assert set(per_key) == {f"k{i}" for i in range(n)}
        # No lost updates: the total equals the per-key sum, with at
        # least one real compile attributed to every thread's region.
        total = sent.compiles()
        assert total == sum(v.get("warmup", 0) for v in per_key.values())
        assert all(v.get("warmup", 0) >= 1 for v in per_key.values())
        assert sent.recompiles() == 0
    finally:
        sent.close()


def test_host_transfer_guard_allow_is_reentrant():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from lux_tpu.analysis.sentinel import HostTransferError, HostTransferGuard

    x = jnp.arange(8)
    with HostTransferGuard("nested") as g:
        with g.allow():
            with g.allow():           # nested window: still open
                assert int(jax.device_get(x)[1]) == 1
            # Inner exit must not close the outer window.
            assert int(jax.device_get(x)[2]) == 2
        with pytest.raises(HostTransferError):
            jax.device_get(x)
        # An exception inside a window must not leak the allow depth.
        with pytest.raises(RuntimeError):
            with g.allow():
                raise RuntimeError("boom")
        with pytest.raises(HostTransferError):
            jax.device_get(x)
    assert int(jax.device_get(x)[0]) == 0

"""Profile-guided auto-tuner (lux_tpu/tune).

Covers the scoped flag overlay (``flags.overrides``: nesting,
None-masking, undeclared rejection, contextvar thread isolation, and
snapshot/config_hash resolving through it), the declared knob space
(determinism, default-first, constraint pruning), the successive-halving
search (same seed + graph -> identical winner and score table; a seeded
synthetic where a known-better non-default exchange mode must be found;
the subsample keeping the all-defaults candidate), tuneconf.v1 artifact
persistence, the TuneCache LRU/evict-on-swap contract, the LUX501-504
offline verifier on seeded corruptions, probe scoring units, and the
serving integration: warmup applies the artifact's capture-at-build
knobs, misses are counted fallbacks, and a hot-swap evicts the tuned
config with the plan cache.
"""

import copy
import math
import os
import subprocess
import sys
import threading
import types

import numpy as np
import pytest

from lux_tpu.analysis import tuneck
from lux_tpu.obs import ledger, metrics
from lux_tpu.tune import artifact, probe, space
from lux_tpu.tune.cache import TuneCache, tune_cache
from lux_tpu.tune.search import tune
from lux_tpu.utils import flags

TESTS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS)
LUXLINT = os.path.join(REPO, "tools", "luxlint.py")

FP = "ab12" * 10   # a plausible checkpoint fingerprint

# Deterministic synthetic cost model for the search's injectable
# measure seam: compact exchange is known-better, full (the default)
# is worst, frontier sits between; tiny knob terms totally order the
# table so argmin is unique.
_BASE_COST = {"full": 4.0, "compact": 1.0, "frontier": 2.0}


def _measure(cand, iters, rung):
    c = _BASE_COST[cand.get("LUX_EXCHANGE", "full")]
    c += 0.01 * float(cand.get("LUX_GAS_DENSITY_HI", "0.0625"))
    c += 0.001 * float(cand.get("LUX_GAS_DENSITY_LO", "0.005"))
    c += 0.0001 * float(cand.get("LUX_EXCHANGE_FRONTIER_FRAC", "0.25"))
    return c


def _graph_stub(nv=100, ne=800):
    # tune() with an injected measure only reads graph.nv/graph.ne.
    return types.SimpleNamespace(nv=nv, ne=ne)


def _synthetic_tune(engine_kind="gas_sharded", measure=_measure, **kw):
    kw.setdefault("program_name", "bfs")
    kw.setdefault("graph_fingerprint", FP)
    kw.setdefault("mesh_shape", "2")
    kw.setdefault("device_kind", "cpu")
    return tune(_graph_stub(), object(), engine_kind,
                measure=measure, **kw)


@pytest.fixture
def tune_dir(tmp_path, monkeypatch):
    """Arm LUX_TUNE_DIR at a fresh store; reset the singleton cache."""
    root = str(tmp_path / "tune")
    monkeypatch.setenv("LUX_TUNE_DIR", root)
    tune_cache().clear()
    yield root
    tune_cache().clear()


# -- flags.overrides ------------------------------------------------------


def test_overrides_scoped_and_nested():
    assert flags.get("LUX_EXCHANGE") == "full"
    with flags.overrides({"LUX_EXCHANGE": "compact"}):
        assert flags.get("LUX_EXCHANGE") == "compact"
        with flags.overrides({"LUX_EXCHANGE": "frontier"}):
            assert flags.get("LUX_EXCHANGE") == "frontier"
        assert flags.get("LUX_EXCHANGE") == "compact"
    assert flags.get("LUX_EXCHANGE") == "full"


def test_overrides_values_stringified_and_typed_accessors():
    with flags.overrides({"LUX_GAS_DENSITY_HI": 0.25,
                          "LUX_TUNE_PROBE_ITERS": 3}):
        assert flags.get("LUX_GAS_DENSITY_HI") == "0.25"
        assert flags.get_float("LUX_GAS_DENSITY_HI") == 0.25
        assert flags.get_int("LUX_TUNE_PROBE_ITERS") == 3


def test_overrides_none_masks_env(monkeypatch):
    monkeypatch.setenv("LUX_EXCHANGE", "compact")
    assert flags.get("LUX_EXCHANGE") == "compact"
    with flags.overrides({"LUX_EXCHANGE": None}):
        # None masks the env var: the declared default wins.
        assert flags.get("LUX_EXCHANGE") == "full"
    assert flags.get("LUX_EXCHANGE") == "compact"


def test_overrides_undeclared_raises_before_applying():
    with pytest.raises(KeyError, match="undeclared"):
        with flags.overrides({"LUX_EXCHANGE": "compact",
                              "LUX_NO_SUCH_KNOB": "1"}):
            pytest.fail("overlay with a typo'd knob must not enter")
    assert flags.get("LUX_EXCHANGE") == "full"


def test_overrides_thread_isolation():
    """The overlay is context-local: a candidate config being probed in
    one thread must never leak into another (concurrent serving)."""
    seen = {}

    def worker():
        seen["worker"] = flags.get("LUX_EXCHANGE")

    with flags.overrides({"LUX_EXCHANGE": "compact"}):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert flags.get("LUX_EXCHANGE") == "compact"
    assert seen["worker"] == "full"


def test_snapshot_and_config_hash_resolve_through_overlay():
    base_hash = flags.config_hash()
    with flags.overrides({"LUX_EXCHANGE": "compact"}):
        assert flags.snapshot()["LUX_EXCHANGE"] == "compact"
        assert flags.config_hash() != base_hash
    assert flags.config_hash() == base_hash


# -- knob space -----------------------------------------------------------


def test_knob_space_default_first_and_deterministic():
    cands = space.knob_space("gas_sharded")
    assert cands[0] == space.default_candidate("gas_sharded")
    assert cands[0]["LUX_EXCHANGE"] == "full"
    assert cands == space.knob_space("gas_sharded")
    modes = {c["LUX_EXCHANGE"] for c in cands}
    assert modes == {"full", "compact", "frontier"}


def test_knob_space_constraint_pruning():
    frac_default = str(flags.default("LUX_EXCHANGE_FRONTIER_FRAC"))
    for cand in space.knob_space("gas_sharded"):
        # Frontier fraction only varies when the exchange runs frontier.
        if cand["LUX_EXCHANGE"] != "frontier":
            assert cand["LUX_EXCHANGE_FRONTIER_FRAC"] == frac_default
        # Hysteresis must keep lo < hi.
        assert float(cand["LUX_GAS_DENSITY_LO"]) \
            < float(cand["LUX_GAS_DENSITY_HI"])


def test_knob_space_kinds():
    assert space.knob_space("pull") == [{}]
    assert space.knob_space("push") == [{}]
    assert space.knob_space("tiled") == [{}]
    gas = space.knob_space("gas")
    assert all(set(c) == {"LUX_GAS_DENSITY_HI", "LUX_GAS_DENSITY_LO"}
               for c in gas)
    assert len(gas) < len(space.knob_space("gas_sharded"))


def test_knob_space_only_tuner_managed():
    for kind in ("gas", "gas_sharded", "pull_sharded", "tiled",
                 "tiled_sharded", "push"):
        for cand in space.knob_space(kind):
            assert set(cand) <= space.TUNER_MANAGED, (kind, cand)


# -- search ---------------------------------------------------------------


def test_tune_same_seed_identical_winner_and_score_table():
    a = _synthetic_tune()
    b = _synthetic_tune()
    assert a["id"] == b["id"]
    assert a["config"] == b["config"]
    assert a["score_table"] == b["score_table"]


def test_tune_finds_known_better_exchange():
    art = _synthetic_tune()
    assert art["config"]["LUX_EXCHANGE"] == "compact", art["config"]
    defaults = [r for r in art["score_table"] if r["candidate_index"] == 0]
    assert defaults, "the all-defaults candidate must always be probed"
    assert defaults[-1]["score"] > art["score"]
    # The winner is the argmin of the final rung, ties on index.
    last = max(r["rung"] for r in art["score_table"])
    final = [r for r in art["score_table"] if r["rung"] == last]
    best = min(final, key=lambda r: (r["score"], r["candidate_index"]))
    assert best["config"] == art["config"]


def test_tune_successive_halving_shape():
    art = _synthetic_tune()
    by_rung = {}
    for row in art["score_table"]:
        by_rung.setdefault(row["rung"], []).append(row)
    cap = flags.get_int("LUX_TUNE_MAX_CANDIDATES")
    eta = flags.get_int("LUX_TUNE_ETA")
    iters0 = flags.get_int("LUX_TUNE_PROBE_ITERS")
    assert len(by_rung[0]) == min(cap, len(space.knob_space("gas_sharded")))
    assert len(by_rung[1]) == math.ceil(len(by_rung[0]) / eta)
    assert all(r["iters"] == iters0 for r in by_rung[0])
    assert all(r["iters"] == 2 * iters0 for r in by_rung[1])


def test_tune_subsample_keeps_default_candidate():
    with flags.overrides({"LUX_TUNE_MAX_CANDIDATES": "4"}):
        art = _synthetic_tune()
    assert art["tuner"]["candidates"] == 4
    assert any(r["candidate_index"] == 0 for r in art["score_table"])
    # Still deterministic under the tightened cap.
    with flags.overrides({"LUX_TUNE_MAX_CANDIDATES": "4"}):
        assert _synthetic_tune()["id"] == art["id"]


def test_tune_lone_candidate_stops_early():
    """A kind with nothing to tune records one honest all-defaults rung
    instead of re-measuring the lone survivor."""
    art = _synthetic_tune("pull", measure=lambda c, i, r: 1.0)
    assert art["config"] == {}
    assert [r["rung"] for r in art["score_table"]] == [0]


def test_tune_select_lands_in_ledger(tmp_path, monkeypatch):
    monkeypatch.setenv("LUX_LEDGER_DIR", str(tmp_path / "ledger"))
    ledger.reset()
    try:
        art = _synthetic_tune()
        recs = ledger.read_all(strict=True)
        selects = [r for r in recs if r["kind"] == "tune_select"]
        assert len(selects) == 1
        assert selects[0]["tune"]["winner"] == art["config"]
        assert art["select_record_id"] == selects[0]["id"]
        # Injected measure -> no probe records, and the artifact says so.
        assert art["probe_ledger_ids"] == []
    finally:
        ledger.reset()


# -- artifact persistence -------------------------------------------------


def test_artifact_roundtrip(tune_dir):
    art = _synthetic_tune()
    path = artifact.save(tune_dir, art)
    assert os.path.basename(path).startswith("tuneconf-")
    assert artifact.load(tune_dir, art["key"]) == art
    other = artifact.make_key("cd34" * 10, "bfs", "gas_sharded", "2", "cpu")
    assert artifact.load(tune_dir, other) is None
    assert artifact.list_artifacts(tune_dir) == [path]


def test_artifact_key_mismatch_raises(tune_dir):
    art = _synthetic_tune()
    path = artifact.save(tune_dir, art)
    # A hand-edited key must never silently serve for another workload.
    edited = dict(art, key_string="tampered")
    import json as _json
    with open(path, "w") as f:
        _json.dump(edited, f)
    with pytest.raises(ValueError, match="key_string"):
        artifact.load(tune_dir, art["key"])


def test_artifact_bad_schema_raises(tune_dir):
    art = dict(_synthetic_tune(), schema="tuneconf.v0")
    path = os.path.join(tune_dir, "tuneconf-000000000000.json")
    os.makedirs(tune_dir, exist_ok=True)
    import json as _json
    with open(path, "w") as f:
        _json.dump(art, f)
    with pytest.raises(ValueError, match="schema"):
        artifact.load_path(path)


# -- TuneCache ------------------------------------------------------------


def _art_for(fp, program="bfs"):
    return _synthetic_tune(graph_fingerprint=fp, program_name=program)


def test_cache_disarmed_is_inert(monkeypatch):
    monkeypatch.delenv("LUX_TUNE_DIR", raising=False)
    tc = TuneCache()
    assert not tc.enabled()
    assert tc.get(artifact.make_key(FP, "bfs", "gas_sharded", "2",
                                    "cpu")) is None
    with pytest.raises(RuntimeError, match="LUX_TUNE_DIR"):
        tc.put(_synthetic_tune())


def test_cache_hit_miss_and_disk_reload(tune_dir):
    tc = TuneCache(root=tune_dir)
    art = _art_for(FP)
    tc.put(art)
    assert tc.get(art["key"]) == art          # memory hit
    tc.clear()
    assert len(tc) == 0
    assert tc.get(art["key"])["id"] == art["id"]   # miss -> disk load
    stats = tc.stats()
    assert stats["armed"] and stats["entries"] == 1


def test_cache_lru_eviction(tune_dir):
    tc = TuneCache(root=tune_dir)
    arts = [_art_for(f"{i:02x}" * 20) for i in range(3)]
    with flags.overrides({"LUX_TUNE_CACHE": "2"}):
        for a in arts:
            tc.put(a)
        assert len(tc) == 2
        # The oldest entry was evicted from memory, never from disk.
        assert tc.get(arts[0]["key"])["id"] == arts[0]["id"]
        assert os.path.exists(artifact.artifact_path(tune_dir,
                                                     arts[1]["key"]))


def test_cache_evict_fingerprint_keeps_disk(tune_dir):
    tc = TuneCache(root=tune_dir)
    keep_fp = "cd34" * 10
    for program in ("bfs", "labelprop"):
        tc.put(_art_for(FP, program))
    tc.put(_art_for(keep_fp))
    assert tc.evict_fingerprint(FP) == 2
    assert len(tc) == 1
    # Disk artifacts are evidence: the swap only drops memory entries,
    # and a later get() reloads the persisted file.
    reloaded = tc.get(_art_for(FP)["key"])
    assert reloaded is not None
    assert reloaded["key"]["graph_fingerprint"] == FP


# -- tuneck (LUX501-504) --------------------------------------------------


def _rule_ids(art):
    res = tuneck.verify_artifact(art)
    assert res.error is None, res.error
    return sorted({f.rule for f in res.findings})


def test_tuneck_clean_artifact():
    assert _rule_ids(_synthetic_tune()) == []


def test_tuneck_lux501_structure():
    art = copy.deepcopy(_synthetic_tune())
    art["schema"] = "tuneconf.v0"
    art["id"] = "not-an-id"
    del art["key"]["device_kind"]
    # The gutted key also trips LUX504's key well-formedness check.
    assert "LUX501" in _rule_ids(art)
    art2 = copy.deepcopy(_synthetic_tune())
    del art2["score_table"][0]["iters"]
    assert "LUX501" in _rule_ids(art2)


def test_tuneck_lux502_knob_domains():
    art = copy.deepcopy(_synthetic_tune())
    art["config"]["LUX_NO_SUCH_KNOB"] = "1"       # undeclared
    art["config"]["LUX_ENGOBS"] = "1"             # declared, not managed
    assert "LUX502" in _rule_ids(art)
    art2 = copy.deepcopy(_synthetic_tune())
    art2["score_table"][0]["config"] = {"LUX_EXCHANGE": "bogus"}
    assert "LUX502" in _rule_ids(art2)
    art3 = copy.deepcopy(_synthetic_tune())
    art3["config"]["LUX_GAS_DENSITY_HI"] = "0.05"
    art3["config"]["LUX_GAS_DENSITY_LO"] = "0.5"  # inverted hysteresis
    findings = tuneck.verify_artifact(art3).findings
    assert any(f.rule == "LUX502" and "hysteresis" in f.message
               for f in findings)


def test_tuneck_lux503_selection():
    art = copy.deepcopy(_synthetic_tune())
    # Swap the winner for the (valid, managed) default candidate: the
    # artifact no longer matches the final rung's argmin.
    default_row = next(r for r in art["score_table"]
                       if r["candidate_index"] == 0)
    art["config"] = dict(default_row["config"])
    art["score"] = default_row["score"]
    assert "LUX503" in _rule_ids(art)

    art2 = copy.deepcopy(_synthetic_tune())
    art2["probe_ledger_ids"] = ["run-deadbeef"]   # ids not in the table
    assert _rule_ids(art2) == ["LUX503"]

    art3 = copy.deepcopy(_synthetic_tune())
    for row in art3["score_table"]:
        if row["candidate_index"] == 0:
            row["candidate_index"] = 99          # default never probed
    findings = tuneck.verify_artifact(art3).findings
    assert any(f.rule == "LUX503" and "default candidate" in f.message
               for f in findings)

    art4 = copy.deepcopy(_synthetic_tune())
    art4["score_table"][0]["score"] = float("nan")
    assert "LUX503" in _rule_ids(art4)


def test_tuneck_lux504_staleness():
    old = _synthetic_tune(created_at=1.0)        # 1970: long past any bound
    assert _rule_ids(old) == ["LUX504"]
    with flags.overrides({"LUX_TUNE_MAX_AGE_S": "0"}):
        assert _rule_ids(old) == []              # 0 disables the age bound

    art = copy.deepcopy(_synthetic_tune())
    art["created_at"] = art["created_at"] + 86400.0   # the future
    assert _rule_ids(art) == ["LUX504"]

    art2 = copy.deepcopy(_synthetic_tune())
    art2["key"]["graph_fingerprint"] = "?"
    art2["key_string"] = artifact.key_string(art2["key"])
    art2["graph_meta"] = {"nv": 0, "ne": -1}
    assert "LUX504" in _rule_ids(art2)


def test_luxlint_tune_cli(tune_dir):
    clean = _synthetic_tune()
    artifact.save(tune_dir, clean)
    proc = subprocess.run(
        [sys.executable, LUXLINT, "--tune", tune_dir],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout[-500:]
    corrupt = copy.deepcopy(clean)
    corrupt["key"]["program"] = "labelprop"
    corrupt["key_string"] = artifact.key_string(corrupt["key"])
    corrupt["config"]["LUX_ENGOBS"] = "1"
    artifact.save(tune_dir, corrupt)
    proc = subprocess.run(
        [sys.executable, LUXLINT, "--tune", tune_dir],
        capture_output=True, text=True)
    assert proc.returncode == 1, proc.stdout[-500:]
    assert "LUX502" in proc.stdout


# -- probe scoring --------------------------------------------------------


def test_score_summary_phase_medians_drop_first_record():
    summary = {"iterations": [
        {"exchange_s": 10.0, "compute_s": 10.0},   # cold-start ramp
        {"exchange_s": 1.0, "compute_s": 2.0},
        {"exchange_s": 1.0, "compute_s": 2.0},
    ]}
    score, detail = probe.score_summary(summary, 3, 0, 0, 0.05)
    assert score == pytest.approx(3.0)
    assert detail["exchange_s_med"] == pytest.approx(1.0)
    assert detail["compute_s_med"] == pytest.approx(2.0)


def test_score_summary_instability_penalty():
    summary = {"iterations": [{"exchange_s": 1.0, "compute_s": 1.0},
                              {"exchange_s": 1.0, "compute_s": 1.0}]}
    calm, _ = probe.score_summary(summary, 4, 0, 0, 0.5)
    flappy, detail = probe.score_summary(summary, 4, 2, 2, 0.5)
    assert flappy == pytest.approx(calm * 1.5)   # 1 + 0.5 * 4/4
    assert detail["direction_switches"] == 2


def test_score_summary_fallbacks():
    wall = {"iterations": [{"t_iter_s": 2.0}, {"t_iter_s": 4.0}]}
    score, detail = probe.score_summary(wall, 2, 0, 0, 0.0)
    assert score == pytest.approx(3.0)
    assert detail["exchange_s_med"] == 0.0
    totals = {"iterations": [], "num_iters": 5, "execute_s": 10.0}
    score2, _ = probe.score_summary(totals, 5, 0, 0, 0.0)
    assert score2 == pytest.approx(2.0)


# -- serving integration --------------------------------------------------


def _session_artifact(g, fp, app="bfs"):
    """A tuneconf.v1 for ``app`` on a single-device session, tuned to a
    distinctly non-default density hysteresis (capture-at-build)."""
    from lux_tpu.obs import report

    def measure(cand, iters, rung):
        # hi=0.9, lo=0.05 is known-better; defaults are worst.
        return 2.0 - float(cand.get("LUX_GAS_DENSITY_HI", "0")) \
            - float(cand.get("LUX_GAS_DENSITY_LO", "0"))

    art = tune(g, object(), "gas", program_name=app,
               graph_fingerprint=fp, mesh_shape="1",
               device_kind=report.device_profile()["device_kind"],
               measure=measure)
    assert art["config"]["LUX_GAS_DENSITY_HI"] == "0.9"
    assert art["config"]["LUX_GAS_DENSITY_LO"] == "0.05"
    return art


def test_session_warmup_applies_tuned_config(tune_dir):
    from lux_tpu.graph import generate
    from lux_tpu.models.bfs import reference_bfs
    from lux_tpu.serve import ServeConfig, Session
    from lux_tpu.utils.checkpoint import fingerprint_hex

    metrics.reset()
    g = generate.gnp(300, 2000, seed=181)
    art = _session_artifact(g, fingerprint_hex(g))
    tune_cache().put(art)
    with Session(g, ServeConfig(max_batch=4, window_s=0.01,
                                pagerank_iters=4)) as s:
        prov = s.tuned_for("bfs")
        assert prov == {"id": art["id"], "score": art["score"]}
        engine = s._gas_single("bfs")
        # Tuned knobs are capture-at-build: the warmup engine carries
        # the artifact's hysteresis, not the declared defaults.
        assert engine.hi_count == math.ceil(0.9 * g.nv)
        assert engine.lo_count == math.ceil(0.05 * g.nv)
        tb = s.statusz()["tune"]
        assert tb["armed"]
        assert tb["artifacts"]["bfs"]["id"] == art["id"]
        assert tb["artifacts"]["bfs"]["probes"] == len(art["score_table"])
        # Every other app is a counted fallback, never silent.
        assert "bfs" not in tb["fallbacks"]
        assert "pagerank" in tb["fallbacks"]
        fallbacks = sum(m["value"] for m in metrics.snapshot()
                        if m["name"] == "lux_tune_fallback_total")
        assert fallbacks == len(tb["fallbacks"]) > 0
        assert s.tuned_for("pagerank") is None
        # Tuning is bitwise-neutral for integral programs.
        out = s.query("bfs", start=3, timeout=60)
        depth, _parent = reference_bfs(g, 3)
        np.testing.assert_array_equal(out["values"], depth)


def test_session_swap_evicts_tuned_config(tune_dir):
    from lux_tpu.graph import EdgeEdits, generate
    from lux_tpu.serve import ServeConfig, Session
    from lux_tpu.utils.checkpoint import fingerprint_hex

    metrics.reset()
    g = generate.gnp(300, 2000, seed=182)
    old_fp = fingerprint_hex(g)
    tune_cache().put(_session_artifact(g, old_fp))
    with Session(g, ServeConfig(max_batch=4, window_s=0.01,
                                pagerank_iters=4)) as s:
        assert s.tuned_for("bfs") is not None
        s.apply_edits(EdgeEdits.from_lists(insert=[(0, g.nv - 1),
                                                   (1, g.nv - 2)]))
        assert s.fingerprint != old_fp
        # The swap retires the tuned config with the engines and the
        # shard plan: the new fingerprint has no artifact, so bfs is a
        # counted fallback until someone re-tunes.
        assert s.tuned_for("bfs") is None
        tb = s.statusz()["tune"]
        assert "bfs" in tb["fallbacks"]
        assert tb["artifacts"] == {}
        from lux_tpu.obs import report
        key = artifact.make_key(old_fp, "bfs", "gas", "1",
                                report.device_profile()["device_kind"])
        # The old artifact is still on disk (evidence), only the
        # in-memory entry was dropped.
        assert os.path.exists(artifact.artifact_path(tune_dir, key))

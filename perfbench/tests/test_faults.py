"""Each cell run end to end on the CPU at a small size (the look for a
chip skipped), sound and with its timed path broken underneath: every
fault the cell can have must turn ``correct`` false.

One chip and one lane per batch leave out two faults: no exchange
between chips exists to drop, and no serving batch holds a second query.
"""

import os

import jax.numpy as jnp
import pytest

from perfbench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KRON = {"name": "t", "generator": "kronecker", "scale": 10, "edgefactor": 16,
        "A": 0.57, "B": 0.19, "C": 0.19}
RATINGS = {"name": "t", "generator": "ratings", "users": 3000, "items": 100,
           "ratings": 30000, "K": 20,
           "rating_probs": [0.046, 0.101, 0.287, 0.336, 0.23],
           "user_exponent": 1.513, "item_exponent": 1.613}
SEED = 2**31 + 77


def run(workload, config, **traffic):
    cell = harness.Cell(ROOT, workload)
    tr = dict(cell.traffic, **traffic)
    return harness.run_cell(ROOT, workload, SEED, 0.5, False,
                            require_tpu=False, config=config, traffic=tr)


def unchanged(self, vals, *args, **kw):
    return vals


def half_left_out(real):
    """The step updates only the first half of the vertices."""
    def step(self, vals, *args, **kw):
        new = real(self, vals, *args, **kw)
        rows = jnp.arange(vals.shape[0]).reshape(
            (-1,) + (1,) * (vals.ndim - 1))
        return jnp.where(rows < vals.shape[0] // 2, new, vals)
    return step


def altered(real):
    """The step's output for one vertex is off by 1%."""
    def step(self, vals, *args, **kw):
        new = real(self, vals, *args, **kw)
        return new.at[0].multiply(1.01)
    return step


def test_pagerank_sound_run_is_correct():
    res = run("pagerank-graph500-22", KRON)
    assert res["correct"] and res["attempted"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [lambda real: unchanged, half_left_out,
                                   altered],
                         ids=["unchanged", "half", "altered"])
def test_pagerank_faults(monkeypatch, fault):
    from lux_tpu.engine.tiled import TiledPullExecutor

    monkeypatch.setattr(TiledPullExecutor, "_step_impl",
                        fault(TiledPullExecutor._step_impl))
    assert not run("pagerank-graph500-22", KRON)["correct"]


def test_colfilter_sound_run_is_correct():
    res = run("cf-netflix", RATINGS)
    assert res["correct"] and res["attempted"] > 0


@pytest.mark.parametrize("fault", [lambda real: unchanged, half_left_out,
                                   altered],
                         ids=["unchanged", "half", "altered"])
def test_colfilter_faults(monkeypatch, fault):
    from lux_tpu.engine.pull import PullExecutor

    monkeypatch.setattr(PullExecutor, "_step_impl",
                        fault(PullExecutor._step_impl))
    assert not run("cf-netflix", RATINGS)["correct"]


def test_sssp_sound_run_is_correct():
    res = run("sssp-serve-graph500-22", KRON)
    assert res["correct"] and res["attempted"] > 0 and res["failed"] == 0


def test_sssp_state_unchanged(monkeypatch):
    from lux_tpu.engine.push import PushExecutor

    real = PushExecutor.run

    def run_unchanged(self, *a, **kw):
        _, iters = real(self, *a, **kw)
        return self.init_state(start=kw["start"]), iters

    monkeypatch.setattr(PushExecutor, "run", run_unchanged)
    assert not run("sssp-serve-graph500-22", KRON)["correct"]


def test_sssp_answer_altered(monkeypatch):
    from lux_tpu.engine.push import PushExecutor

    real = PushExecutor.run

    def run_altered(self, *a, **kw):
        st, iters = real(self, *a, **kw)
        return st._replace(values=st.values.at[::64].add(1)), iters

    monkeypatch.setattr(PushExecutor, "run", run_altered)
    assert not run("sssp-serve-graph500-22", KRON)["correct"]

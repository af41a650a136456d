"""chip_smoke.py off the chip: its phases at a tiny scale on the CPU
mesh, and its refusal to run without a TPU."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


@pytest.mark.parametrize("parts", [1, 4])
def test_phases_pass_on_cpu(tmp_path, capsys, parts):
    chip_smoke.run(10, 16, 42, parts, str(tmp_path / "work"), "cpu")
    out = capsys.readouterr().out
    phases = ["pagerank", "sssp", "bfs"] + (["serve"] if parts == 1 else [])
    for name in phases:
        assert f"phase {name}: " in out and "check=PASS" in out, name
    if parts > 1:
        assert f"operands on {parts} devices" in out
    assert not (tmp_path / "work").exists()


def test_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) == 2
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no TPU" in captured.err

"""The measurement path refuses a machine without a TPU: no fall-back to
the CPU, no result line."""
import os
import subprocess
import sys

import pytest

import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_find_chips_refuses_the_cpu():
    with pytest.raises(harness.NoChip):
        harness.find_chips(1)


def test_run_exits_nonzero_with_no_result_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "embed.decode", "--seed", str(2**31 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 3
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_every_cell_loads_with_its_metrics():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"], bench)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.metric_reader(m["name"]))

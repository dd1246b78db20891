"""The shape of a run's last line, and the refusal to measure anywhere
but on a TPU."""
import json
import os
import subprocess
import sys

import pytest

from bench.lib import spec as SP
from bench.tests.tiny import ROOT, run_tiny, tiny_cell


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "ppo-opt1.3b-p256g256", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=str(ROOT), timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "refusing" in p.stderr


def test_unknown_workload_refused(tmp_path):
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "no-such-cell", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=str(tmp_path), timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("name", ["ppo-opt1.3b-p256g256"])
@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_shape(name, trace):
    cell = tiny_cell(name)
    line = run_tiny(cell, seed=2 ** 33 + 3, seconds=2.0, trace=trace)
    line.pop("_checks")
    assert "setup_s=" in line.pop("_diag")
    text = json.dumps(line)               # strict JSON, no NaN
    assert "NaN" not in text
    keys = list(line)
    assert keys[:3] == ["correct", "attempted", "failed"]
    assert keys[-1] == "checks"
    assert {"metrics", "device"} <= set(keys)
    assert line["correct"] is True and line["failed"] == 0
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    want = ({m.name for m in cell.end_to_end} if not trace
            else set())                   # per-layer readers need the chip
    assert set(line["metrics"]) >= want
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps",
                                          "idle_by_span"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}

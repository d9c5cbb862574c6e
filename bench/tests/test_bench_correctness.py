"""The comparison that decides ``correct``, shown to fail.

Each test drives a whole benchmark run on the CPU at a size a test run
can hold (``tiny_root``: each cell's configuration and mix with fewer
keys and a lower rate), skipping only the look for a chip, in a process
of its own.  A sound run is correct while the bfloat16 control, replayed
beside it, fails one of the cell's limits; and each fault the cell can
have, planted under the timed path, turns ``correct`` false.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import tiny_root  # noqa: E402
from faults import FAULTS  # noqa: E402

CELLS = sorted(tiny_root.TINY)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root.make(tmp_path_factory.mktemp("tiny"))


def _run(root: Path, cell: str, seed: int, fault: str = "-",
         control: bool = False):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(root / ".jax_cache"))
    cmd = [sys.executable, str(HERE / "run_tiny.py"), str(root), cell,
           str(seed), fault] + (["--control"] if control else [])
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    phases = {}
    for line in lines[:-1]:
        row = json.loads(line)
        phases[row.get("phase")] = row
    return json.loads(lines[-1]), phases


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_control_fails_a_limit(root, cell):
    result, phases = _run(root, cell, 2**31 + 5, control=True)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0
    assert phases["reference"]["compared"]["ticks"] >= 1
    assert set(result["checks"]) == {"decision_gap", "tick_gap", "plan_gap"}
    limits = json.loads((root / f"bench/limits/{cell}.json").read_text())
    control = phases["control"]["readings"]
    assert any(control[k] > limits[k] for k in limits), control


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_under_the_timed_path_is_not_correct(root, cell, fault):
    result, _ = _run(root, cell, 4242, fault=fault)
    assert not result["correct"], (fault, result["checks"])

"""The reduction from a profiler trace to device time, idle share and
idle gaps: on hand-made rows, and pinned on a small trace recorded on a
TPU v5e (``record_trace.py``).  Reads files only: no TPU and no
topology."""
from __future__ import annotations

import gzip
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench.harness import trace  # noqa: E402

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def test_union_idle_and_gap_labels_on_hand_made_rows():
    ms = 1_000_000
    rows = [
        (HOST, "python", "quantum", 0, 10 * ms),
        (HOST, "python", "tick", 10 * ms, 20 * ms),
        (HOST, "python", "wait_arrival", 30 * ms, 70 * ms),
        (DEV, "XLA Modules", "jit_admit_quantum(7)", 2 * ms, 4 * ms),
        (DEV, "XLA Ops", "while", 2 * ms, 3 * ms),
        (DEV, "XLA Ops", "fusion.1", 4 * ms, 2 * ms),     # overlaps the while
        (DEV, "XLA Modules", "jit_control_tick(9)", 12 * ms, 1 * ms),
        (DEV, "XLA Ops", "fusion.2", 12 * ms, 1 * ms),
    ]
    r = trace.reduce_planes(rows)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.005)            # [2,6] + [12,13]
    assert r["kernel_s"] == {"admit_quantum": pytest.approx(0.004),
                             "control_tick": pytest.approx(0.001)}
    assert r["device_ops"][0] == ["admit_quantum", pytest.approx(0.004)]
    longest = r["idle_gaps"][0]
    assert longest == ["wait_arrival", pytest.approx(0.087)]
    # 6 -> 12 ms: the quantum span covers 4 ms of it, the tick 2
    assert r["idle_gaps"][1] == ["quantum", pytest.approx(0.006)]
    assert r["idle_gaps"][2] == ["quantum", pytest.approx(0.002)]


def test_no_device_events_gives_nothing_to_read():
    r = trace.reduce_planes([(HOST, "python", "quantum", 0, 5)])
    assert r["busy_s"] == 0.0 and r["kernel_s"] == {}


@pytest.mark.parametrize("module,kernel", [
    ("jit_admit_quantum(12)", "admit_quantum"),
    ("jit_control_tick", "control_tick"),
    ("plan_fleet(3)", "plan_fleet"),
])
def test_kernel_names_from_program_names(module, kernel):
    assert trace.kernel_name(module) == kernel


def test_recorded_tpu_trace_is_pinned(tmp_path):
    """One quantum of 12 requests, a 20 ms wait, a tick, a plan and a
    settle at 1024 tenants, traced on a TPU v5e."""
    xplane = tmp_path / "small.xplane.pb"
    with gzip.open(HERE / "data" / "small.xplane.pb.gz", "rb") as src, \
            open(xplane, "wb") as dst:
        shutil.copyfileobj(src, dst)
    rows = trace.rows_of(xplane)
    host = [r[2] for r in sorted(rows, key=lambda r: r[3])
            if not r[0].startswith("/device:")]
    assert host == ["quantum", "wait_arrival", "tick", "plan", "settle"]
    r = trace.reduce_planes(rows)
    assert r["chips"] == 1
    ks = r["kernel_s"]
    assert ks["admit_quantum"] == pytest.approx(284.048e-6, rel=1e-9)
    assert ks["control_tick"] == pytest.approx(261.961e-6, rel=1e-9)
    assert ks["plan_fleet"] == pytest.approx(7.738e-6, rel=1e-9)
    assert r["busy_s"] == pytest.approx(554.375e-6, rel=1e-9)
    assert r["window_s"] == pytest.approx(54.639566e-3, rel=1e-9)
    # a program's span (launch to completion) holds its operations
    assert ks["admit_quantum"] + ks["control_tick"] <= sum(ks.values())
    assert r["busy_s"] <= sum(ks.values())
    idle = 100.0 * (1.0 - r["busy_s"] / r["window_s"])
    assert idle == pytest.approx(98.98538, abs=1e-4)
    assert [n for n, _ in r["device_ops"][:3]] == [
        "admit_quantum", "control_tick", "plan_fleet"]
    assert r["idle_gaps"][0] == ["wait_arrival",
                                 pytest.approx(27.830637e-3, rel=1e-9)]
    assert r["idle_gaps"][1] == ["plan", pytest.approx(13.435134e-3, rel=1e-9)]
    assert {n for n, _ in r["idle_gaps"][2:]} == {"quantum"}

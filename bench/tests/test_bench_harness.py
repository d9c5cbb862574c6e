"""The benchmark's harness on the CPU: files found by name, statistics
over all requests and the whole window, kernel work from shapes alone,
the peaks table, the traffic generator, the reference's margins, and the
entry point's refusal to run without a chip."""
from __future__ import annotations

import inspect
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench.harness import stats, traffic  # noqa: E402
from bench.harness.fleet import fleet_spec  # noqa: E402
from bench.harness.spec import Bench  # noqa: E402
from bench.reference import control_plane as ref  # noqa: E402


def _bench_tree(tmp_path: Path) -> Path:
    (tmp_path / "bench" / "metrics").mkdir(parents=True)
    (tmp_path / "bench" / "kernels").mkdir()
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "limits").mkdir()
    (tmp_path / "bench" / "configs").mkdir()
    doc = {"paths": ["bench"],
           "configs": [{"name": "fleet_x",
                        "file": "bench/configs/fleet_x.json"}],
           "workloads": [{"name": "fleet_x.mix_y", "config": "fleet_x",
                          "traffic": "mix_y", "chips": 1}],
           "end_to_end": [{"name": "admit_p50_ms"}],
           "per_layer": [{"name": "new_metric",
                          "workloads": ["fleet_x.mix_y"]},
                         {"name": "other_cells_metric",
                          "workloads": ["fleet_z.mix_y"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    (tmp_path / "bench/configs/fleet_x.json").write_text('{"size": 7}')
    (tmp_path / "bench/traffic/mix_y.json").write_text('{"rate_rps": 5}')
    (tmp_path / "bench/limits/fleet_x.mix_y.json").write_text(
        '{"decision_gap": 0.5}')
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    (tmp_path / "bench/kernels/new_kernel.py").write_text(
        "def work(M, N):\n    return 2.0 * M, 4.0 * N\n")
    (tmp_path / "bench/peaks.json").write_text(
        '{"TPU v5 lite": {"flops_per_s": 1.0, "bytes_per_s": 2.0}}')
    return tmp_path


def test_files_added_are_found_by_name(tmp_path):
    bench = Bench(_bench_tree(tmp_path))
    cell = bench.cell("fleet_x.mix_y")
    assert bench.config(cell) == {"size": 7}
    assert bench.traffic(cell) == {"rate_rps": 5}
    assert bench.limits(cell) == {"decision_gap": 0.5}
    assert [m["name"] for m in bench.metrics(cell, "per_layer")] \
        == ["new_metric"]
    assert bench.reader("new_metric").read(None) == 42.0
    assert bench.kernel("new_kernel").work(M=3, N=5) == (6.0, 20.0)
    with pytest.raises(KeyError):
        bench.cell("fleet_x.missing")


def test_unknown_device_kind_is_refused(tmp_path):
    bench = Bench(_bench_tree(tmp_path))
    assert bench.peak("TPU v5 lite")["bytes_per_s"] == 2.0
    with pytest.raises(ValueError, match="no peaks"):
        bench.peak("TPU v9 imaginary")


def test_repo_peaks_name_their_source():
    peaks = json.loads((REPO / "bench" / "peaks.json").read_text())
    assert peaks["TPU v5 lite"]["flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["bytes_per_s"] == 819e9
    assert "TPU v5e" in peaks["TPU v5 lite"]["source"]


def test_percentiles_and_rate_cover_every_request_and_the_window():
    due = np.arange(1000) * 0.01                      # 100 req/s for 10 s
    decided = due + 0.002
    lat = stats.latencies(due, decided, 0.0, 10.0, 10.0)
    assert len(lat) == 1000
    assert stats.percentile(lat, 50) == pytest.approx(0.002)
    assert stats.rate(decided, 0.0, 10.0) == pytest.approx(100.0)
    # a 3 s stall: everything due in it waits for the stall's end
    stalled = decided.copy()
    hit = (due >= 4.0) & (due < 7.0)
    stalled[hit] = 7.0
    lat2 = stats.latencies(due, stalled, 0.0, 10.0, 10.0)
    assert stats.percentile(lat2, 99) > 2.9
    assert stats.percentile(lat2, 50) > stats.percentile(lat, 50)
    # requests never decided count, at their wait to the loop's end
    never = decided.copy()
    never[-20:] = np.nan
    lat3 = stats.latencies(due, never, 0.0, 10.0, 12.0)
    assert len(lat3) == 1000 and stats.percentile(lat3, 99) > 2.0
    # the rate counts only decisions inside the window, over all of it
    late = decided.copy()
    late[500:] += 20.0
    assert stats.rate(late, 0.0, 10.0) == pytest.approx(50.0)


def test_spread_is_interquartile_over_median():
    assert stats.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(
        (4.5 - 1.5) / 3.0)


@pytest.mark.parametrize("kernel", ["admit_quantum", "control_tick",
                                    "control_tick_pools"])
def test_kernel_work_depends_on_shapes_alone(kernel):
    bench = Bench(REPO)
    mod = bench.kernel(kernel)
    params = set(inspect.signature(mod.work).parameters) - {"_"}
    assert params <= {"M", "N", "P"}
    src = inspect.getsource(mod)
    assert "import jax" not in src and "repro" not in src
    shapes = {"M": 4096, "N": 1 << 17, "P": 2}
    a = mod.work(**{k: v for k, v in shapes.items() if k in params})
    b = mod.work(**{k: v for k, v in shapes.items() if k in params})
    assert a == b and a[0] > 0 and a[1] > 0
    bigger = {k: (2 * v if k == "N" else v) for k, v in shapes.items()
              if k in params}
    c = mod.work(**bigger)
    assert a[1] < c[1] < 2 * a[1] + 1
    assert c == mod.work(**bigger)


@pytest.mark.parametrize("config,mix_name", [
    ("platform_131k", "platform_steady"), ("spill_fleet", "spill_steady")])
def test_traffic_is_the_same_work_for_every_seed(config, mix_name):
    cfg = json.loads((REPO / f"bench/configs/{config}.json").read_text())
    cfg.update(keys_per_model=256)
    mix = json.loads((REPO / f"bench/traffic/{mix_name}.json").read_text())
    mix["arrivals"]["rate_rps"] = 500.0
    fleet_a = fleet_spec(cfg, 7)
    fleet_b = fleet_spec(cfg, 2**31 + 11)
    a = traffic.generate(mix, fleet_a["keys_by_rank"], 7, 2.0)
    a2 = traffic.generate(mix, fleet_a["keys_by_rank"], 7, 2.0)
    b = traffic.generate(mix, fleet_b["keys_by_rank"], 2**31 + 11, 2.0)
    assert np.array_equal(a.due, a2.due) and a.key == a2.key
    assert len(a) == len(b) == round(500.0 * 7.0)
    for field in ("input_tokens", "max_tokens", "output_tokens", "hold_s"):
        assert np.array_equal(np.sort(getattr(a, field)),
                              np.sort(getattr(b, field)))
    assert not np.array_equal(a.input_tokens, b.input_tokens)

    def model_rank(fleet):
        return {key: (m, r) for m, keys in enumerate(fleet["keys_by_rank"])
                for r, key in enumerate(keys)}

    rank_a, rank_b = model_rank(fleet_a), model_rank(fleet_b)
    assert sorted(rank_a[k] for k in a.key) == sorted(rank_b[k] for k in b.key)
    assert np.all(a.output_tokens <= a.max_tokens)


ARRIVALS = {"poisson": {"process": "poisson", "rate_rps": 400.0},
            "on_off": {"process": "on_off", "rate_rps": 400.0, "on_s": 0.5,
                       "off_s": 1.5},
            "closed": {"process": "closed", "clients": 50, "think_s": 0.25,
                       "max_rps": 400.0}}


@pytest.mark.parametrize("process", sorted(ARRIVALS))
def test_arrival_process_is_data_and_keeps_the_requests(process):
    cfg = json.loads((REPO / "bench/configs/platform_131k.json").read_text())
    cfg.update(keys_per_model=256)
    mix = json.loads((REPO / "bench/traffic/platform_steady.json")
                     .read_text())
    fleet = fleet_spec(cfg, 5)
    mix["arrivals"] = ARRIVALS[process]
    got = traffic.generate(mix, fleet["keys_by_rank"], 2**31 + 3, 3.0)
    mix["arrivals"] = ARRIVALS["poisson"]
    plain = traffic.generate(mix, fleet["keys_by_rank"], 2**31 + 3, 3.0)
    horizon = mix["burn_in_s"] + 3.0
    # the same requests, whatever the process
    assert len(got) == len(plain) == round(400.0 * horizon)
    assert np.array_equal(np.sort(got.input_tokens),
                          np.sort(plain.input_tokens))
    assert np.all(np.diff(got.due[np.isfinite(got.due)]) >= 0)
    if process == "on_off":
        assert got.due.min() >= 0 and got.due.max() < horizon
        gaps = np.diff(np.concatenate([[0.0], got.due, [horizon]]))
        assert gaps.max() >= 1.0 and not got.exhausted
        # every request falls in an on-period: on the cycle's circle,
        # the due times leave an arc of the off-period free
        on = ARRIVALS["on_off"]
        period = on["on_s"] + on["off_s"]
        r = np.sort(got.due % period)
        assert max(np.diff(r).max(), r[0] + period - r[-1]) \
            >= on["off_s"] - 1e-9
    if process == "closed":
        assert np.all(got.due[:50] == 0.0) and np.all(np.isinf(got.due[50:]))
        got.decided(0, 3, 1.0)
        assert list(got.due[50:53]) == [1.25] * 3 and got.issued == 53
        got.decided(3, len(got), 2.0)
        assert got.exhausted and np.all(np.isfinite(got.due))
    with pytest.raises(ValueError, match="unknown arrival process"):
        traffic.count({"process": "sometimes"}, 1.0)


def test_spill_routes_go_home_then_to_the_sibling_region():
    cfg = json.loads((REPO / "bench/configs/spill_fleet.json").read_text())
    cfg.update(keys_per_model=64)
    fleet = fleet_spec(cfg, 3)
    assert len(fleet["pools"]) == 8
    assert sum(len(e) for e in fleet["entitlements"].values()) == 2 * 4 * 64
    classes = {e["name"]: e["class"]
               for es in fleet["entitlements"].values() for e in es}
    for m, keys in enumerate(fleet["keys_by_rank"]):
        for rank, key in enumerate(keys):
            (home, e0), (sib, e1) = fleet["routes"][key]
            assert home.endswith(cfg["regions"][rank % 2])
            assert sib.endswith(cfg["regions"][(rank + 1) % 2])
            assert home.rsplit("-", 1)[0] == sib.rsplit("-", 1)[0]
            assert (classes[e0], classes[e1]) == ("guaranteed", "elastic")
    # a fixed set of tenants: only the names move with the seed
    other = fleet_spec(cfg, 4)
    assert [p["per_replica"] for p in other["pools"]] \
        == [p["per_replica"] for p in fleet["pools"]]


def test_bfloat16_rounding():
    assert ref.bfloat16(1.0) == 1.0
    assert ref.bfloat16(1.0 + 2**-9) == 1.0            # ties to even
    assert ref.bfloat16(1.0 + 3 * 2**-9) == 1.0 + 2**-7
    x = np.array([3.14159, 1e6, -2.5e-3])
    assert np.all(np.abs(ref.bfloat16(x) - x) <= np.abs(x) * 2**-8)


@pytest.mark.parametrize("decision,margins,gap", [
    (ref.ADMIT, (True, True, 0.5, 1.0, 0.2), 0.0),
    (ref.ADMIT, (True, True, -0.01, 1.0, 0.2), 0.01),
    (ref.ADMIT, (False, True, 0.5, 1.0, 0.2), 1.0),
    (ref.TOKEN_BUDGET, (True, True, 0.03, 1.0, 0.2), 0.03),
    (ref.TOKEN_BUDGET, (True, True, -0.03, 1.0, 0.2), 0.0),
    (ref.LOW_PRIORITY, (True, True, 0.5, 1.0, 0.001), 0.001),
    (ref.LOW_PRIORITY, (True, True, 0.5, 1.0, 0.0), 0.0),
    (ref.CONCURRENCY, (True, True, 0.5, 1.0, 0.2), 1.0),
    (ref.NOT_BOUND, (False, True, 0.5, 1.0, 0.2), 0.0),
])
def test_decision_gap_is_the_margin_that_would_make_it_right(
        decision, margins, gap):
    assert ref._gap(decision, *margins) == pytest.approx(gap)
    assert (ref._gap(ref._decide(*margins), *margins) == 0.0)


def test_entry_point_refuses_to_run_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(REPO / "bench" / "run.py"), "--workload",
         "platform_131k.steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "no chip" in out.stderr

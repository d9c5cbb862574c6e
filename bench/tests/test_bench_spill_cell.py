"""The spill fleet's cell as the benchmark finds it: ``settle_ms`` read
from hand-made benchmark spans, and the ``spill_fleet.steady`` entries
resolved by name through ``Bench`` to their configuration, mix, limits
and metrics, and the configuration's replicas of KV."""
from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench.harness import loop  # noqa: E402
from bench.harness.spec import Bench  # noqa: E402
from bench.run import Context  # noqa: E402

CELL = "spill_fleet.steady"


def _context(rows):
    spans = loop.Spans()
    spans.rows = list(rows)
    bench = Bench(REPO)
    window = SimpleNamespace(open_s=1.0, close_s=3.0)
    return bench, Context(bench, bench.cell(CELL), spans, window, None,
                          None)


def test_settle_ms_is_the_mean_settle_inside_the_window():
    bench, ctx = _context([
        ("settle", 0.5, 0.6),            # burn-in
        ("settle", 1.0, 1.004),
        ("quantum", 1.5, 1.8),
        ("settle", 2.0, 2.002),
        ("settle", 2.999, 3.5),          # runs past the close
    ])
    assert bench.reader("settle_ms").read(ctx) == pytest.approx(3.0)


def test_settle_ms_is_none_without_a_settle():
    bench, ctx = _context([("quantum", 1.5, 1.8), ("settle", 0.1, 0.2)])
    assert bench.reader("settle_ms").read(ctx) is None


def test_spill_cell_resolves_through_bench():
    bench = Bench(REPO)
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "spill_fleet", "spill_poisson", 1)
    (entry,) = [c for c in bench.doc["configs"]
                if c["name"] == "spill_fleet"]
    config = bench.config(cell)
    assert entry["reduced"] == [] and entry["source"] == config["source"]
    assert config["keys_per_model"] == 2048 and len(config["models"]) == 4
    # the mix is spill_steady's with a rate, at most the design's 4000/s
    mix = bench.traffic(cell)
    steady = json.loads((REPO / "bench/traffic/spill_steady.json")
                        .read_text())
    rate = mix["arrivals"].pop("rate_rps")
    assert 0 < rate <= 4000.0 and rate % 100 == 0
    assert {k: v for k, v in mix.items() if k != "about"} \
        == {k: v for k, v in steady.items() if k != "about"}
    assert set(bench.limits(cell)) == {"decision_gap", "tick_gap",
                                       "plan_gap"}
    names = {m["name"] for m in bench.metrics(cell, "per_layer")}
    assert names == {"quantum_ms", "tick_ms", "plan_ms", "settle_ms",
                     "admit_quantum_roofline", "control_tick_pools_roofline",
                     "device_idle_share"}
    for name in names:
        assert callable(bench.reader(name).read)
    assert {m["name"] for m in bench.metrics(cell, "end_to_end")} == {
        "admit_p50_ms", "decisions_per_s", "setup_s"}


def test_spill_fleet_pools_hold_a_real_replica_of_kv():
    from bench.harness.fleet import fleet_spec

    bench = Bench(REPO)
    config = bench.config(bench.cell(CELL))
    replica_kv = {m["pool"]: m["per_replica_kv_bytes"]
                  for m in config["models"]}
    # one value for every pool: the least of the four models' replicas
    assert config["pool"]["per_replica_kv_bytes"] == min(replica_kv.values())
    assert all(1e11 < kv < 1e12 for kv in replica_kv.values())
    by_name = {m["name"]: m for m in config["models"]}
    # the sliding layers count only their window: below full attention
    assert by_name["K-EXAONE-236B-A23B"]["kv_bytes_per_token"] < 48 * 4096
    assert by_name["command-a-plus-05-2026"]["kv_bytes_per_token"] \
        < 32 * 4096
    config = dict(config, keys_per_model=64)
    fleet = fleet_spec(config, 2**31 + 11)
    assert len(fleet["pools"]) == 8
    for pool in fleet["pools"]:
        assert pool["per_replica"][1] == min(replica_kv.values())
        assert pool["kv_bytes_per_token"] == by_name[pool["model"]][
            "kv_bytes_per_token"]

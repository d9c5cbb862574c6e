"""The program's own spans as the benchmark sees them: on the CPU, the
``compile`` spans of each quantum agree with the benchmark's
``CompileLog``; and in a small trace recorded on a TPU v5e
(``record_trace.py``, written to ``data/spans.xplane.pb.gz``) every
program span lies inside the benchmark span that called it, on the
profiler's clock, while the benchmark's own reduction reads the
benchmark's spans alone."""
from __future__ import annotations

import gzip
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))

from bench.harness import trace  # noqa: E402

#: the benchmark span around each root call of the program
CALLER = {"gateway": "quantum", "pool": "tick", "fleet": "plan"}


def _tiny_gateway():
    from bench.harness import fleet as fleet_mod

    cfg = json.loads((ROOT / "bench/configs/platform_131k.json").read_text())
    cfg["design_tokens_per_s"] *= 256 / cfg["keys_per_model"]
    cfg["keys_per_model"] = 256
    fleet = fleet_mod.fleet_spec(cfg, 12)
    return fleet, fleet_mod.build_gateway(fleet)


def test_quantum_compiles_agree_with_the_compile_log():
    import jax

    from bench.harness.device import CompileLog
    from repro.gateway import QuantumRequest

    log = CompileLog(jax)
    fleet, gw = _tiny_gateway()
    kv = fleet["pools"][0]["kv_bytes_per_token"]
    keys = fleet["keys_by_rank"][0]
    jax.clear_caches()                    # every quantum compiles anew
    for q in range(3):
        gw.handle_quantum([QuantumRequest(k, f"q{q}r{i}", 900, 200, kv)
                           for i, k in enumerate(keys[:5 + 4 * q])],
                          float(q))
    r = gw.telemetry.spans.rows()
    roots = (r["name"] == "gateway.quantum") & (r["parent"] < 0)
    spans = list(zip(r["start"][roots], r["end"][roots]))
    compiles = (r["name"] == "compile") & np.isin(r["root"], r["id"][roots])
    logged = [d for _, d, _, t in log.events
              if any(a <= t <= b for a, b in spans)]
    assert len(logged) == int(compiles.sum()) > 0
    assert sum(logged) == pytest.approx(
        float(np.sum(r["end"][compiles] - r["start"][compiles])),
        rel=1e-9)


def _host_events(path: Path):
    from jax.profiler import ProfileData

    from repro.telemetry import SPAN_NAMES

    bench, program = [], []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                row = (ev.name, int(ev.start_ns),
                       int(ev.start_ns + ev.duration_ns))
                if ev.name in trace.HOST_SPANS:
                    bench.append(row)
                elif ev.name in SPAN_NAMES:
                    program.append(row)
    return sorted(bench, key=lambda e: e[1]), sorted(program,
                                                     key=lambda e: e[1])


def test_recorded_program_spans_lie_inside_the_benchmarks(tmp_path):
    """One quantum of 12 requests, a 20 ms wait, a tick, a plan and a
    settle at 1024 tenants, traced on a TPU v5e with the program's
    spans on."""
    xplane = tmp_path / "spans.xplane.pb"
    with gzip.open(HERE / "data" / "spans.xplane.pb.gz", "rb") as src, \
            open(xplane, "wb") as dst:
        shutil.copyfileobj(src, dst)
    bench, program = _host_events(xplane)
    assert [n for n, _, _ in bench] == ["quantum", "wait_arrival", "tick",
                                        "plan", "settle"]
    from repro.telemetry import SPAN_NAMES

    # every span but ``compile`` (which the profiler records itself),
    # once each, in the order the calls ran
    assert [n for n, _, _ in program] == [
        n for n in SPAN_NAMES if n != "compile"]
    roots = {n.split(".")[0]: (s, e) for n, s, e in program
             if n in ("gateway.quantum", "pool.tick", "fleet.plan")}
    for name, start, end in program:
        family = name.split(".")[0]
        (_, lo, hi), = [b for b in bench if b[0] == CALLER[family]]
        assert lo <= start <= end <= hi, name
        assert roots[family][0] <= start <= end <= roots[family][1], name
    # the benchmark's reduction keeps its own spans only
    rows = trace.rows_of(xplane)
    assert {n for p, _, n, _, _ in rows if not p.startswith("/device:")} \
        == set(trace.HOST_SPANS) - {"dispatch"}

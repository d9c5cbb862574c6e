"""Record the small profiler trace that ``test_bench_trace.py`` pins.

    python3 bench/tests/record_trace.py      # on a TPU host

Builds the platform configuration at 1024 tenants, warms its kernels,
and traces one short stretch of the benchmark's loop by hand: a quantum
of 12 requests, an idle wait, the accounting tick, the fleet plan and a
settle, each in the benchmark's own host span.  The trace is written to
``bench/tests/data/small.xplane.pb.gz`` (gzipped, a few hundred KB), or
to the path given as the first argument."""
from __future__ import annotations

import gzip
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

OUT = HERE / "data" / "small.xplane.pb.gz"


def main(out: Path = OUT) -> int:
    import jax

    from bench.harness import fleet as fleet_mod
    from bench.harness import loop
    from bench.harness.device import NoChip, require_tpu
    from bench.harness.trace import (find_xplane, profile_options,
                                     reduce_planes, rows_of)
    from bench.run import warm_up
    from repro.gateway import QuantumRequest

    try:
        require_tpu(jax, 1)
    except NoChip as exc:
        print(f"record_trace: no chip: {exc}", file=sys.stderr)
        return 2
    cfg = json.loads((ROOT / "bench/configs/platform_131k.json").read_text())
    cfg["design_tokens_per_s"] *= 1024 / cfg["keys_per_model"]
    cfg["keys_per_model"] = 1024
    fleet = fleet_mod.fleet_spec(cfg, 12)
    gw = fleet_mod.build_gateway(fleet)
    warm_up(gw, 16)
    kv = fleet["pools"][0]["kv_bytes_per_token"]
    reqs = [QuantumRequest(k, f"r{i}", 900, 200, kv)
            for i, k in enumerate(fleet["keys_by_rank"][0][:12])]
    spans = loop.Spans(annotate=jax.profiler.TraceAnnotation)
    tmp = Path(tempfile.mkdtemp(prefix="bench-trace-"))
    jax.profiler.start_trace(str(tmp), profiler_options=profile_options(jax))
    with spans("quantum"):
        resp = gw.handle_quantum(reqs, 1.0)
    with spans("wait_arrival"):
        time.sleep(0.02)
    with spans("tick"):
        records = gw.manager.tick(1.5)
        jax.block_until_ready([p.store.device_state()
                               for p in gw.manager.pools.values()])
    with spans("plan"):
        gw.plan_quantum(1.5, records=records)
    with spans("settle"):
        gw.on_complete_batch([(r.request_id, 50, 0.5) for r in resp
                              if r.status == 200], 1.6)
    jax.profiler.stop_trace()
    out.parent.mkdir(exist_ok=True)
    xplane = find_xplane(tmp)
    with open(xplane, "rb") as src, gzip.open(out, "wb", 9) as dst:
        shutil.copyfileobj(src, dst)
    red = reduce_planes(rows_of(xplane))
    shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"bytes": out.stat().st_size, **red}))
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1]) if len(sys.argv) > 1 else OUT))

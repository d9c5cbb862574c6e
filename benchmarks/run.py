"""Benchmark harness — one entry per paper table/figure + the framework
benches.  Prints ``name,value,details`` CSV rows.

  experiment1   paper §5.2 Figs 2–4 (cross-class protection)
  experiment2   paper §5.3 Fig 5/6 + Table 2 (SLO fair share, debt)
  experiment3   fleet autoscaling + cross-pool rebalancing (closed
                control loop; plan_fleet latency at 8/64/512 pools)
  admission     control-plane throughput (scalar oracle vs unified tick)
  kernels       kernel/oracle micro-timings
  roofline      per-cell roofline table from dry-run artifacts (if
                benchmarks/artifacts/dryrun is populated)

``--quick`` runs a CI-sized smoke pass: tiny entitlement counts and
short simulation windows, no wall-clock thresholds asserted — it only
proves every benchmark path still executes (control-plane perf
regressions then surface as timing rows in the PR log).
"""
from __future__ import annotations

import os
import sys
import traceback


def _section(name):
    print(f"# --- {name} " + "-" * max(0, 60 - len(name)))


def main(quick: bool = False) -> None:
    failures = []

    _section("experiment1: cross-class protection (paper Figs 2-4)")
    try:
        from benchmarks.experiment1_protection import main as e1
        # TELEMETRY_snapshot.json + TRACE_overload.json: the registry
        # snapshot and Perfetto timeline of the overload incident —
        # uploaded as CI artifacts
        e1(duration=30.0 if quick else 90.0,
           artifacts_dir=os.path.join(
               os.path.dirname(__file__), "artifacts"))
    except Exception:                              # noqa: BLE001
        failures.append("experiment1")
        traceback.print_exc()

    _section("experiment2: SLO-aware fair share (paper Fig 5/6, Tab 2)")
    try:
        from benchmarks.experiment2_fairshare import main as e2
        e2(duration=60.0 if quick else 300.0)
    except Exception:                              # noqa: BLE001
        failures.append("experiment2")
        traceback.print_exc()

    _section("experiment3: fleet autoscaling + rebalancing")
    try:
        from benchmarks.experiment3_autoscale import main as e3
        # BENCH_autoscale.json: plan_fleet latency (8/64/512 pools) +
        # the surge P99 trajectory — uploaded as a CI artifact.  The
        # scenario's event timeline (surge end 65 s, scale-down after
        # cooldown) is fixed, so even --quick must run past it.
        e3(duration=80.0 if quick else 90.0,
           out_json=os.path.join(
               os.path.dirname(__file__), "artifacts",
               "BENCH_autoscale.json"))
    except Exception:                              # noqa: BLE001
        failures.append("experiment3")
        traceback.print_exc()

    _section("admission throughput (scalar oracle vs unified tick)")
    try:
        from benchmarks.admission_throughput import main as adm
        # BENCH_admission.json: scalar-vs-quantum gateway decisions/s
        # trajectory — uploaded as a CI artifact
        adm(quick=quick, out_json=os.path.join(
            os.path.dirname(__file__), "artifacts",
            "BENCH_admission.json"))
    except Exception:                              # noqa: BLE001
        failures.append("admission")
        traceback.print_exc()

    _section("kernel micro-bench")
    try:
        from benchmarks.kernel_bench import main as kb
        kb()
    except Exception:                              # noqa: BLE001
        failures.append("kernels")
        traceback.print_exc()

    _section("roofline (from dry-run artifacts)")
    art = os.path.join(os.path.dirname(__file__), "artifacts", "dryrun")
    if os.path.isdir(art) and os.listdir(art):
        try:
            from repro.launch.roofline import analyze, load_artifacts
            print("arch,shape,mesh,chips,compute_s,memory_s,"
                  "collective_s,dominant,useful_ratio")
            for a in load_artifacts(art):
                r = analyze(a)
                if r is None:
                    print(f"{a['arch']},{a['shape']},{a['mesh']},,,,,SKIP,")
                else:
                    print(f"{r.arch},{r.shape},{r.mesh},{r.chips},"
                          f"{r.compute_s:.3e},{r.memory_s:.3e},"
                          f"{r.collective_s:.3e},{r.dominant},"
                          f"{r.useful_ratio:.3f}")
        except Exception:                          # noqa: BLE001
            failures.append("roofline")
            traceback.print_exc()
    else:
        print("roofline,skipped,no dry-run artifacts "
              "(run benchmarks/run_dryrun_sweep.sh)")

    if failures:
        print(f"# FAILURES: {failures}", file=sys.stderr)
        sys.exit(1)
    print("# all benchmarks completed")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(quick="--quick" in sys.argv)

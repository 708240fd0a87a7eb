"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and writes the same rows to
``BENCH_comm.json`` (override with --json=PATH, disable with --json=) so the
perf trajectory is machine-trackable across PRs.

Multi-device benches need >1 host device; when launched with a single CPU
device this driver re-execs itself with 8 host devices (opt out with
REPRO_BENCH_NO_REEXEC=1 or --single-device).  Every row is a host-CPU
rehearsal: on an accelerator the driver exits at once (``chip_smoke.py``
is the chip path).
"""
import json
import os
import sys


def _ensure_devices():
    if os.environ.get("REPRO_BENCH_NO_REEXEC"):
        return
    if "--single-device" in sys.argv:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
        os.environ["REPRO_BENCH_NO_REEXEC"] = "1"
        os.execv(sys.executable, [sys.executable, "-m", "benchmarks.run"]
                 + sys.argv[1:])


def main() -> None:
    _ensure_devices()
    from repro.launch import compile_cache
    from repro.launch.mesh import exit_unless_host_cpu
    exit_unless_host_cpu("python -m benchmarks.run")
    compile_cache.configure()
    from benchmarks import (b_eff, e2e_objective, fault_tolerance,
                            lm_collectives, lm_roofline, plan_store,
                            reliability, resources, serving, swe_scaling,
                            topology_hops)

    print("name,us_per_call,derived")
    modules = [("b_eff(fig4)", b_eff), ("resources(fig3)", resources),
               ("swe(fig9,fig10,table1)", swe_scaling),
               ("lm_roofline", lm_roofline),
               ("lm_collectives", lm_collectives),
               ("e2e_objective", e2e_objective),
               ("topology_hops", topology_hops),
               ("plan_store", plan_store),
               ("fault_tolerance", fault_tolerance),
               ("reliability", reliability),
               ("serving", serving)]
    only = None
    json_path = "BENCH_comm.json"
    for a in sys.argv[1:]:
        if a.startswith("--only="):
            only = a.split("=", 1)[1]
        if a.startswith("--json="):
            json_path = a.split("=", 1)[1]
    results = {}
    ok_labels = []
    for label, mod in modules:
        if only and only not in label:
            continue
        try:
            for name, us, derived in mod.run():
                print(f"{name},{us:.3f},{derived}")
                results[name] = {"us_per_call": round(us, 3),
                                 "derived": derived}
            ok_labels.append(label)
        except Exception as e:  # noqa: BLE001
            print(f"{label}_ERROR,0,{type(e).__name__}:{e}")
            results[f"{label}_ERROR"] = {
                "us_per_call": 0.0, "derived": f"{type(e).__name__}:{e}"}
    # Overlap report: the Eq. 2 overlap term's predicted fused->overlapped
    # speedup next to the measured one (rows from swe_scaling.fig11).
    overlap_rows = {k: v for k, v in results.items()
                    if k.startswith("fig11_speedup")}
    for name, row in sorted(overlap_rows.items()):
        print(f"# overlap {name}: measured {row['us_per_call']:.2f}x, "
              f"{row['derived']}", file=sys.stderr)
    # E2E-objective report: how much e2e the bare-latency winner leaves on
    # the table per consumer loop (rows from e2e_objective).
    for name, row in sorted(results.items()):
        if name.startswith("e2e_gain_"):
            print(f"# e2e objective {name}: lat-winner/e2e-winner = "
                  f"{row['us_per_call']:.2f}x, {row['derived']}",
                  file=sys.stderr)
    # Hop-scaling report: measured multi-hop cost next to the Eq. 1
    # prediction (rows from topology_hops on the virtual 2x4 torus).
    for name, row in sorted(results.items()):
        if name.startswith("topo_hop_ratio"):
            print(f"# hop scaling {name}: measured "
                  f"{row['us_per_call']:.2f}x, {row['derived']}",
                  file=sys.stderr)
    # Plan-store report: what disk persistence saves a fresh process
    # (rows from plan_store; smaller ratio = better warm start).
    for name, row in sorted(results.items()):
        if name == "pstore_warm_ratio":
            print(f"# plan store {name}: fresh-process warm/cold = "
                  f"{row['us_per_call']:.2f}x, {row['derived']}",
                  file=sys.stderr)
    # Fault-tolerance report: model-based re-selection vs the resweep the
    # elastic recovery path avoids (rows from fault_tolerance).
    for name, row in sorted(results.items()):
        if name == "ft_reselect_speedup":
            print(f"# fault tolerance {name}: resweep/reselect = "
                  f"{row['us_per_call']:.0f}x, {row['derived']}",
                  file=sys.stderr)
    # Serving report: decode cost under its own winner vs the prefill
    # winner, and whether 48 ranks resolved phase-distinct configs
    # (rows from serving).
    for name, row in sorted(results.items()):
        if name in ("srv_phase_win", "srv_distinct_48"):
            print(f"# serving {name}: {row['us_per_call']:.2f}, "
                  f"{row['derived']}", file=sys.stderr)
    if json_path:
        # Merge into any existing file so a partial (--only=...) run updates
        # its rows without destroying the rest of the benchmark record.
        rows = {}
        if os.path.exists(json_path):
            try:
                with open(json_path) as f:
                    rows = json.load(f).get("rows", {})
            except (json.JSONDecodeError, OSError):
                rows = {}
        rows.update(results)
        for label in ok_labels:   # a clean run clears the module's old error
            rows.pop(f"{label}_ERROR", None)
        with open(json_path, "w") as f:
            json.dump({"schema": "repro-bench-v1", "rows": rows}, f,
                      indent=1, sort_keys=True)
        print(f"# wrote {len(results)} rows ({len(rows)} total) -> {json_path}",
              file=sys.stderr)


if __name__ == "__main__":
    main()

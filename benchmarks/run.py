"""Benchmark driver — one experiment per paper table/figure.

Prints each experiment's human-readable table, then a final CSV block:
``name,us_per_call,derived``.

  BENCH_N=10000 PYTHONPATH=src python -m benchmarks.run        # paper scale
  PYTHONPATH=src python -m benchmarks.run                      # default 6000
  BENCH_N=200 python -m benchmarks.run table1_success_rate     # smoke subset

Scenario shaping (the event-driven runtime's `Scenario` hooks):

  python -m benchmarks.run table1_success_rate --scenario burst
  python -m benchmarks.run fig4_processing_time --scenario bwdrop

`--scenario` picks a registered arrival/bandwidth scenario (burst, diurnal,
bwdrop, overload, cloud-outage, trace, poisson) for the shared simulation
matrix; `--admission` gives PerLLM admission control; `--topology
edge-cloud` swaps the per-server bandwidth model for the explicit link
graph. Equivalent env vars: BENCH_SCENARIO / BENCH_ADMISSION /
BENCH_TOPOLOGY. (Every cell is event-driven; the slotted runtime and its
`--runtime` flag were retired.)

`--json PATH` additionally writes the run's derived metrics as JSON —
the artifact the CI regression gate feeds to
`benchmarks/compare_baseline.py`.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import traceback


def _parse_derived(derived: str) -> dict:
    """`k=v;k2=v2` pairs -> numeric metrics (%/x suffixes stripped)."""
    metrics = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        key, val = part.split("=", 1)
        with contextlib.suppress(ValueError):
            metrics[key.strip()] = float(val.strip().rstrip("%x"))
    return metrics


def write_json(rows, path: str) -> None:
    """Dump each experiment's wall time + parsed derived metrics.
    `us_per_call` rides inside `metrics` too, so the baseline gate can
    hold the line on simulator wall-clock like any other metric."""
    out = {}
    for r in rows:
        name, us, derived = r.split(",", 2)
        metrics = _parse_derived(derived)
        metrics["us_per_call"] = float(us)
        out[name] = {"us_per_call": float(us), "derived": derived,
                     "metrics": metrics}
    with open(path, "w") as fh:
        json.dump(out, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(f"# wrote {path}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        prog="benchmarks.run",
        description="Run the paper-reproduction benchmark suite.")
    ap.add_argument("experiments", nargs="*",
                    help="subset of experiments to run (default: all)")
    ap.add_argument("--scenario", default=None, metavar="NAME",
                    help="arrival/bandwidth scenario for the simulation "
                         "matrix: burst, diurnal, bwdrop, overload, "
                         "cloud-outage, trace, poisson "
                         "(default: stationary poisson)")
    ap.add_argument("--admission", action="store_true",
                    help="run PerLLM with admission control: infeasible "
                         "requests are shed (SLO-violation cost) instead "
                         "of queueing forever")
    ap.add_argument("--topology", default=None,
                    choices=("degenerate", "edge-cloud"),
                    help="network model for the simulation matrix: the "
                         "legacy per-server links (default) or the "
                         "explicit edge-cloud link graph")
    ap.add_argument("--tiers", action="store_true",
                    help="give every server the stock DVFS frequency "
                         "ladder: PerLLM schedules (server, tier) pairs "
                         "and fig6 reports the learned-tier energy cut "
                         "vs the fixed-nominal comparator")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write derived metrics as JSON (the CI "
                         "regression-gate artifact)")
    args = ap.parse_args(argv if argv is not None else sys.argv[1:])
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # benchmarks.common reads these at import time, so set them before the
    # experiment imports below
    if args.scenario:
        from repro.core import available_scenarios, make_scenario
        try:
            make_scenario(args.scenario)
        except KeyError:
            sys.exit(f"unknown scenario {args.scenario!r}; choose from "
                     + ", ".join(available_scenarios()))
        except TypeError:
            sys.exit(f"scenario {args.scenario!r} needs constructor "
                     "arguments (e.g. trace times) — use it "
                     "programmatically via repro.core.make_scenario")
        os.environ["BENCH_SCENARIO"] = args.scenario
    if args.admission:
        os.environ["BENCH_ADMISSION"] = "1"
    if args.topology:
        os.environ["BENCH_TOPOLOGY"] = args.topology
    if args.tiers:
        os.environ["BENCH_TIERS"] = "1"
    rebind = (args.scenario or args.admission
              or args.topology or args.tiers)
    if rebind and "benchmarks.common" in sys.modules:
        # already imported (programmatic/repeat use): env vars were read at
        # import time, so rebind and drop the stale cell cache
        common = sys.modules["benchmarks.common"]
        if args.scenario:
            common.SCENARIO = args.scenario
        if args.admission:
            common.ADMISSION = True
        if args.topology:
            common.TOPOLOGY = args.topology
        if args.tiers:
            common.TIERS = True
        common.run_cell.cache_clear()

    from benchmarks import (
        ablation_csucb, fig2_motivation, fig4_processing_time,
        fig5_throughput, fig6_energy, hetero_edges, kv_pressure,
        regret_bound, roofline, table1_success_rate, tpu_cloud,
    )
    experiments = [
        ("fig2_motivation", fig2_motivation.run),
        ("table1_success_rate", table1_success_rate.run),
        ("fig4_processing_time", fig4_processing_time.run),
        ("fig5_throughput", fig5_throughput.run),
        ("fig6_energy", fig6_energy.run),
        ("regret_bound", regret_bound.run),
        ("ablation_csucb", ablation_csucb.run),
        ("kv_pressure", kv_pressure.run),
        ("tpu_cloud", tpu_cloud.run),
        ("hetero_edges", hetero_edges.run),
        ("roofline", roofline.run),
    ]
    selected = args.experiments
    if selected:
        known = {name for name, _ in experiments}
        unknown = [s for s in selected if s not in known]
        if unknown:
            sys.exit(f"unknown experiment(s) {unknown}; "
                     f"choose from {sorted(known)}")
        experiments = [(n, f) for n, f in experiments if n in selected]
    rows = []
    for name, fn in experiments:
        print(f"\n===== {name} =====")
        try:
            rows.append(fn())
        except Exception:  # noqa: BLE001 — keep the suite running
            traceback.print_exc()
            rows.append(f"{name},0.0,ERROR")
    print("\n# name,us_per_call,derived")
    for r in rows:
        print(r)
    json_path = args.json or os.environ.get("BENCH_JSON")
    if json_path:
        write_json(rows, json_path)
    if any(r.endswith("ERROR") for r in rows):
        sys.exit(1)


if __name__ == "__main__":
    main()

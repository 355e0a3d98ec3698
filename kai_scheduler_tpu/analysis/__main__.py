"""``python -m kai_scheduler_tpu.analysis`` — the kai-lint CLI.

Default run: layer-1 AST lint over the package (the KAI0xx trace-safety
rules plus the KAI1xx kai-race concurrency pass), the layer-2 jaxpr
probe, the layer-4 kai-cost audit, and the layer-5 kai-comms sharding
audit (one shared jaxpr walk feeds probe, cost, and comms).  Exit
status is nonzero on any non-baselined finding, so the command doubles
as the CI gate (``scripts/lint.py`` wraps the lint-only fast path for
pre-commit).

    python -m kai_scheduler_tpu.analysis            # lint+probe+cost+comms
    python -m kai_scheduler_tpu.analysis --no-probe   # AST lint only
    python -m kai_scheduler_tpu.analysis --race       # kai-race only
    python -m kai_scheduler_tpu.analysis --cost       # kai-cost only
    python -m kai_scheduler_tpu.analysis --cost --scaling   # + N-growth fit
    python -m kai_scheduler_tpu.analysis --comms      # kai-comms only
    python -m kai_scheduler_tpu.analysis --comms --scaling  # + comm-vs-d fit
    python -m kai_scheduler_tpu.analysis --json       # machine output
    python -m kai_scheduler_tpu.analysis --list-rules
    python -m kai_scheduler_tpu.analysis --probe --update-baseline
    python -m kai_scheduler_tpu.analysis --update-baseline  # ALL baselines
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m kai_scheduler_tpu.analysis",
        description="kai-lint: trace-safety, determinism, and "
                    "recompile-hazard analysis for the TPU hot path")
    ap.add_argument("--root", default=None,
                    help="repo root (default: the package's parent)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable output")
    ap.add_argument("--select", default=None,
                    help="comma-separated KAI codes to run (lint)")
    ap.add_argument("--baseline", default=None,
                    help="baseline JSON for the lint layer (default: "
                         "the package baseline.json)")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--no-probe", action="store_true",
                      help="skip the jaxpr probe (AST lint only)")
    mode.add_argument("--probe", action="store_true",
                      help="jaxpr probe only (skip the AST lint)")
    mode.add_argument("--race", action="store_true",
                      help="kai-race concurrency pass only (KAI1xx; "
                           "jax-free)")
    mode.add_argument("--cost", action="store_true",
                      help="kai-cost jaxpr dataflow audit only "
                           "(KAI2xx: liveness peak-memory, FLOPs, "
                           "traffic, blowup)")
    mode.add_argument("--comms", action="store_true",
                      help="kai-comms sharding audit only (KAI3xx: "
                           "PartitionSpec propagation, collective "
                           "byte budgets, declared-vs-inferred "
                           "sharding drift, HLO cross-validation)")
    ap.add_argument("--ops", default=None,
                    help="comma-separated op names for the probe/cost/"
                         "comms stages")
    ap.add_argument("--scaling", action="store_true",
                    help="scaling mode: the cost stage fits the "
                         "peak-memory growth exponent over 2-3 node "
                         "widths; the comms stage fits modeled comm "
                         "bytes over device counts {2,4,8} (reported, "
                         "never a failure)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the measured stats in baseline.json "
                         "(probe stage), cost_baseline.json (cost "
                         "stage) and comm_baseline.json (comms stage) "
                         "— a default full run refreshes all three in "
                         "one invocation, together or not at all")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    from .engine import lint_package, load_baseline, rule_catalog
    if args.list_rules:
        for code, title in rule_catalog().items():
            print(f"{code}  {title}")
        return 0

    pkg_dir = os.path.dirname(os.path.abspath(__file__))
    root = args.root or os.path.dirname(os.path.dirname(pkg_dir))
    baseline_path = args.baseline or os.path.join(pkg_dir,
                                                  "baseline.json")
    out: dict = {"findings": [], "probe": []}
    failed = False

    #: stage selection — default (no mode flag) runs lint + probe +
    #: cost + comms; each mode flag narrows to its own stage
    run_probe_stage = not (args.no_probe or args.cost or args.race
                           or args.comms)
    run_cost_stage = args.cost or not (args.no_probe or args.probe
                                       or args.race or args.comms)
    run_comms_stage = args.comms or not (args.no_probe or args.probe
                                         or args.race or args.cost)

    if args.scaling and not (run_cost_stage or run_comms_stage):
        # a mode that skips both scaling-capable stages would silently
        # drop the exponent report — a clean exit with no scaling
        # output reads as "nothing super-linear / nothing to fit"
        ap.error("--scaling requires the kai-cost or kai-comms stage "
                 "(drop the mode flag, or use --cost / --comms)")
    if args.select and any(c.startswith(("KAI2", "KAI3"))
                           for c in args.select.split(",")):
        # KAI2xx/KAI3xx are program-level checks (costmodel.py /
        # comms.py), not engine rules: the lint select filter would
        # match nothing and print a FALSE "0 findings" clean bill
        ap.error("KAI2xx/KAI3xx rules are jaxpr-level — run them via "
                 "--cost / --comms (they are not --select-able lint "
                 "rules)")

    if not args.probe and not args.cost and not args.comms:
        baseline = (load_baseline(baseline_path)
                    if os.path.exists(baseline_path) else [])
        select = (args.select.split(",") if args.select else None)
        if args.race:
            from .concurrency import race_codes
            select = list(race_codes()) if select is None else [
                c for c in select if c in race_codes()]
            if not select:
                # --select named no KAI1xx code: running zero rules
                # would print a FALSE "0 findings" clean bill
                ap.error("--race with --select requires at least one "
                         "KAI1xx code")
        res = lint_package(root, select=select, baseline=baseline)
        out["findings"] = [f.__dict__ for f in res.findings]
        out["baselined"] = res.baselined
        if res.race is not None:
            # the kai-race layer's report: discovered thread roots and
            # the KAI1xx slice of the findings (consumed by the CLI
            # smoke test and any tooling watching the race surface)
            race_findings = [f.__dict__ for f in res.findings
                             if f.code.startswith("KAI1")]
            out["race"] = {
                "thread_roots": {
                    r.root_id: {"kind": r.kind, "multi": r.multi}
                    for r in res.race.roots},
                "findings": race_findings,
                "live_annotations": res.race.live_annotations,
                "declared_attrs": len(res.race.disciplines),
            }
        if not args.as_json:
            for f in res.findings:
                print(f.render())
            n = len(res.findings)
            extra = ""
            if res.race is not None:
                extra = (f", {len(res.race.roots)} thread roots, "
                         f"{res.race.live_annotations} live guarded-by "
                         f"annotations")
            print(f"kai-lint: {n} finding{'s' * (n != 1)} "
                  f"({res.raw_count} raw, {res.baselined} baselined, "
                  f"{len(res.stale_suppressions)} stale suppressions"
                  f"{extra})")
        failed |= bool(res.findings)

    if args.race:
        if args.as_json:
            json.dump(out, sys.stdout, indent=2, default=str)
            print()
        return 1 if failed else 0

    if run_comms_stage:
        # the lowering stage jits against an 8-way mesh; the flag must
        # land before the CPU backend's first init (no-op afterwards)
        from ..parallel.mesh import ensure_virtual_cpu_devices
        ensure_virtual_cpu_devices()

    names = args.ops.split(",") if args.ops else None
    shared_traces = None
    if run_probe_stage + run_cost_stage + run_comms_stage >= 2:
        # ONE shared per-entry jaxpr walk feeds every jax layer —
        # tracing the fused entries costs seconds each, never pay it
        # twice (or three times)
        from .trace_probe import trace_entries
        shared_traces = trace_entries(names)

    #: joint-refresh bookkeeping: when several stages run with
    #: --update-baseline, the files rewrite together or not at all (a
    #: half-refresh would absorb cost growth caused by the very change
    #: the probe blocked on, or vice versa) — the LAST jax stage to
    #: run performs the deferred writes
    last_jax_stage = ("comms" if run_comms_stage else
                      "cost" if run_cost_stage else "probe")
    probe_update_ok = None      # None = probe stage ran no update
    probe_reports = None
    cost_update_ok = None       # None = cost stage ran no update
    cost_reports_pending = None

    if run_probe_stage:
        from .trace_probe import (check_against_baseline,
                                  check_invariants, load_stats_baseline,
                                  run_probe, update_baseline)
        reports = run_probe(names, traces=shared_traces)
        if args.update_baseline:
            # the baseline only absorbs eqn/const stats; callbacks,
            # f64, and cache misses have no legitimate new value and
            # still fail (and block the rewrite) here
            problems = check_invariants(reports)
            probe_update_ok = not problems
            if problems:
                if not args.as_json:
                    print("probe baseline NOT updated — invariant "
                          "failures first:")
            elif last_jax_stage == "probe":
                update_baseline(reports, baseline_path)
                if not args.as_json:
                    print(f"probe baseline updated: {baseline_path}")
            else:
                # deferred until the last jax stage clears its gates
                probe_reports = reports
        else:
            stats = (load_stats_baseline(baseline_path)
                     if os.path.exists(baseline_path) else {})
            problems = check_against_baseline(
                reports, stats, full_coverage=not args.ops)
        out["probe"] = [r.__dict__ for r in reports]
        out["probe_problems"] = problems
        if not args.as_json:
            for r in reports:
                hit = {True: "cache-hit", False: "CACHE-MISS",
                       None: "cache-n/a"}[r.cache_hit]
                print(f"probe {r.name}: {r.eqns} eqns, "
                      f"{r.const_bytes}B consts, {hit}")
            for p in problems:
                print(f"PROBE FAIL: {p}")
        failed |= bool(problems)

    if run_cost_stage:
        from . import costmodel
        cost_path = costmodel.COST_BASELINE_PATH
        cost_base = (costmodel.load_cost_baseline(cost_path)
                     if os.path.exists(cost_path) else {})
        reports = costmodel.run_cost(
            names, traces=shared_traces,
            baseline=cost_base.get("entries", {}))
        findings = costmodel.cost_findings(reports, cost_base)
        if args.update_baseline:
            # stats (peak/FLOPs/traffic/blowup ratios) are absorbed
            problems = []
            cost_update_ok = probe_update_ok is not False
            if not cost_update_ok:
                if not args.as_json:
                    print("cost baseline NOT updated — probe "
                          "invariant failures blocked the joint "
                          "refresh")
            elif last_jax_stage != "cost":
                # deferred until the comms stage verifies lowering
                cost_reports_pending = reports
                findings = []
            else:
                costmodel.update_cost_baseline(reports, cost_path)
                findings = []
                if not args.as_json:
                    print(f"cost baseline updated: {cost_path}")
                if probe_update_ok:
                    from .trace_probe import update_baseline
                    update_baseline(probe_reports, baseline_path)
                    if not args.as_json:
                        print(f"probe baseline updated: "
                              f"{baseline_path}")
        else:
            problems = costmodel.check_against_cost_baseline(
                reports, cost_base, full_coverage=not args.ops)
        scaling = (costmodel.scaling_report() if args.scaling
                   else None)
        out["cost"] = [dataclasses.asdict(r) for r in reports]
        out["cost_problems"] = problems
        out["cost_findings"] = [f.__dict__ for f in findings]
        if scaling is not None:
            out["cost_scaling"] = scaling
        if not args.as_json:
            for r in reports:
                extra = ""
                if r.unknown_prims:
                    extra += (f", {sum(r.unknown_prims.values())} "
                              f"bytes-only eqns")
                print(f"cost {r.name}: peak "
                      f"{r.peak_live_bytes / 1e6:.2f}MB, "
                      f"{r.flops / 1e6:.2f} MFLOP, traffic "
                      f"{r.traffic_bytes / 1e6:.2f}MB, blowup "
                      f"{r.max_blowup}x{extra}")
            if scaling is not None:
                for name, row in sorted(scaling["entries"].items()):
                    flag = ("  ** SUPER-LINEAR **"
                            if row["superlinear"] else "")
                    print(f"cost-scaling {name}: peak exponent "
                          f"{row['exponent']} over nodes "
                          f"{scaling['node_counts']}{flag}")
            for f in findings:
                print(f.render())
            for p in problems:
                print(f"COST FAIL: {p}")
        failed |= bool(problems) or bool(findings)

    if run_comms_stage:
        from . import comms
        comm_path = comms.COMM_BASELINE_PATH
        comm_base = (comms.load_comm_baseline(comm_path)
                     if os.path.exists(comm_path) else {})
        reports = comms.run_comms(names, traces=shared_traces)
        # KAI302 drift is mesh-level, not per-entry: always checked
        # when the stage runs, regardless of --ops narrowing
        drift = comms.check_declared_shardings()
        findings = comms.comm_findings(reports, comm_base, extra=drift)
        lowering_names = tuple(
            n for n in comms.LOWERING_ENTRIES
            if names is None or n in names)
        lowering = (comms.lowering_check(lowering_names,
                                         reports=reports)
                    if lowering_names else [])
        lowering_probs = comms.lowering_problems(lowering)
        if args.update_baseline:
            # measured collective counts / byte totals are absorbed;
            # KAI3xx findings (absolute-threshold rules the refresh
            # cannot absorb — only a hand-justified baseline row can)
            # and a failed (or UNVERIFIABLE) lowering cross-validation
            # have no legitimate new value, so they block the rewrite
            # — and hold the deferred probe/cost writes back too,
            # joint or nothing
            kai3 = [f for f in findings if f.code.startswith("KAI3")]
            problems = list(lowering_probs)
            if kai3 or problems:
                if not args.as_json:
                    print("comm baseline NOT updated — sharding "
                          "drift / lowering failures first:")
                    if cost_update_ok:
                        print("cost baseline NOT updated — comms "
                              "stage blocked the joint refresh")
                    if probe_update_ok:
                        print("probe baseline NOT updated — comms "
                              "stage blocked the joint refresh")
            elif probe_update_ok is False:
                if not args.as_json:
                    print("comm baseline NOT updated — probe invariant "
                          "failures blocked the joint refresh")
            else:
                comms.update_comm_baseline(reports, comm_path)
                if not args.as_json:
                    print(f"comm baseline updated: {comm_path}")
                if cost_reports_pending is not None:
                    costmodel.update_cost_baseline(
                        cost_reports_pending, cost_path)
                    if not args.as_json:
                        print(f"cost baseline updated: {cost_path}")
                if probe_update_ok:
                    from .trace_probe import update_baseline
                    update_baseline(probe_reports, baseline_path)
                    if not args.as_json:
                        print(f"probe baseline updated: "
                              f"{baseline_path}")
        else:
            problems = comms.check_against_comm_baseline(
                reports, comm_base, full_coverage=not args.ops)
            problems += lowering_probs
        scaling = (comms.comm_scaling_report(reports=reports)
                   if args.scaling else None)
        out["comms"] = [r.doc() for r in reports]
        out["comms_problems"] = problems
        out["comms_findings"] = [f.__dict__ for f in findings]
        out["comms_lowering"] = lowering
        if scaling is not None:
            out["comms_scaling"] = scaling
        if not args.as_json:
            for r in reports:
                kinds = ",".join(r.kinds) if r.kinds else "none"
                print(f"comms {r.name}: {r.collective_sites} "
                      f"collective sites, "
                      f"{r.comm_bytes / 1e6:.2f}MB modeled "
                      f"({r.loop_comm_bytes / 1e6:.2f}MB under "
                      f"loops), kinds [{kinds}]")
            for d in lowering:
                mark = "verified" if d["verified"] else "UNVERIFIED"
                print(f"comms-lowering {d['entry']}: {mark} on "
                      f"{d['num_devices']} devices, hlo "
                      f"{d['hlo']}")
            if scaling is not None:
                for name, row in sorted(scaling["entries"].items()):
                    flag = ("" if row["sublinear"]
                            else "  ** SUPRA-LINEAR **")
                    print(f"comms-scaling {name}: comm-bytes "
                          f"exponent {row['exponent']} over devices "
                          f"{scaling['device_counts']}{flag}")
            for f in findings:
                print(f.render())
            for p in problems:
                print(f"COMMS FAIL: {p}")
        failed |= bool(problems) or bool(findings)
        if args.update_baseline and probe_update_ok is False:
            failed = True

    if args.as_json:
        json.dump(out, sys.stdout, indent=2, default=str)
        print()
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

"""Unified experiment runner with regression gates (ROADMAP item 5).

Enumerates experiment configs (domain x mode x path x replicas/devices,
see ``benchmarks.experiments``), runs each in a subprocess with its own
environment (XLA device counts must be committed before jax imports —
this is what lets one invocation bench 1-device serving *and* the
4-forced-host-device cluster), collects everything into one
``repro.bench/1`` document (``benchmarks.schema``), and optionally
diffs it against the committed ``BENCH_baselines.json`` with
core-count-aware tolerance gates: hard gates (drift ratio, LEE,
zero-drop/zero-loss counts, byte accounting) fail the run on any
machine at any size; soft perf gates (throughput, latency, speedup)
apply a relative band and only compare on matching core counts.

    # CI: smoke-size every domain, enforce the hard gates
    PYTHONPATH=src python -m benchmarks.run --smoke --diff-baselines

    # full suite on the reference machine, refresh the committed docs
    PYTHONPATH=src python -m benchmarks.run --write-domain-docs
    PYTHONPATH=src python -m benchmarks.run --refresh-baselines

    # re-gate an existing results document without rerunning anything
    PYTHONPATH=src python -m benchmarks.run --diff-only --results out.json

Exit codes: 0 clean, 1 an experiment crashed, 2 a regression gate
failed. See docs/experiments.md for axes, schema, and gate policy.
The legacy paper-table / roofline analysis sections remain available
via ``--section tables|roofline|kernels``.
"""
from __future__ import annotations

import argparse
import json
import sys

from benchmarks import experiments, schema


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="benchmarks.run",
        description="unified experiment runner with regression gates")
    ap.add_argument("--domains", nargs="+",
                    choices=sorted(experiments.DOMAINS),
                    help="subset of domains (default: all five)")
    ap.add_argument("--modes", nargs="+",
                    choices=["fp32", "w8a8", "w4a8"],
                    help="expand the quantization-mode axis")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized runs (soft perf gates are skipped; "
                         "hard correctness gates still apply)")
    ap.add_argument("--out", default="BENCH_experiments.json",
                    help="combined results document path")
    ap.add_argument("--work-dir", default="/tmp/repro_experiments",
                    help="scratch dir for per-experiment config/result JSON")
    ap.add_argument("--timeout-s", type=float, default=3600.0,
                    help="per-experiment subprocess timeout")
    ap.add_argument("--diff-baselines", action="store_true",
                    help="gate the results against --baselines; exit 2 "
                         "on regression")
    ap.add_argument("--baselines", default=experiments.BASELINES_PATH)
    ap.add_argument("--refresh-baselines", action="store_true",
                    help="derive --baselines from the committed per-domain "
                         "BENCH_*.json documents and exit")
    ap.add_argument("--write-domain-docs", action="store_true",
                    help="after a full (non-smoke) run, rewrite each "
                         "domain's committed BENCH_*.json from the results")
    ap.add_argument("--list", action="store_true",
                    help="print the enumerated configs and exit")
    ap.add_argument("--extra", default=None,
                    help="JSON dict of bench-arg overrides applied to every "
                         "config (tests use this to shrink below smoke size)")
    # re-gate an existing document without running anything
    ap.add_argument("--diff-only", action="store_true",
                    help="load --results and gate it against --baselines")
    ap.add_argument("--results", default=None,
                    help="results document for --diff-only")
    # internal: the subprocess-isolated child entrypoint
    ap.add_argument("--run-one", metavar="CONFIG_JSON", default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--result-out", metavar="RESULT_JSON", default=None,
                    help=argparse.SUPPRESS)
    # legacy analysis sections (paper tables / roofline CSV harness)
    ap.add_argument("--section", default=None,
                    choices=["tables", "roofline", "kernels"],
                    help="legacy analysis sections; kernels now also runs "
                         "as a domain of the experiment runner")
    return ap


def _diff(doc, args, expected=None) -> int:
    baselines = schema.load_baselines(args.baselines)
    report = schema.diff_against_baselines(doc, baselines,
                                           expected_fingerprints=expected)
    print(f"\n-- regression gates vs {args.baselines} --")
    print(report.render())
    if not report.ok:
        print("REGRESSION: one or more gates failed", file=sys.stderr)
        return 2
    print("all gates clean")
    return 0


def main(argv=None) -> int:
    args = _parser().parse_args(argv)

    if args.section:
        # legacy CSV harness sections, untouched by the runner
        if args.section == "tables":
            from benchmarks import paper_tables
            paper_tables.main()
        elif args.section == "roofline":
            from benchmarks import roofline
            roofline.main()
        else:
            from benchmarks import kernel_bench
            kernel_bench.main([])
        return 0

    if args.run_one:
        # child process: env (devices, threads) already committed by the
        # parent; run exactly one config and write its result. Only the
        # child imports jax: the parent stays off the chip.
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        with open(args.run_one) as f:
            config = experiments.ExperimentConfig.from_json(json.load(f))
        result = experiments.run_config_inprocess(config)
        with open(args.result_out, "w") as f:
            json.dump(result.to_json(), f, indent=2)
        return 0

    if args.refresh_baselines:
        baselines = experiments.refresh_baselines(args.domains)
        with open(args.baselines, "w") as f:
            json.dump(baselines, f, indent=2)
        n = sum(len(e["metrics"]) for e in baselines["gates"].values())
        print(f"wrote {args.baselines}: {len(baselines['gates'])} "
              f"experiments, {n} gated metrics")
        return 0

    if args.diff_only:
        if not args.results:
            print("--diff-only needs --results", file=sys.stderr)
            return 1
        doc = schema.load_document(args.results)
        return _diff(doc, args,
                     expected=[r["fingerprint"] for r in doc["results"]])

    extra = json.loads(args.extra) if args.extra else None
    configs = experiments.enumerate_experiments(
        domains=args.domains, modes=args.modes, smoke=args.smoke,
        extra=extra)
    if args.list:
        for c in configs:
            print(f"{c.fingerprint}  devices={c.devices} smoke={c.smoke}")
        return 0

    try:
        doc = experiments.run_suite(configs, args.work_dir, args.timeout_s)
    except experiments.ExperimentFailed as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    schema.write_document(args.out, doc)
    print(f"\nwrote {args.out} ({len(doc['results'])} experiments)")

    if args.write_domain_docs:
        if args.smoke:
            print("refusing --write-domain-docs on a --smoke run: the "
                  "committed documents are full-size reference numbers",
                  file=sys.stderr)
            return 1
        by_domain = {}
        for r in doc["results"]:
            by_domain.setdefault(r["experiment"]["domain"], []).append(r)
        for domain, results in by_domain.items():
            path = experiments.domain_document_path(domain)
            schema.write_document(path, {
                "schema": schema.SCHEMA_VERSION,
                "generated_by": experiments.DOMAINS[domain]["module"],
                "results": results})
            print(f"wrote {path}")

    if args.diff_baselines:
        return _diff(doc, args, expected=[c.fingerprint for c in configs])
    return 0


if __name__ == "__main__":
    sys.exit(main())

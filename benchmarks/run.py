"""Benchmark harness entry point — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV.  Set REPRO_BENCH_FULL=1 for the
paper-scale grids (default: CPU-quick grids).

``--json [PATH]`` runs only the machine-readable sweeps and writes them as
JSON: the facade solver sweep to PATH (default ``BENCH_solvers.json``,
loss + the fresh/cached distance-evaluation ledger per registered solver
at fixed (n, k)), the core-engine wall-clock sweep (per-solver ×
stats-backend × fused/stepped driver, median of >= 3 reps) to
``BENCH_core.json`` next to it, the sharded-engine sweep
(``banditpam_dist`` on simulated devices vs the single-device solver) to
``BENCH_distributed.json``, and the batched multi-fit throughput sweep
(``fit_batch`` vs the Python loop at B=64) to ``BENCH_multifit.json``,
and the serving-layer sweep (p50/p99 predict latency,
refit-behind-traffic throughput, warm-vs-cold refit ledger) to
``BENCH_serve.json``, and the compiled-graph cost census (flops/bytes
from ``cost_analysis`` + peak temp vs the GRC001 budget, per graphcheck
entrypoint) to ``BENCH_graphs.json``.
``--solver`` (repeatable) restricts the solver sweep to named solvers."""
from __future__ import annotations

import argparse
import os
import sys
import traceback


def main(argv=None) -> None:
    from repro.api import available_solvers
    from repro.runtime import compile_cache

    from . import (core_bench, distributed_bench, graphs_bench,
                   kernels_bench, loss_quality, megakernel_bench,
                   multifit_bench, roofline, scaling_n, serve_bench,
                   sigma_adaptivity, solvers, violation_pca)

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", nargs="?", const="BENCH_solvers.json",
                    default=None, metavar="PATH",
                    help="write the solver sweep to PATH as JSON and exit")
    ap.add_argument("--solver", action="append", choices=available_solvers(),
                    help="restrict the solver sweep (repeatable; default: "
                         "every registered solver)")
    args = ap.parse_args(argv)
    compile_cache.enable(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    print("name,us_per_call,derived")
    if args.json is not None:
        outdir = os.path.dirname(args.json) or "."
        solvers.write_json(args.json, solvers=args.solver)
        core_bench.write_json(os.path.join(outdir, "BENCH_core.json"))
        distributed_bench.write_json(
            os.path.join(outdir, "BENCH_distributed.json"))
        multifit_bench.write_json(
            os.path.join(outdir, "BENCH_multifit.json"))
        serve_bench.write_json(os.path.join(outdir, "BENCH_serve.json"))
        megakernel_bench.write_json(
            os.path.join(outdir, "BENCH_megakernel.json"))
        graphs_bench.write_json(os.path.join(outdir, "BENCH_graphs.json"))
        return
    failed = []
    for mod in (loss_quality, scaling_n, sigma_adaptivity, violation_pca,
                solvers, core_bench, distributed_bench, multifit_bench,
                serve_bench, kernels_bench, megakernel_bench, graphs_bench,
                roofline):
        try:
            if mod is solvers:
                mod.sweep(solvers=args.solver)
            else:
                mod.run()
        except Exception:
            failed.append(mod.__name__)
            traceback.print_exc()
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()

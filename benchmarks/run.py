"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Module → paper artifact map:
  bench_assembly_scaling   — Fig. 1 / §2 O(1)-graph property
  bench_solver_scaling     — Fig. 2 (3D Poisson + elasticity scaling)
  bench_mixed_bc           — SM B.1.5 Table B.3 (mixed-BC Poisson)
  bench_batch_generation   — SM B.1.4 Fig. B.4 (batched RHS solves)
  bench_neural_solvers     — Table 1 (PINN/VPINN/DeepRitz/TensorPILS)
  bench_loss_eval          — Fig. 4 / Fig. B.12 (loss-eval cost vs DoF)
  bench_operator_learning  — Table 2 (wave operator learning, ID/OOD)
  bench_topo_opt           — Table 3 (cantilever SIMP)
  bench_kernels            — Pallas kernel microbench (interpret mode off-TPU)
  bench_transient          — repro.transient rollouts (heat/wave, CSR vs ELL)
  bench_weakform           — fused multi-term WeakForm assemble vs separate+add
  bench_batched_assembly   — vmap-batched multi-instance assembly vs B singles
  bench_matfree            — matrix-free apply/solve vs assembled CSR
  bench_precond            — elemalg preconditioners + static condensation
  bench_serve              — repro.serve admission batching vs sequential
  bench_telemetry          — spans/telemetry overhead on the hot solve path
  bench_dryrun_roofline    — harness roofline table (from dry-run JSON)

Usage:
  python -m benchmarks.run [--only PREFIX[,PREFIX...]] [--quick]

``--only matfree`` runs just the modules whose name contains the prefix
(``bench_`` is implied); a comma-separated list (``--only matfree,serve``)
runs every module matching any prefix.  ``--quick`` switches modules to
their reduced problem sizes (the perf-smoke CI subset).
``BENCH_JSON=<path>`` appends machine-readable JSON-lines rows (compared
against the committed ``benchmarks/BENCH_baseline.json`` by
``benchmarks/compare.py``).
"""

import argparse
import os
import sys
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("Usage:")[0])
    ap.add_argument(
        "--only", default=None, metavar="PREFIX[,PREFIX...]",
        help="run only modules whose name contains any PREFIX "
             "(bench_ implied; comma-separated)",
    )
    ap.add_argument(
        "--quick", action="store_true",
        help="reduced problem sizes (sets BENCH_QUICK=1 for all modules)",
    )
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    if args.quick:
        os.environ["BENCH_QUICK"] = "1"

    from . import (
        bench_assembly_scaling,
        bench_batch_generation,
        bench_batched_assembly,
        bench_dryrun_roofline,
        bench_kernels,
        bench_loss_eval,
        bench_matfree,
        bench_mixed_bc,
        bench_neural_solvers,
        bench_operator_learning,
        bench_precond,
        bench_serve,
        bench_solver_scaling,
        bench_telemetry,
        bench_topo_opt,
        bench_transient,
        bench_weakform,
    )

    modules = [
        bench_assembly_scaling,
        bench_solver_scaling,
        bench_mixed_bc,
        bench_batch_generation,
        bench_neural_solvers,
        bench_loss_eval,
        bench_operator_learning,
        bench_topo_opt,
        bench_kernels,
        bench_transient,
        bench_weakform,
        bench_batched_assembly,
        bench_matfree,
        bench_precond,
        bench_serve,
        bench_telemetry,
        bench_dryrun_roofline,
    ]
    if args.only:
        needles = [p.removeprefix("bench_") for p in args.only.split(",") if p]
        modules = [m for m in modules
                   if any(nd in m.__name__ for nd in needles)]
        if not modules:
            print(f"no benchmark module matches --only {args.only!r}", file=sys.stderr)
            sys.exit(2)
    print("name,us_per_call,derived")
    failed = []
    for mod in modules:
        try:
            mod.main()
        except Exception:
            failed.append(mod.__name__)
            traceback.print_exc()
    if failed:
        print(f"FAILED_MODULES={failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()

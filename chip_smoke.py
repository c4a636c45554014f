"""Chip smoke: the million-DOF Poisson solve and the solve service on a TPU.

    python chip_smoke.py                # one chip: solve phase, serve phase
    python chip_smoke.py --four-chips   # four chips: sharded paths only

One process drives the chip; nothing runs in a child process.  Without a TPU
the script exits nonzero after its first line and prints no result.  The
last line of standard output is ``{"ok": true, "device": {...}}`` and is
printed only when every phase passed.  A failed check raises; its phase is
reported with its traceback, the next phase still runs, and the process
exits nonzero.

Solve phase: ``PoissonProblem(unit_square_tri(1000)).solve()`` (1,002,001
DOFs, ``csr`` backend, Jacobi-CG, relative tolerance 1e-10) on the
manufactured solution ``u = sin(πx) sin(πy)``.  The relative residual is
recomputed on the host in float64 with ``scipy.sparse`` from the CSR arrays
pulled back, and the nodal error is held to the O(h²) bound below.

Serve phase: a warmed :class:`repro.serve.SolveService` answers two waves of
8 resolution-256 Poisson requests (66,049 DOFs each) with no retrace; every
answer meets the same host-side residual bound and is compared with a
sequential ``sparse_solve`` reference.  Then ``repro.launch.serve --smoke``
runs in this process, unchanged.

Four chips (``--four-chips``): the same 1,002,001-DOF solve with the
``matfree_sharded`` backend over all four chips against ``matfree`` on one
chip, the element arrays that the compiled sharded apply receives checked
to be split over the four chips, and
``assemble_sharded`` CSR values against ``assemble``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

RESOLUTION = 1000          # unit_square_tri(1000): 1,002,001 DOFs
SERVE_RESOLUTION = 256     # 66,049 DOFs per served request
SERVE_WAVE = 8
SERVE_BUCKETS = (1, 2, 4, 8)
TOL = 1e-10
RESIDUAL_BOUND = 2 * TOL   # host-recomputed ‖K u − f‖ / ‖f‖
# max nodal error of P1 on the manufactured solution: 1.609·h² to 1.645·h²
# at resolutions 16..256 (CPU, float64); the bound is 2·h², so a solve that
# stops early or computes in float32 fails it
ERROR_CONSTANT = 2.0
SHARDED_AGREEMENT = 1e-8   # max |u_sharded − u_single|: 0.5% of the bound
ASSEMBLY_AGREEMENT = 1e-12  # max |vals_sharded − vals| (entries are O(1))


def _spec():
    """CG + Jacobi at relative tolerance ``TOL``.  ``atol=0`` keeps the stop
    relative: the default absolute floor (1e-10) sits above ``TOL·‖f‖`` for
    these loads (‖f‖ ≈ 1e-2), so it would end the solve early."""
    from repro.core import SolverSpec

    return SolverSpec(method="cg", precond="jacobi", tol=TOL, atol=0.0)


def _source(x):
    import jax.numpy as jnp

    return 2 * np.pi**2 * jnp.sin(np.pi * x[..., 0]) * jnp.sin(np.pi * x[..., 1])


def _host_relres(k, u, f) -> float:
    """‖K u − f‖₂ / ‖f‖₂ in float64 on the host, from the pulled-back CSR."""
    import scipy.sparse as sp

    a = sp.csr_matrix((np.asarray(k.vals, np.float64), k.indices, k.indptr),
                      shape=k.shape)
    f = np.asarray(f, np.float64)
    return float(np.linalg.norm(a @ np.asarray(u, np.float64) - f)
                 / np.linalg.norm(f))


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {what}")


def _timed(fn):
    import jax

    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def _memory_line(device) -> str:
    stats = device.memory_stats() or {}
    keys = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
    return "memory_stats " + json.dumps({k: stats.get(k) for k in keys})


def _exact(prob) -> np.ndarray:
    p = prob.space.dof_points
    return np.sin(np.pi * p[:, 0]) * np.sin(np.pi * p[:, 1])


def solve_phase(resolution: int = RESOLUTION) -> None:
    import jax

    from repro.core import unit_square_tri
    from repro.fem.tensormesh import PoissonProblem

    t0 = time.perf_counter()
    prob = PoissonProblem(unit_square_tri(resolution))
    n = prob.space.num_dofs
    print(f"solve: {n} DOFs, setup {time.perf_counter() - t0:.3f}s",
          flush=True)
    spec = _spec()

    def run():
        return prob.solve(f=_source, spec=spec, backend="csr",
                          return_info=True)

    (k, f), t_asm = _timed(lambda: prob.assemble(f=_source))
    print(f"solve: first assembly {t_asm:.3f}s (compile included)",
          flush=True)
    (res, info), cold = _timed(run)
    print(f"solve: first solve {cold:.3f}s", flush=True)
    (res, info), warm = _timed(run)
    relres = _host_relres(k, res.u, f)
    h = 1.0 / resolution
    err = float(np.max(np.abs(np.asarray(res.u) - _exact(prob))))
    bound = ERROR_CONSTANT * h * h
    solver_relres = float(info.residual) / float(np.linalg.norm(np.asarray(f)))
    print(f"solve: iterations {int(info.iters)}, converged "
          f"{bool(info.converged)}, solver relres {solver_relres:.3e}, "
          f"host relres {relres:.3e} "
          f"(bound {RESIDUAL_BOUND:.0e}), max nodal error {err:.3e} "
          f"(bound {ERROR_CONSTANT}·h² = {bound:.3e})")
    print(f"solve: first assembly {t_asm:.3f}s, cold solve {cold:.3f}s "
          f"(Krylov compile included; assembly already compiled), warm "
          f"solve {warm:.3f}s")
    print(_memory_line(jax.devices()[0]))
    _check(bool(info.converged), "1M-DOF CG converged")
    _check(relres <= RESIDUAL_BOUND, f"host relres {relres:.3e} ≤ 2e-10")
    _check(err <= bound, f"max nodal error {err:.3e} ≤ {bound:.3e}")


def serve_phase(resolution: int = SERVE_RESOLUTION) -> None:
    import jax

    from repro import serve, telemetry
    from repro.core import assemble, sparse_solve
    from repro.launch import serve as serve_launch

    spec = _spec()
    telemetry.enable()
    svc = serve.SolveService(window=0.002)
    template = serve.poisson_requests(n_requests=1, resolution=resolution,
                                      spec=spec)[0]
    _, t_warm = _timed(lambda: svc.warmup(template, batch_sizes=SERVE_BUCKETS))
    base = telemetry.jit_trace_total("serve")
    print(f"serve: {template.rhs.shape[0]} DOFs per request, warmup of "
          f"buckets {SERVE_BUCKETS} {t_warm:.3f}s")

    waves = [serve.poisson_requests(n_requests=SERVE_WAVE,
                                    resolution=resolution, spec=spec,
                                    seed=seed) for seed in (1, 2)]
    responses = []
    with svc:
        for i, reqs in enumerate(waves):
            t0 = time.perf_counter()
            pendings = [svc.submit(r) for r in reqs]
            wave = [p.response(timeout=600) for p in pendings]
            print(f"serve: wave {i}: {sum(r.ok for r in wave)}/{len(wave)} "
                  f"ok in {time.perf_counter() - t0:.3f}s, batch sizes "
                  f"{sorted({r.batch_size for r in wave})}")
            responses += wave
    retraces = telemetry.jit_trace_total("serve") - base
    print(f"serve: retraces after warmup {retraces}")
    _check(all(r.status == "ok" for r in responses),
           f"all served requests ok: {[r.status for r in responses]}")
    _check(retraces == 0, f"0 retraces after warmup, got {retraces}")

    worst_served = worst_ref = worst_diff = 0.0
    for req, resp in zip([r for w in waves for r in w], responses):
        k = req.bc.apply_matrix_only(assemble(req.plan, req.form))
        f = req.rhs * req.bc.free_mask
        u_ref = sparse_solve(k, f, spec)
        worst_served = max(worst_served, _host_relres(k, resp.u, f))
        worst_ref = max(worst_ref, _host_relres(k, u_ref, f))
        worst_diff = max(worst_diff, float(np.max(np.abs(
            np.asarray(resp.u) - np.asarray(u_ref)))))
    print(f"serve: worst host relres served {worst_served:.3e}, sequential "
          f"reference {worst_ref:.3e}, max |served - reference| "
          f"{worst_diff:.3e}")
    print(_memory_line(jax.devices()[0]))
    _check(worst_served <= RESIDUAL_BOUND,
           f"served relres {worst_served:.3e} ≤ 2e-10")
    _check(worst_ref <= RESIDUAL_BOUND,
           f"reference relres {worst_ref:.3e} ≤ 2e-10")

    print("serve: python -m repro.launch.serve --smoke, in this process")
    rc = serve_launch.main(["--smoke"])
    _check(rc == 0, f"repro.launch.serve --smoke returned {rc}")


def _element_input_splits(sop, n_cells: int) -> dict:
    """How the compiled sharded apply receives each element-aligned array
    of the operator: ``{leaf: (devices, blocks along the element axis)}``,
    read from the input shardings of the compiled program (arrays the apply
    does not read are left out)."""
    import jax

    x = jax.ShapeDtypeStruct((sop.shape[0],), np.float64)
    compiled = jax.jit(lambda s, v: s.matvec(v)).lower(sop, x).compile()
    pairs = jax.tree.map(lambda a, s: (np.shape(a), s), sop,
                         compiled.input_shardings[0][0])
    flat, _ = jax.tree_util.tree_flatten_with_path(
        pairs, is_leaf=lambda t: isinstance(t, tuple) and len(t) == 2
        and isinstance(t[0], tuple))
    return {jax.tree_util.keystr(path[-1:]): (len(s.device_set),
                                         n_cells // s.shard_shape(shape)[0])
            for path, (shape, s) in flat
            if s is not None and shape[:1] == (n_cells,)}


def four_chip_phase(resolution: int = RESOLUTION, n_devices: int = 4) -> None:
    import jax

    from repro.core import assemble, assemble_sharded, matfree_operator
    from repro.core import unit_square_tri, weakform as wf
    from repro.fem.tensormesh import PoissonProblem

    prob = PoissonProblem(unit_square_tri(resolution))
    spec = _spec()
    print(f"four chips: {prob.space.num_dofs} DOFs over "
          f"{len(jax.devices())} devices")

    sop = matfree_operator(prob.plan, wf.diffusion()).sharded()
    splits = _element_input_splits(sop, prob.mesh.num_cells)
    print(f"four chips: element arrays of the compiled sharded apply, "
          f"(devices, blocks along the element axis): {splits}")
    _check(bool(splits) and all(v == (n_devices, n_devices)
                                for v in splits.values()),
           f"every element array is split over {n_devices} devices")

    def run(backend):
        return prob.solve(f=_source, spec=spec, backend=backend,
                          return_info=True)

    (single, info_1), t_single = _timed(lambda: run("matfree"))
    (sharded, info_s), t_sharded = _timed(lambda: run("matfree_sharded"))
    k, f = prob.assemble(f=_source)
    rel_1 = _host_relres(k, single.u, f)
    rel_s = _host_relres(k, sharded.u, f)
    diff = float(np.max(np.abs(np.asarray(sharded.u) - np.asarray(single.u))))
    print(f"four chips: matfree iterations {int(info_1.iters)}, relres "
          f"{rel_1:.3e}, {t_single:.3f}s cold; matfree_sharded iterations "
          f"{int(info_s.iters)}, relres {rel_s:.3e}, {t_sharded:.3f}s cold; "
          f"max |sharded - single| {diff:.3e} (bound {SHARDED_AGREEMENT:.0e})")
    _check(bool(info_1.converged) and bool(info_s.converged),
           "both matrix-free solves converged")
    _check(max(rel_1, rel_s) <= RESIDUAL_BOUND,
           f"host relres {max(rel_1, rel_s):.3e} ≤ 2e-10")
    _check(diff <= SHARDED_AGREEMENT,
           f"sharded solve matches single-chip matfree: {diff:.3e}")

    form = wf.diffusion()
    vals = np.asarray(assemble(prob.plan, form).vals)
    vals_s = np.asarray(assemble_sharded(prob.plan, form).vals)
    adiff = float(np.max(np.abs(vals_s - vals)))
    print(f"four chips: assemble_sharded vs assemble, {vals.size} values, "
          f"max |diff| {adiff:.3e} (bound {ASSEMBLY_AGREEMENT:.0e})")
    _check(adiff <= ASSEMBLY_AGREEMENT, "assemble_sharded matches assemble")
    print(_memory_line(jax.devices()[0]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded paths and their "
                         "single-chip comparisons")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}, platform {dev.platform}, device_kind "
          f"{dev.device_kind!r}, device count {len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform!r} devices only",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"need {want} TPU devices, found {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import configure_compile_cache

    print(f"compile cache: {configure_compile_cache()}")
    phases = [four_chip_phase] if args.four_chips else [solve_phase,
                                                        serve_phase]
    failed = []
    for phase in phases:
        t0 = time.perf_counter()
        try:
            phase()
            outcome = "passed"
        except Exception:  # report it, run the next phase, exit nonzero
            traceback.print_exc()
            failed.append(phase.__name__)
            outcome = "FAILED"
        print(f"{phase.__name__} {outcome} in "
              f"{time.perf_counter() - t0:.3f}s", flush=True)
    if failed:
        print(f"failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": want}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

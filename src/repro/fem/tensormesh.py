"""TensorMesh — the numerical PDE solver built on TensorGalerkin (paper §3 i).

Problem classes own (mesh → space → assembler → condenser) and expose:
* ``solve()``                 — assembly + preconditioned Krylov solve,
* ``solve_batch(fs)``         — many-query batched-RHS solves (SM B.1.4):
  one assembly, one jitted vmapped solve over the RHS batch,
* ``residual(u)``             — relative linear-system residual (Eq. B.8).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..telemetry import events
from ..core import (
    DirichletCondenser,
    FacetAssembler,
    FunctionSpace,
    GalerkinAssembler,
    SolverSpec,
    make_matvec,
    make_preconditioner,
    matfree_operator,
    resolve_solver_spec,
    weakform as wf,
)
from ..core.solvers import _method
from ..core import forms
from ..core.mesh import Mesh, element_for_mesh

__all__ = [
    "PoissonProblem",
    "AdvectionDiffusionProblem",
    "ElasticityProblem",
    "MixedBCPoisson",
]


@dataclasses.dataclass
class _SolveResult:
    u: jnp.ndarray
    iters: int
    residual: float
    converged: bool = True


class _ProblemBase:
    method = "cg"
    use_ell = True  # ELL matvec in the Krylov loop: 2.1× end-to-end (§Perf-FEM)
    backend = None  # default matvec backend (None → "ell" per use_ell flag)

    def _spec(self, spec, tol, maxiter, where) -> SolverSpec:
        """One :class:`~repro.core.SolverSpec` per solve: ``spec=`` wins,
        legacy ``tol=``/``maxiter=`` kwargs shim into it (deprecated)."""
        return resolve_solver_spec(
            spec, tol=tol, maxiter=maxiter,
            default=SolverSpec(method=self.method),
            where=f"{type(self).__name__}.{where}")

    @property
    def plan(self):
        """The problem's :class:`~repro.core.AssemblyPlan` — the functional
        assembly signature consumed by the pure ``assemble`` /
        ``assemble_batched`` / ``assemble_sharded`` entry points."""
        return self.asm.plan

    def _default_backend(self) -> str:
        if self.backend is not None:
            return self.backend
        return "ell" if self.use_ell else "csr"

    def _solve_system(self, k, f, spec: SolverSpec, backend=None,
                      return_info=False):
        """Krylov solve on an assembled operator with the inner matvec from
        the unified registry (:mod:`repro.core.matvec`) and the
        preconditioner resolved from ``spec.precond`` via the registry.  A
        ``maxiter`` exit is reported through
        :func:`repro.telemetry.check_convergence` (warn/raise per policy)
        and the ``converged`` flag on the result; ``return_info=True``
        appends the raw :class:`~repro.core.solvers.SolveInfo`."""
        be = backend or self._default_backend()
        # krylov: build the matvec and preconditioner, trace, compile and
        # dispatch the loop; wait: the host blocks on the loop's result
        with telemetry.span("tg.solve.krylov"):
            matvec = make_matvec(k, be)
            u, info = _method(spec.method)(
                matvec, f, m=make_preconditioner(k, spec.precond),
                tol=spec.tol, atol=spec.atol, maxiter=spec.maxiter)
        where = f"{type(self).__name__}.solve"
        with telemetry.span("tg.solve.wait"):
            events.check_convergence(info, where=where)
            events.record_solve(where, info, method=spec.method, backend=be,
                                precond=spec.precond_name)
            rel = float(jnp.linalg.norm(matvec(u) - f) / jnp.linalg.norm(f))
        res = _SolveResult(u, int(info.iters), rel, bool(info.converged))
        return (res, info) if return_info else res

    def _solve_matfree(self, form, load, spec: SolverSpec,
                       dirichlet_values=0.0, return_info=False,
                       sharded=False, condensed=False):
        """Matrix-free Krylov solve: the operator applies ``form`` straight
        from the plan (element-local Map → per-element action →
        scatter-Reduce), Jacobi from a diagonal-only assembly, Dirichlet
        condensation as an apply wrapper (the RHS lift runs one matrix-free
        apply of the uncondensed operator) — global CSR values are never
        materialized.  ``sharded=True`` partitions every apply (including
        the Jacobi diagonal assembly and the RHS lift) over the local device
        mesh, so one Krylov solve spans all devices.  (For a
        *differentiable* matrix-free solve use
        :func:`repro.core.matfree_solve` on the same operator.)

        ``spec.precond`` selects any registered preconditioner — ``"ebe"``
        and ``"chebyshev"`` stay matrix-free.  ``condensed=True`` statically
        condenses the higher-order DOFs (degree ≥ 2 spaces) and runs the
        Krylov iteration on the interface Schur complement only
        (:func:`repro.core.elemalg.condensed_solve` machinery)."""
        from ..core import elemalg

        with telemetry.span("tg.solve.krylov"):
            op_full = matfree_operator(self.plan, form)
            if sharded:
                op_full = op_full.sharded()
            op = op_full.condensed(self.bc)
            if (isinstance(dirichlet_values, (int, float))
                    and dirichlet_values == 0.0):
                # homogeneous: the lift reduces to masking — skip the dead
                # matrix-free apply of the all-zero boundary field
                f = self.bc.project_residual(load)
            else:
                f = self.bc.lift(op_full, load, dirichlet_values)
            if condensed:
                sys = elemalg.condense(op, elemalg.vertex_split(self.space))
                u, info = sys.solve(f, spec)
            else:
                u, info = _method(spec.method)(
                    op.matvec, f, m=make_preconditioner(op, spec.precond),
                    tol=spec.tol, atol=spec.atol, maxiter=spec.maxiter)
        where = f"{type(self).__name__}.solve"
        with telemetry.span("tg.solve.wait"):
            events.check_convergence(info, where=where)
            events.record_solve(
                where, info, method=spec.method,
                backend="matfree_sharded" if sharded else "matfree",
                precond="condensed" if condensed else spec.precond_name)
            rel = float(jnp.linalg.norm(op.matvec(u) - f)
                        / jnp.linalg.norm(f))
        res = _SolveResult(u, int(info.iters), rel, bool(info.converged))
        return (res, info) if return_info else res


class PoissonProblem(_ProblemBase):
    """−∇·(ρ∇u) = f with homogeneous Dirichlet BCs (paper Benchmark I)."""

    def __init__(self, mesh: Mesh, degree: int = 1, quad_order: int | None = None):
        self.mesh = mesh
        self.space = FunctionSpace(mesh, element_for_mesh(mesh, degree))
        self.asm = GalerkinAssembler(self.space, quad_order)
        self.bc = DirichletCondenser(self.asm, self.space.boundary_dofs())

    def assemble(self, rho=None, f=1.0):
        with telemetry.span("tg.assemble"):
            with telemetry.span("tg.assemble.matrix"):
                k = self.asm.assemble(wf.diffusion(rho))
            with telemetry.span("tg.assemble.vector"):
                load = self.asm.assemble_rhs(wf.source(f))
            with telemetry.span("tg.condense"):
                return self.bc.apply(k, load)

    def solve(self, rho=None, f=1.0, spec: SolverSpec | None = None,
              tol=None, maxiter=None, backend=None, return_info=False,
              condensed=False):
        """Solve with a registry-selected matvec backend; ``"matfree"``
        skips matrix assembly entirely (only the RHS vector is assembled)
        and ``"matfree_sharded"`` additionally spans the solve over all
        local devices.  Solver knobs come in as one
        :class:`~repro.core.SolverSpec` (``spec=``; legacy ``tol=`` /
        ``maxiter=`` kwargs still work but are deprecated).
        ``condensed=True`` (matfree backends, degree ≥ 2) runs the Krylov
        iteration on the statically condensed interface system.
        ``return_info=True`` appends the raw
        :class:`~repro.core.solvers.SolveInfo`."""
        spec = self._spec(spec, tol, maxiter, "solve")
        if condensed and backend not in ("matfree", "matfree_sharded"):
            raise ValueError("condensed=True needs a matfree backend")
        with telemetry.span("tg.solve"):
            if backend in ("matfree", "matfree_sharded"):
                load = self.asm.assemble_rhs(wf.source(f))
                return self._solve_matfree(
                    wf.diffusion(rho), load, spec, return_info=return_info,
                    sharded=backend == "matfree_sharded",
                    condensed=condensed)
            k, load = self.assemble(rho, f)
            return self._solve_system(k, load, spec, backend=backend,
                                      return_info=return_info)

    # -- many-query batched data generation (SM B.1.4) ------------------------
    def solve_batch(self, f_batch: jnp.ndarray, rho=None, tol=1e-10, maxiter=2000):
        """Solve K u_b = F(f_b) for a batch of nodal source fields
        ``f_batch: (B, num_dofs)`` — assembly amortized, solve vmapped."""
        k = self.bc.apply_matrix_only(self.asm.assemble(wf.diffusion(rho)))
        m = make_preconditioner(k, "jacobi")

        @jax.jit
        def run(fb):
            def solve_one(f_nodal):
                load = self.asm.assemble_rhs(wf.source(f_nodal))
                load = self.bc.project_residual(load)
                u, info = _method("cg")(k.matvec, load, m=m, tol=tol,
                                        maxiter=maxiter)
                return u, info.iters

            return jax.vmap(solve_one)(fb)

        return run(f_batch)

    def solve_coeff_batch(self, rho_batch: jnp.ndarray, f=1.0, tol=1e-10,
                          maxiter=10000):
        """Solve the *family* −∇·(ρ_b ∇u_b) = f for a batch of per-element
        coefficient fields ``rho_batch: (B, E)``: ONE batched assembly
        (``assemble_batched`` → shared-pattern ``BatchedCSR``), shared-mask
        condensation, and one vmapped adjoint ``sparse_solve`` — a single
        XLA executable for all B operators.  Returns ``(B, num_dofs)``.
        """
        from ..core import assemble_batched, assemble_rhs, sparse_solve_batched

        rho_batch = jnp.asarray(rho_batch)
        kb = assemble_batched(
            self.plan, wf.diffusion(rho_batch[0]), leaves_batch=(rho_batch, None)
        )
        kc = self.bc.apply_matrix_only(kb)
        load = self.bc.project_residual(assemble_rhs(self.plan, wf.source(f)))
        return sparse_solve_batched(
            kc, load, SolverSpec(method="cg", tol=tol, atol=tol,
                                 maxiter=maxiter))


class AdvectionDiffusionProblem(_ProblemBase):
    """−∇·(ε∇u) + β·∇u = f with Dirichlet BCs — the steady nonsymmetric
    problem the composable weak-form API unlocks: no assembler edits, just
    ``diffusion(eps) + advection(beta)`` (BiCGStab since K is nonsymmetric).
    """

    method = "bicgstab"

    def __init__(self, mesh: Mesh, degree: int = 1, quad_order: int | None = None):
        self.mesh = mesh
        self.space = FunctionSpace(mesh, element_for_mesh(mesh, degree))
        self.asm = GalerkinAssembler(self.space, quad_order)
        self.bc = DirichletCondenser(self.asm, self.space.boundary_dofs())

    def assemble(self, eps=1.0, beta=(1.0, 0.0), f=1.0, dirichlet_values=0.0):
        form = wf.diffusion(eps) + wf.advection(jnp.asarray(beta))
        k = self.asm.assemble(form)
        load = self.asm.assemble_rhs(wf.source(f))
        return self.bc.apply(k, load, dirichlet_values)

    def solve(self, eps=1.0, beta=(1.0, 0.0), f=1.0, dirichlet_values=0.0,
              spec: SolverSpec | None = None, tol=None, maxiter=None,
              backend=None, return_info=False):
        spec = self._spec(spec, tol, maxiter, "solve")
        if backend in ("matfree", "matfree_sharded"):
            form = wf.diffusion(eps) + wf.advection(jnp.asarray(beta))
            load = self.asm.assemble_rhs(wf.source(f))
            return self._solve_matfree(form, load, spec,
                                       dirichlet_values=dirichlet_values,
                                       return_info=return_info,
                                       sharded=backend == "matfree_sharded")
        k, load = self.assemble(eps, beta, f, dirichlet_values)
        return self._solve_system(k, load, spec, backend=backend,
                                  return_info=return_info)


class ElasticityProblem(_ProblemBase):
    """Isotropic linear elasticity, constant body force (paper Benchmark II).
    The form is symmetric positive definite, so CG is the default."""

    def __init__(self, mesh: Mesh, e_mod=1.0, nu=0.3):
        d = mesh.dim
        self.mesh = mesh
        self.space = FunctionSpace(mesh, element_for_mesh(mesh), value_size=d)
        self.asm = GalerkinAssembler(self.space)
        self.bc = DirichletCondenser(self.asm, self.space.boundary_dofs())
        self.lam = e_mod * nu / ((1 + nu) * (1 - 2 * nu))
        self.mu = e_mod / (2 * (1 + nu))

    def assemble(self, body_force=None, scale=None):
        d = self.mesh.dim
        bf = jnp.ones(d) if body_force is None else jnp.asarray(body_force)
        with telemetry.span("tg.assemble"):
            with telemetry.span("tg.assemble.matrix"):
                k = self.asm.assemble(
                    wf.elasticity(self.lam, self.mu, scale=scale))
            with telemetry.span("tg.assemble.vector"):
                f = self.asm.assemble_rhs(wf.source(bf))
            with telemetry.span("tg.condense"):
                return self.bc.apply(k, f)

    def solve(self, body_force=None, spec: SolverSpec | None = None,
              tol=None, maxiter=None, backend=None, return_info=False):
        spec = self._spec(spec, tol, maxiter, "solve")
        with telemetry.span("tg.solve"):
            if backend in ("matfree", "matfree_sharded"):
                d = self.mesh.dim
                bf = (jnp.ones(d) if body_force is None
                      else jnp.asarray(body_force))
                load = self.asm.assemble_rhs(wf.source(bf))
                return self._solve_matfree(
                    wf.elasticity(self.lam, self.mu), load, spec,
                    return_info=return_info,
                    sharded=backend == "matfree_sharded",
                )
            k, f = self.assemble(body_force)
            return self._solve_system(k, f, spec, backend=backend,
                                      return_info=return_info)


class MixedBCPoisson(_ProblemBase):
    """Poisson with simultaneous Dirichlet + Neumann + Robin boundary parts
    (paper SM B.1.5).  Boundary parts are selected by coordinate predicates;
    Neumann/Robin route through the same Map-Reduce (FacetAssembler)."""

    method = "bicgstab"

    def __init__(self, mesh: Mesh, dirichlet_pred, neumann_pred=None, robin_pred=None):
        self.mesh = mesh
        self.space = FunctionSpace(mesh, element_for_mesh(mesh))
        self.asm = GalerkinAssembler(self.space)

        facets = mesh.boundary_facets()
        centers = mesh.points[facets].mean(axis=1)
        d_mask = dirichlet_pred(centers)
        n_mask = neumann_pred(centers) if neumann_pred else np.zeros(len(facets), bool)
        r_mask = robin_pred(centers) if robin_pred else np.zeros(len(facets), bool)
        # Dirichlet wins on overlaps; remaining facets default to Dirichlet
        n_mask &= ~d_mask
        r_mask &= ~(d_mask | n_mask)
        self.d_facets = facets[d_mask | ~(n_mask | r_mask)]
        self.n_facets = facets[n_mask]
        self.r_facets = facets[r_mask]

        d_dofs = np.unique(self.d_facets)
        self.bc = DirichletCondenser(self.asm, d_dofs)
        self._fa_n = (
            FacetAssembler(self.space, self.n_facets, volume_routing=self.asm.mat_routing)
            if len(self.n_facets)
            else None
        )
        self._fa_r = (
            FacetAssembler(self.space, self.r_facets, volume_routing=self.asm.mat_routing)
            if len(self.r_facets)
            else None
        )
        # quadrature contexts, built once: per-solve callables are evaluated
        # on them *eagerly* so they enter the fused assembly as traced array
        # leaves — fresh lambdas per solve() reuse one compiled executable
        self._vol_ctx = self.asm.context()
        self._ctx_n = self._fa_n.context() if self._fa_n is not None else None
        self._ctx_r = self._fa_r.context() if self._fa_r is not None else None

    def solve(self, f, g_neumann=None, robin_alpha=1.0, g_robin=None,
              dirichlet_values=None, rho=None,
              spec: SolverSpec | None = None, tol=None, maxiter=None,
              backend=None, return_info=False):
        spec = self._spec(spec, tol, maxiter, "solve")
        if backend in ("matfree", "matfree_sharded"):
            raise NotImplementedError(
                "MixedBCPoisson has Robin facet terms, which the matrix-free "
                "apply does not support (volume terms only) — use an "
                "assembled backend ('csr'/'ell'/'ell_pallas'/'ell_stream')"
            )
        # mixed volume + boundary form → ONE CSR from one fused assembly
        # (Robin facet terms inject into the volume pattern), and one fused
        # RHS over volume source + Neumann/Robin boundary loads.  Callables
        # are pre-evaluated to quadrature arrays (traced leaves) so per-call
        # lambdas don't recompile the fused executable.
        if callable(rho):
            rho = forms.eval_coefficient(rho, self._vol_ctx)
        if callable(f):
            f = forms.eval_coefficient(f, self._vol_ctx)
        form = wf.diffusion(rho)
        rhs = wf.source(f)
        if self._fa_r is not None:
            form = form + wf.robin(robin_alpha, on=self._fa_r)
            if g_robin is not None:
                if callable(g_robin):
                    g_robin = forms.eval_coefficient(g_robin, self._ctx_r)
                rhs = rhs + wf.neumann(g_robin, on=self._fa_r)
        if self._fa_n is not None and g_neumann is not None:
            if callable(g_neumann):
                g_neumann = forms.eval_coefficient(g_neumann, self._ctx_n)
            rhs = rhs + wf.neumann(g_neumann, on=self._fa_n)
        k = self.asm.assemble(form)
        load = self.asm.assemble_rhs(rhs)
        bvals = 0.0
        if dirichlet_values is not None:
            d_dofs = self.bc.bc_dofs
            bvals = jnp.asarray(dirichlet_values(self.space.dof_points[d_dofs]))
        kc, fc = self.bc.apply(k, load, bvals)
        return self._solve_system(kc, fc, spec, backend=backend,
                                  return_info=return_info)

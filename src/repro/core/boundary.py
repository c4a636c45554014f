"""Boundary conditions.

* **Dirichlet** — hard constraints via in-pattern condensation (the paper's
  "reducing the linear system"): rows/columns of constrained DoFs are masked,
  unit diagonal inserted, RHS lifted by ``F ← F − K·u_D`` — all with *static*
  masks precomputed from the DoF set so the operation is a handful of fused
  element-wise ops inside jit (pattern and graph stay O(1)).
* **Neumann / Robin** — assembled on boundary facets through the *same*
  Map-Reduce pipeline (facet contexts + facet routing; paper SM B.1.5).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from . import forms
from .assembly import facet_context, reduce_matrix, reduce_vector
from .elements import get_element
from .mesh import FunctionSpace
from .routing import build_matrix_routing, build_vector_routing
from .sparse import CSR

__all__ = ["DirichletCondenser", "FacetAssembler"]


class DirichletCondenser:
    """Precomputes the static masks that impose ``u[dofs] = values``."""

    def __init__(self, space_or_routing, bc_dofs: np.ndarray):
        routing = getattr(space_or_routing, "mat_routing", space_or_routing)
        self.num_dofs = routing.num_dofs
        self.bc_dofs = np.asarray(bc_dofs, dtype=np.int64)
        is_bc = np.zeros(self.num_dofs, dtype=bool)
        is_bc[self.bc_dofs] = True
        self.is_bc = is_bc
        row_bc = is_bc[routing.row_of_nnz]
        col_bc = is_bc[routing.indices]
        self.keep_mask = jnp.asarray(~(row_bc | col_bc), dtype=float)
        # diag entries of constrained rows -> 1.0
        diag_of_bc = routing.diag_pos[self.bc_dofs]
        assert np.all(diag_of_bc >= 0), "constrained DoF missing diagonal entry"
        self.diag_of_bc = jnp.asarray(diag_of_bc)
        self.free_mask = jnp.asarray(~is_bc, dtype=float)
        # device mirrors staged once (not per traced call)
        self._bc_dofs_dev = jnp.asarray(self.bc_dofs)
        self._is_bc_dev = jnp.asarray(is_bc)

    def boundary_field(self, values, dtype=None) -> jnp.ndarray:
        """Expand Dirichlet data to a full ``(num_dofs,)`` field ``u_D``.

        ``values`` may be a scalar, a ``(n_bc,)`` array (one entry per
        constrained DoF, in ``bc_dofs`` order), or a full ``(num_dofs,)``
        field whose non-constrained entries are ignored.  Traced values are
        fine — all branching is on static shapes, so this works per-step
        inside ``lax.scan`` (time-varying boundary data).
        """
        values = jnp.asarray(values, dtype=dtype)
        u_d = jnp.zeros(self.num_dofs, dtype=values.dtype)
        if values.ndim == 0:
            return u_d.at[self._bc_dofs_dev].set(values)
        if values.shape == (self.bc_dofs.shape[0],):
            return u_d.at[self._bc_dofs_dev].set(values)
        if values.shape == (self.num_dofs,):
            # where(), not multiplication: free-DoF entries must be *ignored*,
            # even when non-finite (0 * NaN would leak into the lift matvec)
            return jnp.where(self._is_bc_dev, values, 0.0).astype(values.dtype)
        raise ValueError(f"un-interpretable Dirichlet value shape {values.shape}")

    def lift(self, k: CSR, f: jnp.ndarray, values=0.0) -> jnp.ndarray:
        """RHS-only condensation: ``F ← F − K u_D`` on free rows, ``F[bc] = g``.

        The matrix half of the condensation (:meth:`apply_matrix_only`) is
        value-independent, so for time-varying Dirichlet data the condensed
        matrix is hoisted out of the time loop and only this cheap lift runs
        per step — no condenser rebuild inside ``lax.scan``.  ``k`` must be
        the *uncondensed* matrix (the lift needs the constrained columns).
        """
        u_d = self.boundary_field(values, dtype=f.dtype)
        # homogeneous data: K u_D = 0, and the lift is the masking alone
        # (an apply of the whole operator saved)
        homogeneous = isinstance(values, (int, float)) and values == 0.0
        f_lift = (f if homogeneous else f - k.matvec(u_d)) * self.free_mask
        bc = self._bc_dofs_dev
        return f_lift.at[bc].set(u_d[bc])

    def apply(self, k: CSR, f: jnp.ndarray, values=0.0) -> tuple[CSR, jnp.ndarray]:
        """Return the condensed system (same sparsity pattern)."""
        return self.apply_matrix_only(k), self.lift(k, f, values)

    def apply_matrix_only(self, k: CSR) -> CSR:
        """Mask constrained rows/columns, unit diagonal.  The masks broadcast
        over leading axes, so this also condenses a whole ``BatchedCSR``
        family ((B, nnz) vals) in one fused elementwise op."""
        vals = k.vals * self.keep_mask.astype(k.vals.dtype)
        vals = vals.at[..., self.diag_of_bc].set(1.0)
        return dataclasses.replace(k, vals=vals)

    def project_residual(self, r: jnp.ndarray) -> jnp.ndarray:
        """Zero residual entries on constrained DoFs (for loss functions)."""
        return r * self.free_mask.astype(r.dtype)


class FacetAssembler:
    """Boundary-facet Map-Reduce: Robin matrices and Neumann loads that share
    the *volume* DoF numbering, so their reduce lands directly in the global
    system.  For matrix terms, the facet routing is built over the same
    ``num_dofs`` and merged CSR patterns are avoided by assembling into the
    volume pattern via an injection map (facet-nnz -> volume-nnz).

    A ``FacetAssembler`` is also the *integration domain* of boundary terms
    in the weak-form API — ``weakform.robin(alpha, on=fa)`` /
    ``weakform.neumann(g, on=fa)`` — where :meth:`context` supplies the
    facet geometry inside the fused assembly trace and
    :meth:`injection_into` supplies the nnz injection into the volume
    pattern of the assembling :class:`~repro.core.assembly.GalerkinAssembler`.
    """

    def __init__(self, space: FunctionSpace, facets: np.ndarray,
                 volume_routing=None, quad_order: int | None = None):
        assert space.value_size == 1, "facet terms implemented for scalar spaces"
        self.space = space
        mesh = space.mesh
        if mesh.cell_type != "tri":
            raise NotImplementedError("facet assembly: 2D triangles")
        el = get_element("P1_line")
        pts, w = el.default_rule(quad_order)
        self.w = jnp.asarray(w)
        self.phi = jnp.asarray(el.tabulate(pts))
        self.gradhat = jnp.asarray(el.tabulate_grad(pts))
        self.facets = np.asarray(facets, dtype=np.int64)       # (F, 2) vertex ids
        self.coords = jnp.asarray(mesh.points[self.facets])    # (F, 2, d)
        self._facet_dofs_dev = jnp.asarray(self.facets)
        self.vec_routing = build_vector_routing(self.facets, space.num_dofs)
        self.mat_routing = build_matrix_routing(self.facets, None, space.num_dofs)
        self._injections: dict = {}    # id(volume_routing) -> (routing, pos)
        self._vol_injection = None
        if volume_routing is not None:
            self._vol_injection = self.injection_into(volume_routing)

    def injection_into(self, volume_routing) -> np.ndarray:
        """Positions of this facet pattern's nnz inside a volume CSR pattern
        (precomputed numpy, cached per volume routing)."""
        hit = self._injections.get(id(volume_routing))
        if hit is not None:
            return hit[1]
        n = self.space.num_dofs
        vol_key = volume_routing.row_of_nnz * n + volume_routing.indices
        fac_key = self.mat_routing.row_of_nnz * n + self.mat_routing.indices
        pos = np.searchsorted(vol_key, fac_key)
        assert np.all(vol_key[pos] == fac_key), "facet entry outside volume pattern"
        # keep the routing alive so the id() key stays unique
        self._injections[id(volume_routing)] = (volume_routing, pos)
        return pos

    def context(self) -> forms.FormContext:
        return facet_context(
            self.coords, self.phi, self.gradhat, self.w,
            scalar_facet_dofs=self._facet_dofs_dev,
        )

    def neumann_load(self, g) -> jnp.ndarray:
        """∫_Γ g φ over the facet set → global (num_dofs,) vector."""
        ctx = self.context()
        f_local = forms.load(ctx, g)
        return reduce_vector(f_local, self.vec_routing)

    def robin_matrix_vals(self, alpha) -> jnp.ndarray:
        """∫_Γ α φφ — returns vals aligned with the *volume* CSR pattern."""
        ctx = self.context()
        k_local = forms.mass(ctx, alpha)
        vals = reduce_matrix(k_local, self.mat_routing)
        assert self._vol_injection is not None, "need volume_routing for Robin"
        return vals, self._vol_injection

    def add_robin(self, k: CSR, alpha) -> CSR:
        vals, inj = self.robin_matrix_vals(alpha)
        return dataclasses.replace(
            k, vals=k.vals.at[jnp.asarray(inj)].add(vals.astype(k.vals.dtype))
        )

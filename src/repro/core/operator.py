"""Matrix-free Galerkin operators: ``y = A(form) @ x`` without CSR values.

The assembled path materializes the global value vector (one float per nnz,
plus pattern arrays) before the Krylov loop ever
runs.  This module applies the operator **directly from the weak form**:

    gather   x_e = x[cell_dofs]                 (element-local Map input)
    apply    y_e = K_e(form) x_e                (per-element dense action)
    scatter  y   = S_vec · vec(y_e)             (the Sparse-Reduce, but onto
                                                 a vector — num_dofs segments
                                                 instead of nnz)

For the built-in kernels the per-element action is *fused*: diffusion applies
``𝒢ᵀ(w ρ (𝒢 x_e))`` through (E, Q, d) intermediates and never forms the
(E, k, k) element matrices — the same message-passing-on-the-sparsity-graph
structure that graph-Galerkin networks exploit matrix-free.  Unknown kernels
fall back to forming K_e on the fly (still no *global* values).

Storage strategies (the memory/speed dial):

=========  =====================================  ===========================
store      per-apply state beyond the plan        geometry work per apply
=========  =====================================  ===========================
"coords"   coefficient leaves only                full Stage-I recompute
"context"  the Stage-I FormContext (E·Q·k·d)      none (precomputed)
"local"    the element matrices (E·k²)            none (K_e precomputed)
=========  =====================================  ===========================

``"coords"`` shares the plan's coordinate array, so the operator adds
essentially no storage — DoF counts whose CSR values no longer fit stay
reachable.  ``"local"`` is the classical element-by-element (EbE) scheme.

Everything is a pytree: coefficient values and geometry are traced leaves,
the form signature and plan tables are identity-hashed aux data — so a
re-built operator with new coefficient *values* reuses the jitted apply
executable (zero retraces), and ``jvp``/``vjp`` flow through the apply like
any other jnp program.  :func:`repro.core.solvers.matfree_solve` adds the
O(1)-graph adjoint solve on top (grad through a matrix-free solve matches
the assembled ``sparse_solve`` path).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..telemetry import annotate
from . import forms, weakform
from .assembly import AssemblyPlan, PlanStatic, geometry_context, reduce_vector
from .sparse import _dev

__all__ = [
    "LinearOperator",
    "MatFreeOperator",
    "MatFreeFamily",
    "ShardedMatFreeOperator",
    "matfree_operator",
    "matfree_family",
    "n_matfree_traces",
]

_N_MF_TRACES = [0]


def n_matfree_traces() -> int:
    """Trace counter of the jitted matrix-free applies — re-applying with new
    coefficient/geometry *values* must not grow it (zero-retrace property)."""
    return _N_MF_TRACES[0]


class LinearOperator:
    """Minimal abstract interface the solver stack dispatches on.

    Anything exposing ``matvec`` / ``rmatvec`` / ``diagonal`` / ``shape`` can
    drive :func:`~repro.core.solvers.cg`,
    :func:`~repro.core.solvers.bicgstab`,
    :func:`~repro.core.solvers.jacobi_preconditioner` and
    :func:`~repro.core.solvers.matfree_solve`.  :class:`~repro.core.CSR`
    satisfies the protocol structurally; :class:`MatFreeOperator` is the
    matrix-free implementation.
    """

    shape: tuple[int, int]

    def matvec(self, x):  # pragma: no cover - interface
        raise NotImplementedError

    def rmatvec(self, x):  # pragma: no cover - interface
        raise NotImplementedError

    def diagonal(self):  # pragma: no cover - interface
        raise NotImplementedError

    def __matmul__(self, x):
        return self.matvec(x)


# ---------------------------------------------------------------------------
# Fused per-element actions: y_e = K_e x_e through (E, Q, ...) intermediates,
# never materializing the (E, k, k) element matrices.  One (action, transpose
# action, diagonal) triple per weak-form kernel; kernels without an entry
# fall back to forming K_e (still matrix-free at the global level).
# ---------------------------------------------------------------------------

# the diffusion action and diagonal are broadcast products and sums, not
# einsums, for the reason given in forms.diffusion (float64 batched dots
# with tiny minor dims blow up in HBM and compile slowly on a TPU)
def _diffusion_act(ctx, vs, xe, rho=None):
    rho_q = forms.eval_coefficient(rho, ctx)
    g = ctx.grad                                        # (E, Q, k, d)
    gu = jnp.sum(g * xe[:, None, :, None], axis=2)      # (E, Q, d)
    flux = (ctx.wdet * rho_q)[..., None] * gu
    return jnp.sum(g * flux[:, :, None, :], axis=(1, 3))


def _diffusion_diag(ctx, vs, rho=None):
    rho_q = forms.eval_coefficient(rho, ctx)
    return jnp.sum((ctx.wdet * rho_q)[..., None]
                   * jnp.sum(ctx.grad * ctx.grad, axis=-1), axis=1)


def _mass_act(ctx, vs, xe, c=None):
    c_q = forms.eval_coefficient(c, ctx)
    uq = jnp.einsum("qa,ea->eq", ctx.phi, xe)
    return jnp.einsum("eq,qa->ea", ctx.wdet * c_q * uq, ctx.phi)


def _mass_diag(ctx, vs, c=None):
    c_q = forms.eval_coefficient(c, ctx)
    return jnp.einsum("eq,qa,qa->ea", ctx.wdet * c_q, ctx.phi, ctx.phi)


def _advection_act(ctx, vs, xe, beta):
    d = ctx.grad.shape[-1]
    b_q = forms.eval_coefficient(beta, ctx, vector_size=d)
    gu = jnp.einsum("eqbi,eb->eqi", ctx.grad, xe)
    s = jnp.einsum("eqi,eqi->eq", b_q, gu)
    return jnp.einsum("eq,qa->ea", ctx.wdet * s, ctx.phi)


def _advection_act_t(ctx, vs, xe, beta):
    # Kᵀ: y_b = Σ_q ŵ|detJ| (β·𝒢_b) u_q with u_q the interpolated input
    d = ctx.grad.shape[-1]
    b_q = forms.eval_coefficient(beta, ctx, vector_size=d)
    uq = jnp.einsum("qa,ea->eq", ctx.phi, xe)
    return jnp.einsum("eq,eqi,eqbi->eb", ctx.wdet * uq, b_q, ctx.grad)


def _advection_diag(ctx, vs, beta):
    d = ctx.grad.shape[-1]
    b_q = forms.eval_coefficient(beta, ctx, vector_size=d)
    return jnp.einsum("eq,qa,eqi,eqai->ea", ctx.wdet, ctx.phi, b_q, ctx.grad)


def _aniso_act(ctx, vs, xe, a=None):
    d = ctx.grad.shape[-1]
    a_q = forms.eval_tensor_coefficient(a, ctx, d)
    gu = jnp.einsum("eqbj,eb->eqj", ctx.grad, xe)
    z = jnp.einsum("eqij,eqj->eqi", a_q, gu)
    return jnp.einsum("eq,eqai,eqi->ea", ctx.wdet, ctx.grad, z)


def _aniso_act_t(ctx, vs, xe, a=None):
    d = ctx.grad.shape[-1]
    a_q = jnp.swapaxes(forms.eval_tensor_coefficient(a, ctx, d), -1, -2)
    gu = jnp.einsum("eqbj,eb->eqj", ctx.grad, xe)
    z = jnp.einsum("eqij,eqj->eqi", a_q, gu)
    return jnp.einsum("eq,eqai,eqi->ea", ctx.wdet, ctx.grad, z)


def _aniso_diag(ctx, vs, a=None):
    d = ctx.grad.shape[-1]
    a_q = forms.eval_tensor_coefficient(a, ctx, d)
    return jnp.einsum("eq,eqai,eqij,eqaj->ea", ctx.wdet, ctx.grad, a_q, ctx.grad)


# kind -> (action, transpose action, diagonal); None → generic K_e fallback
_ACTIONS: dict[str, tuple] = {
    "diffusion": (_diffusion_act, _diffusion_act, _diffusion_diag),
    "mass": (_mass_act, _mass_act, _mass_diag),
    "advection": (_advection_act, _advection_act_t, _advection_diag),
    "anisotropic_diffusion": (_aniso_act, _aniso_act_t, _aniso_diag),
}


def _generic_act(kind, ctx, vs, xe, *coeffs, transpose=False):
    k_local = weakform.KERNELS[kind].fn(ctx, vs, *coeffs)
    sub = "eab,ea->eb" if transpose else "eab,eb->ea"
    return jnp.einsum(sub, k_local, xe)


def _generic_diag(kind, ctx, vs, *coeffs):
    k_local = weakform.KERNELS[kind].fn(ctx, vs, *coeffs)
    return jnp.einsum("eaa->ea", k_local)


# ---------------------------------------------------------------------------
# The operator
# ---------------------------------------------------------------------------

_STORES = ("coords", "context", "local")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)
class MatFreeOperator(LinearOperator):
    """``y = A(form) @ x`` straight from an :class:`AssemblyPlan` + lowered
    :class:`~repro.core.weakform.WeakForm` — build with
    :func:`matfree_operator`.

    Pytree layout: geometry (``coords`` | ``ctx`` | ``k_local``, per the
    storage strategy), coefficient ``leaves`` and the Dirichlet ``free_mask``
    are traced children; the plan tables, form signature and store tag are
    identity-hashed aux — so jit keys on the *signature* and re-applies with
    new values hit the compiled executable.
    """

    coords: jnp.ndarray | None      # (E, nv_geo, d)   store="coords"
    ctx: forms.FormContext | None   # Stage-I tensors  store="context"
    k_local: jnp.ndarray | None     # (E, k, k)        store="local"
    leaves: tuple                   # traced coefficient/scale leaves
    free_mask: jnp.ndarray | None   # (n,) 1=free, 0=Dirichlet (condensed)
    static: PlanStatic              # aux: plan tables
    spec: tuple                     # aux: lowered form signature
    store: str                      # aux: storage strategy tag

    # -- pytree ----------------------------------------------------------
    def tree_flatten(self):
        return (
            (self.coords, self.ctx, self.k_local, self.leaves, self.free_mask),
            (self.static, self.spec, self.store),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    # -- shape / dtype ----------------------------------------------------
    @property
    def shape(self) -> tuple[int, int]:
        return (self.static.num_dofs, self.static.num_dofs)

    def condensed(self, bc) -> "MatFreeOperator":
        """Dirichlet condensation as an operator wrapper: rows/columns of
        constrained DoFs are masked and a unit diagonal inserted —
        ``y = m·A(m·x) + (1−m)·x`` — matching
        :meth:`~repro.core.boundary.DirichletCondenser.apply_matrix_only`
        on the assembled matrix exactly."""
        return dataclasses.replace(self, free_mask=bc.free_mask)

    # -- the apply --------------------------------------------------------
    def _context(self) -> forms.FormContext:
        if self.ctx is not None:
            return self.ctx
        st = self.static
        return geometry_context(
            self.coords, st.geo_phi, st.geo_grad, st.phi, st.gradhat, st.w,
            scalar_cell_dofs=st.scalar_cell_dofs,
        )

    def _term_values(self):
        leaf = iter(self.leaves)
        for kind, domain, desc in self.spec:
            vals = [next(leaf) if d == weakform.TRACED else d[1] for d in desc]
            *coeffs, scale = vals
            yield kind, coeffs, scale

    def _local_apply(self, xe, transpose: bool):
        if self.k_local is not None:
            sub = "eab,ea->eb" if transpose else "eab,eb->ea"
            return jnp.einsum(sub, self.k_local, xe)
        ctx, vs = self._context(), self.static.value_size
        out = None
        for kind, coeffs, scale in self._term_values():
            entry = _ACTIONS.get(kind)
            if entry is not None:
                act = entry[1] if transpose else entry[0]
                y = act(ctx, vs, xe, *coeffs)
            else:
                y = _generic_act(kind, ctx, vs, xe, *coeffs, transpose=transpose)
            y = y * jnp.asarray(scale)
            out = y if out is None else out + y
        return out

    def _apply_impl(self, x, transpose: bool):
        _N_MF_TRACES[0] += 1
        telemetry.count_trace("matfree", self.static, self.spec,
                              backend=self.store)
        st = self.static
        if self.free_mask is not None:
            m = self.free_mask.astype(x.dtype)
            x_in = m * x
        else:
            x_in = x
        with annotate("tg.matfree.gather"):
            xe = x_in[_dev(st.cell_dofs)]                # gather (E, k)
        with annotate("tg.matfree.action"):
            y_local = self._local_apply(xe, transpose)   # per-element apply
        with annotate("tg.matfree.scatter"):
            y = reduce_vector(y_local, st.vec_routing, st.reduce_mode)
        if self.free_mask is not None:
            y = m * y + (1.0 - m) * x
        return y

    def matvec(self, x: jnp.ndarray) -> jnp.ndarray:
        """y = A x — jitted, cached per (plan, form signature, store)."""
        return _apply_jit(self, x, False)

    def rmatvec(self, x: jnp.ndarray) -> jnp.ndarray:
        """y = Aᵀ x.  Galerkin row and column DoF maps coincide, so the
        global transpose is the same gather → apply → scatter pipeline with
        the *per-element* apply transposed (kernels declared ``symmetric``
        in :data:`repro.core.weakform.KERNELS` reuse the forward action)."""
        if self.k_local is None and all(
            weakform.KERNELS[kind].symmetric for kind, _, _ in self.spec
        ):
            return _apply_jit(self, x, False)
        return _apply_jit(self, x, True)

    def diagonal(self) -> jnp.ndarray:
        """diag(A) by a diagonal-only assembly: per-element diagonals reduce
        through the vector routing — O(E·k) work and memory, no nnz vector —
        feeding :func:`~repro.core.solvers.jacobi_preconditioner`."""
        return _diag_jit(self)

    def _diag_local(self):
        if self.k_local is not None:
            return jnp.einsum("eaa->ea", self.k_local)
        ctx, vs = self._context(), self.static.value_size
        d_local = None
        for kind, coeffs, scale in self._term_values():
            entry = _ACTIONS.get(kind)
            d = (
                entry[2](ctx, vs, *coeffs)
                if entry is not None
                else _generic_diag(kind, ctx, vs, *coeffs)
            )
            d = d * jnp.asarray(scale)
            d_local = d if d_local is None else d_local + d
        return d_local

    def _diag_impl(self):
        st = self.static
        d_local = self._diag_local()
        diag = reduce_vector(d_local, st.vec_routing, st.reduce_mode)
        if self.free_mask is not None:
            m = self.free_mask.astype(diag.dtype)
            diag = m * diag + (1.0 - m)
        return diag

    def element_matrices(self) -> jnp.ndarray:
        """The per-element dense tensors ``K_e`` of this form, ``(E, k, k)``
        — the Map-stage output the tentpole element tensor-algebra layer
        (:mod:`repro.core.elemalg`) factorizes, condenses and inverts.
        ``store="local"`` operators return their stored tensors; the other
        stores compute them on demand (no global matrix either way).  The
        Dirichlet ``free_mask`` is *not* applied — callers mask per-element
        rows/columns themselves (see ``elemalg.masked_element_matrices``)."""
        if self.k_local is not None:
            return self.k_local
        ctx, vs = self._context(), self.static.value_size
        k_local = None
        for kind, coeffs, scale in self._term_values():
            k = weakform.KERNELS[kind].fn(ctx, vs, *coeffs)
            k = k * jnp.asarray(scale)
            k_local = k if k_local is None else k_local + k
        return k_local

    def is_spd(self) -> bool:
        """True when every kernel in the form signature is declared SPD
        (``repro.core.weakform.KERNELS[kind].spd``) — drives the
        Cholesky-vs-LU factorization choice in :mod:`repro.core.elemalg`.
        ``store="local"`` operators erase coefficient info, so they only
        keep the kind tags — the declaration still resolves."""
        return all(weakform.KERNELS[kind].spd for kind, _, _ in self.spec)

    def sharded(self, mesh=None, axis_name: str | None = None
                ) -> "ShardedMatFreeOperator":
        """This operator with its apply partitioned over the element axis of
        a device mesh (defaults to :func:`repro.sharding.fem_mesh` over all
        local devices) — see :class:`ShardedMatFreeOperator`."""
        from ..sharding.partitioning import FEM_MESH_AXIS, fem_mesh

        axis = FEM_MESH_AXIS if axis_name is None else axis_name
        if mesh is None:
            mesh = fem_mesh(axis_name=axis)
        return ShardedMatFreeOperator(self, mesh, axis)

    def in_axes(self, leaf_axes=None, coords_ax=None, free_mask_ax=None,
                k_local_ax=None, ctx_ax=None) -> "MatFreeOperator":
        """An operator-shaped ``jax.vmap`` axes object for this pytree: the
        same aux data (so tree structures match) with each traced child
        replaced by its batch axis (``0``) or ``None`` (shared).

        ``leaf_axes`` aligns with ``self.leaves`` (defaults to all-shared);
        the other slots default to shared.  This is what lets a family of
        operators with ``(B, ...)`` coefficient leaves vmap through
        ``matvec`` / ``diagonal`` / :func:`~repro.core.solvers.matfree_solve`
        without hand-building the pytree of axes.
        """
        if leaf_axes is None:
            leaf_axes = (None,) * len(self.leaves)
        if len(leaf_axes) != len(self.leaves):
            raise ValueError(
                f"leaf_axes has {len(leaf_axes)} entries but the operator "
                f"carries {len(self.leaves)} traced leaves"
            )
        return MatFreeOperator(
            coords=coords_ax, ctx=ctx_ax, k_local=k_local_ax,
            leaves=tuple(leaf_axes), free_mask=free_mask_ax,
            static=self.static, spec=self.spec, store=self.store,
        )

    # -- introspection ----------------------------------------------------
    def state_bytes(self) -> int:
        """Bytes of traced state this operator carries *beyond* the plan —
        the matrix-free side of the memory trade-off table (a ``"coords"``
        operator shares the plan's coordinates: ~coefficients only)."""
        leaves = [self.k_local, self.free_mask, *self.leaves]
        if self.store == "context":
            leaves += list(jax.tree_util.tree_leaves(self.ctx))
        return sum(
            v.nbytes for v in leaves
            if v is not None and hasattr(v, "nbytes")
        )


@partial(jax.jit, static_argnums=(2,))
def _apply_jit(op: MatFreeOperator, x, transpose: bool):
    return op._apply_impl(x, transpose)


@jax.jit
def _diag_jit(op: MatFreeOperator):
    return op._diag_impl()


def matfree_operator(plan: AssemblyPlan, form, store: str = "context",
                     coords=None) -> MatFreeOperator:
    """Build the matrix-free operator of a bilinear form on a plan.

    ``store`` picks the memory/speed point (see module docstring):
    ``"context"`` (default) precomputes the Stage-I geometry once for the
    fastest apply; ``"coords"`` recomputes it per apply and stores nothing
    beyond the plan's coordinates; ``"local"`` precomputes the (E, k, k)
    element matrices (classical EbE).  All three are differentiable w.r.t.
    coefficients and coordinates and share the assembled operator's values
    to machine precision: ``op.matvec(x) == assemble(plan, form).matvec(x)``.
    """
    if store not in _STORES:
        raise ValueError(f"unknown store {store!r}; use one of {_STORES}")
    spec, leaves = weakform.lower(form, weakform.MATRIX)
    if any(domain is not None for _, domain, _ in spec):
        raise NotImplementedError(
            "matrix-free apply supports volume terms only: assemble facet "
            "terms into a CSR and combine, or condense them into the RHS"
        )
    st = plan.static
    if st.cell_dofs is None:
        raise ValueError(
            "plan.static.cell_dofs is missing — rebuild the plan with "
            "repro.core.build_plan (older pickled plans predate the "
            "matrix-free subsystem)"
        )
    c = plan.coords if coords is None else coords
    op = MatFreeOperator(
        coords=c, ctx=None, k_local=None, leaves=leaves, free_mask=None,
        static=st, spec=spec, store=store,
    )
    if store == "context":
        op = dataclasses.replace(
            op, ctx=geometry_context(
                c, st.geo_phi, st.geo_grad, st.phi, st.gradhat, st.w,
                scalar_cell_dofs=st.scalar_cell_dofs,
            ), coords=None,
        )
    elif store == "local":
        op = dataclasses.replace(
            op, k_local=op.element_matrices(), coords=None, leaves=(),
            spec=tuple((kind, None, ()) for kind, _, _ in spec),
        )
    telemetry.gauge_set("operator_state_bytes", op.state_bytes(), store=store)
    return op


# ---------------------------------------------------------------------------
# Multi-device sharding: the same gather → action → scatter apply, with the
# element axis partitioned over a device mesh (per-device partial scatter +
# one psum) — a single Krylov solve spans every device with no materialized
# matrix and no element-sized intermediate replicated anywhere.
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)
class ShardedMatFreeOperator(LinearOperator):
    """A :class:`MatFreeOperator` whose apply is ``shard_map``-partitioned
    over the element axis (the ``repro.sharding`` FEM mesh axis).

    Per apply, each device gathers from the replicated ``(n,)`` vector into
    its *element shard* only, runs the per-element fused action on that
    shard, reduces it to a partial touched-DoF vector, and one ``psum``
    completes the Sparse-Reduce — the element-sized intermediates (the
    gather, the (E, Q, ...) action state, the local results) exist only as
    per-device shards.  ``matvec`` / ``rmatvec`` / ``diagonal`` all ride the
    same partitioning, so :func:`~repro.core.solvers.matfree_solve` (and its
    custom-vjp adjoint solve + operator-cotangent pullback) runs sharded
    end-to-end.  Build with :meth:`MatFreeOperator.sharded`.

    Pytree: the wrapped operator is the traced child; the device mesh and
    axis name are aux — re-applies with new coefficient values reuse the
    compiled sharded executable.
    """

    op: MatFreeOperator      # traced child
    mesh: Any                # aux: jax.sharding.Mesh
    axis_name: str           # aux

    # -- pytree ----------------------------------------------------------
    def tree_flatten(self):
        return (self.op,), (self.mesh, self.axis_name)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)

    # -- structure --------------------------------------------------------
    @property
    def shape(self) -> tuple[int, int]:
        return self.op.shape

    @property
    def static(self) -> PlanStatic:
        return self.op.static

    def condensed(self, bc) -> "ShardedMatFreeOperator":
        """Dirichlet condensation — same apply wrapper as the single-device
        operator (the masking runs on the replicated vector, outside the
        sharded region)."""
        return dataclasses.replace(self, op=self.op.condensed(bc))

    def state_bytes(self) -> int:
        return self.op.state_bytes()

    # -- applies ----------------------------------------------------------
    def matvec(self, x: jnp.ndarray) -> jnp.ndarray:
        return _sharded_apply_jit(self, x, False)

    def rmatvec(self, x: jnp.ndarray) -> jnp.ndarray:
        op = self.op
        if op.k_local is None and all(
            weakform.KERNELS[kind].symmetric for kind, _, _ in op.spec
        ):
            return _sharded_apply_jit(self, x, False)
        return _sharded_apply_jit(self, x, True)

    def diagonal(self) -> jnp.ndarray:
        return _sharded_diag_jit(self)


def _shard_scaffold(sop: ShardedMatFreeOperator):
    """Static partitioning tables + the traced geometry/leaf shards and their
    PartitionSpecs for one sharded apply/diagonal trace."""
    from jax.sharding import PartitionSpec as P

    op, mesh, axis_name = sop.op, sop.mesh, sop.axis_name
    st = op.static
    ndev = mesh.shape[axis_name]
    cd = np.asarray(st.cell_dofs)
    e = cd.shape[0]
    pad = (-e) % ndev
    routing = st.vec_routing
    n_seg = routing.touched.shape[0]
    slots = routing.seg_ids_unsorted.shape[0] // e

    # static numpy precompute: padded rows carry out-of-range segment ids
    # (dropped by segment_sum) and replicate the last element's DoFs
    seg = routing.seg_ids_unsorted.reshape(e, slots)
    if pad:
        seg = np.concatenate([seg, np.full((pad, slots), n_seg, seg.dtype)])
        cd = np.concatenate([cd, np.broadcast_to(cd[-1:], (pad,) + cd.shape[1:])])

    def pad_rows(a):
        if not pad:
            return a
        return jnp.concatenate(
            [a, jnp.broadcast_to(a[-1:], (pad,) + a.shape[1:])]
        )

    shard, rep = P(axis_name), P()

    # traced geometry state, store-dependent; ``rebuild`` reassembles a
    # shard-local operator inside the shard_map body
    if op.k_local is not None:
        geo = (pad_rows(op.k_local),)
        geo_specs = (shard,)

        def rebuild(inner, geo_s, leaves_s):
            return dataclasses.replace(inner, k_local=geo_s[0],
                                       leaves=leaves_s)
    elif op.ctx is not None:
        ctx = op.ctx
        fields = [("w", ctx.w, rep), ("phi", ctx.phi, rep),
                  ("detj", pad_rows(ctx.detj), shard)]
        if ctx.grad is not None:
            fields.append(("grad", pad_rows(ctx.grad), shard))
        fields.append(("xq", pad_rows(ctx.xq), shard))
        if ctx.scalar_cell_dofs is not None:
            fields.append(
                ("scalar_cell_dofs", pad_rows(ctx.scalar_cell_dofs), shard))
        names = tuple(f[0] for f in fields)
        geo = tuple(f[1] for f in fields)
        geo_specs = tuple(f[2] for f in fields)

        def rebuild(inner, geo_s, leaves_s):
            d = dict(zip(names, geo_s))
            ctx_s = forms.FormContext(
                w=d["w"], phi=d["phi"], detj=d["detj"], grad=d.get("grad"),
                xq=d["xq"], scalar_cell_dofs=d.get("scalar_cell_dofs"),
            )
            return dataclasses.replace(inner, ctx=ctx_s, leaves=leaves_s)
    else:  # store == "coords": Stage-I geometry recomputed per shard
        scd = st.scalar_cell_dofs
        geo = (pad_rows(op.coords),) \
            + ((pad_rows(jnp.asarray(scd)),) if scd is not None else ())
        geo_specs = (shard,) + ((shard,) if scd is not None else ())

        def rebuild(inner, geo_s, leaves_s):
            ctx_s = geometry_context(
                geo_s[0], st.geo_phi, st.geo_grad, st.phi, st.gradhat, st.w,
                scalar_cell_dofs=geo_s[1] if len(geo_s) > 1 else None,
            )
            return dataclasses.replace(inner, ctx=ctx_s, coords=None,
                                       leaves=leaves_s)

    # element-aligned coefficient leaves shard; everything else replicates
    # (mirrors the leaf resolution of the sharded assembly path)
    leaf_flags = tuple(
        jnp.ndim(lv) >= 1 and jnp.shape(lv)[0] == e for lv in op.leaves
    )
    leaves_p = tuple(
        pad_rows(jnp.asarray(lv)) if flag else jnp.asarray(lv)
        for lv, flag in zip(op.leaves, leaf_flags)
    )
    leaf_specs = tuple(shard if flag else rep for flag in leaf_flags)

    inner = dataclasses.replace(op, free_mask=None)
    return (inner, rebuild, jnp.asarray(cd), jnp.asarray(seg), n_seg,
            geo, geo_specs, leaves_p, leaf_specs, routing)


def _sharded_mf_impl(sop: ShardedMatFreeOperator, x, transpose: bool):
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    _N_MF_TRACES[0] += 1
    op = sop.op
    telemetry.count_trace("matfree", op.static, op.spec,
                          backend=f"sharded_{op.store}")
    (inner, rebuild, cd, seg, n_seg, geo, geo_specs, leaves_p, leaf_specs,
     routing) = _shard_scaffold(sop)
    axis_name = sop.axis_name
    n_geo = len(geo)

    if op.free_mask is not None:
        m = op.free_mask.astype(x.dtype)
        x_in = m * x
    else:
        x_in = x

    def body(x_rep, cd_s, seg_s, *rest):
        op_s = rebuild(inner, rest[:n_geo], rest[n_geo:])
        xe = x_rep[cd_s]                               # shard-local gather
        y_local = op_s._local_apply(xe, transpose)     # per-element action
        part = jax.ops.segment_sum(
            y_local.reshape(-1), seg_s.reshape(-1), num_segments=n_seg
        )
        return jax.lax.psum(part, axis_name)

    shard = P(axis_name)
    sharded = shard_map(
        body, mesh=sop.mesh,
        in_specs=(P(), shard, shard) + geo_specs + leaf_specs,
        out_specs=P(),
        check_rep=False,
    )
    with annotate("tg.matfree.sharded_apply"):
        packed = sharded(x_in, cd, seg, *geo, *leaves_p)
    out = jnp.zeros((routing.num_dofs,), dtype=packed.dtype)
    y = out.at[routing.touched_dev].set(packed)
    if op.free_mask is not None:
        y = m * y + (1.0 - m) * x
    return y


def _sharded_mf_diag_impl(sop: ShardedMatFreeOperator):
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    op = sop.op
    (inner, rebuild, cd, seg, n_seg, geo, geo_specs, leaves_p, leaf_specs,
     routing) = _shard_scaffold(sop)
    axis_name = sop.axis_name
    n_geo = len(geo)

    def body(seg_s, *rest):
        op_s = rebuild(inner, rest[:n_geo], rest[n_geo:])
        d_local = op_s._diag_local()
        part = jax.ops.segment_sum(
            d_local.reshape(-1), seg_s.reshape(-1), num_segments=n_seg
        )
        return jax.lax.psum(part, axis_name)

    shard = P(axis_name)
    sharded = shard_map(
        body, mesh=sop.mesh,
        in_specs=(shard,) + geo_specs + leaf_specs,
        out_specs=P(),
        check_rep=False,
    )
    packed = sharded(seg, *geo, *leaves_p)
    out = jnp.zeros((routing.num_dofs,), dtype=packed.dtype)
    diag = out.at[routing.touched_dev].set(packed)
    if op.free_mask is not None:
        m = op.free_mask.astype(diag.dtype)
        diag = m * diag + (1.0 - m)
    return diag


@partial(jax.jit, static_argnums=(2,))
def _sharded_apply_jit(sop: ShardedMatFreeOperator, x, transpose: bool):
    return _sharded_mf_impl(sop, x, transpose)


@jax.jit
def _sharded_diag_jit(sop: ShardedMatFreeOperator):
    return _sharded_mf_diag_impl(sop)


# ---------------------------------------------------------------------------
# Batched families: B same-signature operators on ONE shared plan
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)
class MatFreeFamily(LinearOperator):
    """A *family* of B matrix-free operators sharing one plan and one form
    signature — the matrix-free twin of :class:`~repro.core.sparse.BatchedCSR`.

    ``op`` is a :class:`MatFreeOperator` whose batched coefficient leaves
    carry a leading ``(B, ...)`` axis (slots listed in ``leaf_axes``); the
    geometry, plan tables and Dirichlet mask are shared across the family.
    Every method vmaps the single-operator apply with the right axes, so the
    whole family runs in ONE executable:

    * ``matvec(X)`` / ``rmatvec(X)`` — ``(B, n)`` (a ``(n,)`` input
      broadcasts across the family),
    * ``diagonal()`` — ``(B, n)`` diagonals (family Jacobi preconditioning),
    * ``condensed(bc)`` — shared-mask Dirichlet condensation,
    * ``family[i]`` — instance ``i`` as a plain :class:`MatFreeOperator`.

    :func:`repro.core.solvers.matfree_solve_batched` solves the family with
    one vmapped adjoint :func:`~repro.core.solvers.matfree_solve` — gradients
    match per-instance adjoint solves.  Built by :func:`matfree_family`.
    """

    op: MatFreeOperator      # traced child: batched-leaf operator
    batch: int               # aux: family size B
    leaf_axes: tuple         # aux: per-leaf vmap axis (0 | None)
    coords_ax: Any = None    # aux: coords batch axis (0 | None)
    k_local_ax: Any = None   # aux: element-matrix batch axis (store="local")

    # -- pytree ----------------------------------------------------------
    def tree_flatten(self):
        return (self.op,), (self.batch, self.leaf_axes, self.coords_ax,
                            self.k_local_ax)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)

    # -- structure --------------------------------------------------------
    @property
    def shape(self) -> tuple[int, int]:
        return self.op.shape  # per-instance shape (like BatchedCSR)

    def in_axes(self) -> MatFreeOperator:
        """The operator-shaped ``vmap`` axes pytree of this family."""
        return self.op.in_axes(leaf_axes=self.leaf_axes,
                               coords_ax=self.coords_ax,
                               k_local_ax=self.k_local_ax)

    def __getitem__(self, b: int) -> MatFreeOperator:
        if not isinstance(b, (int, np.integer)):
            raise TypeError(
                f"MatFreeFamily indices must be int, got {type(b).__name__}"
            )
        leaves = tuple(
            leaf[b] if ax == 0 else leaf
            for leaf, ax in zip(self.op.leaves, self.leaf_axes)
        )
        coords = self.op.coords
        if self.coords_ax == 0 and coords is not None:
            coords = coords[b]
        k_local = self.op.k_local
        if self.k_local_ax == 0 and k_local is not None:
            k_local = k_local[b]
        return dataclasses.replace(self.op, leaves=leaves, coords=coords,
                                   k_local=k_local)

    def condensed(self, bc) -> "MatFreeFamily":
        """Shared-mask Dirichlet condensation of the whole family (the mask
        broadcasts — one ``(n,)`` mask for all B instances)."""
        return dataclasses.replace(self, op=self.op.condensed(bc))

    # -- vmapped applies ---------------------------------------------------
    def _vmap(self, fn, x=None):
        ax = self.in_axes()
        if x is None:
            return jax.vmap(fn, in_axes=(ax,))(self.op)
        in_x = None if jnp.ndim(x) == 1 else 0
        return jax.vmap(fn, in_axes=(ax, in_x))(self.op, x)

    def matvec(self, x: jnp.ndarray) -> jnp.ndarray:
        """``Y_b = A_b @ x_b`` for ``x: (B, n)`` (``(n,)`` broadcasts)."""
        return self._vmap(lambda o, xi: o.matvec(xi), x)

    def rmatvec(self, x: jnp.ndarray) -> jnp.ndarray:
        return self._vmap(lambda o, xi: o.rmatvec(xi), x)

    def diagonal(self) -> jnp.ndarray:
        """Per-instance diagonals ``(B, n)`` by one vmapped diagonal-only
        assembly — the family Jacobi preconditioner input."""
        return self._vmap(lambda o: o.diagonal())

    def state_bytes(self) -> int:
        return self.op.state_bytes()


def matfree_family(plan: AssemblyPlan, form, leaves_batch=None,
                   store: str = "context", coords_batch=None) -> MatFreeFamily:
    """Build a batched matrix-free operator family on one shared plan.

    ``form`` is the template form; ``leaves_batch`` batches its traced
    leaves with the same conventions as
    :func:`~repro.core.assembly.assemble_batched` — a tuple aligned with the
    form's traced leaves in slot order (per term: coefficients, then the
    scale factor), each entry ``None`` (shared) or an array with a leading
    ``(B, ...)`` batch axis; a bare array batches the first slot::

        fam = matfree_family(plan, wf.diffusion(rho_b[0]),
                             leaves_batch=(rho_b, None))     # (B, E) coeffs

    ``coords_batch: (B, E, nv, d)`` batches the geometry instead of (or in
    addition to) the coefficients; batched geometry forces ``store="coords"``
    (per-apply geometry recompute — the precomputed-context layout would
    have to materialize B full contexts).
    """
    spec, leaves0 = weakform.lower(form, weakform.MATRIX)
    if any(domain is not None for _, domain, _ in spec):
        raise NotImplementedError(
            "matrix-free families support volume terms only (same restriction "
            "as the single-instance matrix-free apply)"
        )
    if leaves_batch is None:
        leaves_batch = (None,) * len(leaves0)
    elif not isinstance(leaves_batch, (tuple, list)):
        leaves_batch = (leaves_batch,) + (None,) * (len(leaves0) - 1)
    if len(leaves_batch) != len(leaves0):
        raise ValueError(
            f"leaves_batch has {len(leaves_batch)} slots but the form lowers "
            f"to {len(leaves0)} traced leaves (per term: coefficients, then "
            "the scale factor) — pass None for slots shared across the family"
        )
    sizes = {int(jnp.shape(b)[0]) for b in leaves_batch if b is not None}
    if coords_batch is not None:
        sizes.add(int(jnp.shape(coords_batch)[0]))
        if store != "coords":
            store = "coords"
    if not sizes:
        raise ValueError(
            "nothing is batched: pass coords_batch and/or batched leaves"
        )
    if len(sizes) > 1:
        raise ValueError(f"inconsistent family batch sizes {sorted(sizes)}")
    (batch,) = sizes
    merged = tuple(
        b if b is not None else l0 for b, l0 in zip(leaves_batch, leaves0)
    )
    leaf_axes = tuple(0 if b is not None else None for b in leaves_batch)
    coords_ax = 0 if coords_batch is not None else None

    if store == "local":
        # per-instance element matrices, built by one vmapped local assembly:
        # k_local becomes the only (batched) traced leaf, like the
        # single-instance "local" store
        base = matfree_operator(plan, form, store="context")
        ctx, vs = base.ctx, plan.static.value_size

        def k_of(lv):
            k_local = None
            leaf = iter(lv)
            for kind, _, desc in spec:
                vals = [next(leaf) if d == weakform.TRACED else d[1]
                        for d in desc]
                *coeffs, scale = vals
                k = weakform.KERNELS[kind].fn(ctx, vs, *coeffs)
                k = k * jnp.asarray(scale)
                k_local = k if k_local is None else k_local + k
            return k_local

        k_b = jax.vmap(k_of, in_axes=(leaf_axes,))(merged)
        op = dataclasses.replace(
            base, k_local=k_b, ctx=None, coords=None, leaves=(),
            spec=tuple((kind, None, ()) for kind, _, _ in spec),
            store="local",
        )
        return MatFreeFamily(op=op, batch=batch, leaf_axes=(),
                             coords_ax=None, k_local_ax=0)
    coords = plan.coords if coords_batch is None else coords_batch
    op = matfree_operator(plan, form, store=store,
                          coords=coords if coords_ax is None else None)
    if coords_ax == 0:
        op = dataclasses.replace(op, coords=coords)
    op = dataclasses.replace(op, leaves=merged)
    telemetry.gauge_set("operator_state_bytes", op.state_bytes(),
                        store=f"family_{store}")
    return MatFreeFamily(op=op, batch=batch, leaf_axes=leaf_axes,
                         coords_ax=coords_ax)

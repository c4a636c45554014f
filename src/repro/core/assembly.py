"""TensorGalerkin: Batch-Map + Sparse-Reduce assembly (the paper's core).

The assembly subsystem is a **functional core** behind a thin class facade:

* :func:`geometry_context` — Stage-I geometry: batched Jacobians, closed-form
  inverses/determinants, push-forward gradients (Alg. 1, lines 1–3).
* :class:`AssemblyPlan` — a frozen, pytree-registered value holding one
  (mesh topology × element × quadrature) signature: the static quadrature /
  element tables and Stage-II routing live in identity-hashed aux data
  (:class:`PlanStatic`), the traced ``coords`` array is the single pytree
  leaf.  Plans cross ``jit`` / ``vmap`` / ``grad`` boundaries like any other
  value; build one with :func:`build_plan`.
* Pure top-level entry points that close over **nothing**:
  :func:`assemble` / :func:`assemble_rhs` (single instance, jit-cached per
  form signature), :func:`assemble_batched` / :func:`assemble_rhs_batched`
  (one fused Map over ``(B, E, ...)`` and one Reduce per instance via
  ``vmap`` — B coefficient-sets / geometries in a single XLA executable,
  zero retraces across the batch), and :func:`assemble_sharded` /
  :func:`assemble_rhs_sharded` (opt-in ``shard_map`` partitioning of the
  element axis of the Map stage across devices, Reduce completed by one
  all-reduce over partial nnz contributions).
* :class:`GalerkinAssembler` — the cache-owning facade over a plan: every
  historical call site keeps working; new code may use the plan functions
  directly.  A multi-term form traces **one fused Map** (all volume kernels
  against a shared geometry context, built inside the jit boundary) and
  **one Reduce**; facet terms inject into the volume CSR pattern.  Jaxprs
  contain no element-indexed Python constructs — the JAX analogue of the
  O(1)-graph property.
* Deprecated shims ``assemble_stiffness`` / ``assemble_mass`` /
  ``assemble_elasticity`` / ``assemble_load`` / ``assemble_reaction_load``
  forward to the form API one term at a time (with a ``DeprecationWarning``).
* Baselines for the paper's comparison: a Python per-element scatter-add loop
  (the "white box" of Fig. 1) and a dense ``.at[].add()`` scatter.
"""

from __future__ import annotations

import dataclasses
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..telemetry import annotate
from . import forms, weakform
from .elements import get_element
from .mesh import FunctionSpace
from .routing import (
    MatrixRouting,
    VectorRouting,
    build_matrix_routing,
    build_vector_routing,
    scatter_free,
    table_segment_sum,
)
from .sparse import CSR, BatchedCSR

__all__ = [
    "AssemblyPlan",
    "PlanStatic",
    "build_plan",
    "assemble",
    "assemble_rhs",
    "assemble_batched",
    "assemble_rhs_batched",
    "assemble_sharded",
    "assemble_rhs_sharded",
    "GalerkinAssembler",
    "geometry_context",
    "facet_context",
    "clear_assembly_caches",
    "n_core_traces",
]


# ---------------------------------------------------------------------------
# Stage I geometry helpers (closed-form small-matrix linear algebra: these
# shapes (d ≤ 3) would be crippled by generic LU on TPU; adjugate formulas
# keep everything element-parallel on the VPU)
# ---------------------------------------------------------------------------

def _det(j: jnp.ndarray) -> jnp.ndarray:
    d = j.shape[-1]
    if d == 1:
        return j[..., 0, 0]
    if d == 2:
        return j[..., 0, 0] * j[..., 1, 1] - j[..., 0, 1] * j[..., 1, 0]
    if d == 3:
        return (
            j[..., 0, 0] * (j[..., 1, 1] * j[..., 2, 2] - j[..., 1, 2] * j[..., 2, 1])
            - j[..., 0, 1] * (j[..., 1, 0] * j[..., 2, 2] - j[..., 1, 2] * j[..., 2, 0])
            + j[..., 0, 2] * (j[..., 1, 0] * j[..., 2, 1] - j[..., 1, 1] * j[..., 2, 0])
        )
    raise ValueError(d)


def _inv(j: jnp.ndarray, det: jnp.ndarray) -> jnp.ndarray:
    d = j.shape[-1]
    if d == 1:
        return 1.0 / j
    if d == 2:
        adj = jnp.stack(
            [
                jnp.stack([j[..., 1, 1], -j[..., 0, 1]], -1),
                jnp.stack([-j[..., 1, 0], j[..., 0, 0]], -1),
            ],
            -2,
        )
        return adj / det[..., None, None]
    if d == 3:
        c00 = j[..., 1, 1] * j[..., 2, 2] - j[..., 1, 2] * j[..., 2, 1]
        c01 = j[..., 0, 2] * j[..., 2, 1] - j[..., 0, 1] * j[..., 2, 2]
        c02 = j[..., 0, 1] * j[..., 1, 2] - j[..., 0, 2] * j[..., 1, 1]
        c10 = j[..., 1, 2] * j[..., 2, 0] - j[..., 1, 0] * j[..., 2, 2]
        c11 = j[..., 0, 0] * j[..., 2, 2] - j[..., 0, 2] * j[..., 2, 0]
        c12 = j[..., 0, 2] * j[..., 1, 0] - j[..., 0, 0] * j[..., 1, 2]
        c20 = j[..., 1, 0] * j[..., 2, 1] - j[..., 1, 1] * j[..., 2, 0]
        c21 = j[..., 0, 1] * j[..., 2, 0] - j[..., 0, 0] * j[..., 2, 1]
        c22 = j[..., 0, 0] * j[..., 1, 1] - j[..., 0, 1] * j[..., 1, 0]
        adj = jnp.stack(
            [
                jnp.stack([c00, c01, c02], -1),
                jnp.stack([c10, c11, c12], -1),
                jnp.stack([c20, c21, c22], -1),
            ],
            -2,
        )
        return adj / det[..., None, None]
    raise ValueError(d)


def geometry_context(
    coords: jnp.ndarray,
    geo_phi: jnp.ndarray,
    geo_grad: jnp.ndarray,
    phi: jnp.ndarray,
    gradhat: jnp.ndarray,
    w: jnp.ndarray,
    scalar_cell_dofs=None,
) -> forms.FormContext:
    """Build the Stage-I :class:`FormContext` from batched coordinates.

    coords: (E, nv_geo, d); geo_phi/geo_grad: geometric element tables
    (Q, nv_geo[, d]); phi/gradhat: field element tables (Q, k[, d]).
    Fully differentiable w.r.t. ``coords`` (shape optimization).
    """
    # J_eqij = Σ_a X_eai ĝeo_qaj     (Alg. 1 line 1)
    j = jnp.einsum("eai,qaj->eqij", coords, geo_grad)
    det = _det(j)
    jinv = _inv(j, det)
    detj = jnp.abs(det)
    # push-forward 𝒢_eqai = Σ_j (J⁻¹)_ji ĝ_qaj   (Alg. 1 line 2)
    grad = jnp.einsum("eqji,qaj->eqai", jinv, gradhat)
    xq = jnp.einsum("qa,eai->eqi", geo_phi, coords)
    return forms.FormContext(
        w=w, phi=phi, detj=detj, grad=grad, xq=xq,
        scalar_cell_dofs=scalar_cell_dofs,
    )


def facet_context(
    coords: jnp.ndarray, phi: jnp.ndarray, gradhat: jnp.ndarray, w: jnp.ndarray,
    scalar_facet_dofs=None,
) -> forms.FormContext:
    """Geometry for (d-1)-facets embedded in R^d: surface measure
    √det(JᵀJ) replaces |det J| (used for Neumann/Robin boundary terms, which
    route through the *same* Map-Reduce pipeline — paper SM B.1.5)."""
    j = jnp.einsum("eai,qaj->eqij", coords, gradhat)     # (F, Q, d, d-1)
    jtj = jnp.einsum("eqij,eqik->eqjk", j, j)
    measure = jnp.sqrt(_det(jtj))
    xq = jnp.einsum("qa,eai->eqi", phi, coords)
    return forms.FormContext(
        w=w, phi=phi, detj=measure, grad=None, xq=xq,
        scalar_cell_dofs=scalar_facet_dofs,
    )


# ---------------------------------------------------------------------------
# Stage II reduce
# ---------------------------------------------------------------------------

def reduce_matrix(k_local: jnp.ndarray, routing: MatrixRouting, mode: str = "sorted"):
    """``S_mat · vec(K_local)`` as a deterministic segment reduction, in one
    of the modes of :mod:`repro.core.routing`."""
    v = k_local.reshape(-1)
    if mode == "sorted":
        vals = jax.ops.segment_sum(
            v[routing.perm_dev],
            routing.seg_ids_dev,
            num_segments=routing.nnz,
            indices_are_sorted=True,
        )
    elif mode == "table":
        vals = table_segment_sum(v, routing.table_dev)
    else:  # direct scatter-add (one XLA scatter)
        vals = jax.ops.segment_sum(
            v, routing.seg_ids_unsorted_dev, num_segments=routing.nnz
        )
    return vals


def reduce_vector(f_local: jnp.ndarray, routing: VectorRouting, mode: str = "sorted"):
    """``S_vec · vec(F_local)`` — reduce to touched dofs, then spread them to
    all dofs: by one scatter, or in ``"table"`` mode by a gather."""
    v = f_local.reshape(-1)
    if mode == "table":
        packed = table_segment_sum(v, routing.table_dev)
        return jnp.take(packed, routing.spread_dev, axis=-1, mode="fill",
                        fill_value=0)
    if mode == "sorted":
        packed = jax.ops.segment_sum(
            v[routing.perm_dev],
            routing.seg_ids_dev,
            num_segments=routing.touched.shape[0],
            indices_are_sorted=True,
        )
    else:
        packed = jax.ops.segment_sum(
            v, routing.seg_ids_unsorted_dev,
            num_segments=routing.touched.shape[0],
        )
    out = jnp.zeros((routing.num_dofs,), dtype=v.dtype)
    return out.at[routing.touched_dev].set(packed)


def default_reduce_mode() -> str:
    """``"table"`` where :func:`~repro.core.routing.scatter_free` (a TPU),
    ``"direct"`` elsewhere (one scatter-add: the fastest Reduce on the
    CPU)."""
    return "table" if scatter_free() else "direct"


# ---------------------------------------------------------------------------
# The assembly plan: static tables as identity-hashed aux, coords as the leaf
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class PlanStatic:
    """Compile-time constants of one assembly signature.

    ``eq=False`` keeps identity hashing, so a ``PlanStatic`` is a valid jit
    static argument and pytree aux datum: two plans compare equal exactly
    when they share tables, which is what executable reuse needs.
    """

    w: jnp.ndarray                       # (Q,) quadrature weights
    phi: jnp.ndarray                     # (Q, k) field basis values
    gradhat: jnp.ndarray                 # (Q, k, d) reference gradients
    geo_phi: jnp.ndarray                 # (Q, nv_geo) geometry basis
    geo_grad: jnp.ndarray                # (Q, nv_geo, d) geometry gradients
    scalar_cell_dofs: jnp.ndarray | None  # (E, k_scalar) for nodal coeffs
    mat_routing: MatrixRouting
    vec_routing: VectorRouting
    num_dofs: int
    value_size: int
    reduce_mode: str = "direct"
    cell_dofs: jnp.ndarray | None = None  # (E, k) full DoF map (matrix-free gather)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)
class AssemblyPlan:
    """One (mesh topology × element × quadrature) assembly signature as a
    value: ``coords`` is the single traced pytree leaf (differentiable —
    shape optimization, batched geometries), everything else is aux data.

    ``eq=False``: plans compare/hash by identity — the generated field-wise
    ``__eq__``/``__hash__`` would choke on the traced coords array."""

    coords: jnp.ndarray                  # (E, nv_geo, d) — the ONLY leaf
    static: PlanStatic

    # -- pytree ----------------------------------------------------------
    def tree_flatten(self):
        return (self.coords,), self.static

    @classmethod
    def tree_unflatten(cls, aux, children):
        (coords,) = children
        return cls(coords, aux)

    # -- derived ---------------------------------------------------------
    @property
    def num_dofs(self) -> int:
        return self.static.num_dofs

    @property
    def nnz(self) -> int:
        return self.static.mat_routing.nnz

    @property
    def num_cells(self) -> int:
        return int(self.coords.shape[0])

    def with_coords(self, coords: jnp.ndarray) -> "AssemblyPlan":
        return AssemblyPlan(coords, self.static)

    def context(self, coords: jnp.ndarray | None = None) -> forms.FormContext:
        st = self.static
        return geometry_context(
            self.coords if coords is None else coords,
            st.geo_phi, st.geo_grad, st.phi, st.gradhat, st.w,
            scalar_cell_dofs=st.scalar_cell_dofs,
        )

    def csr(self, vals: jnp.ndarray) -> CSR:
        r = self.static.mat_routing
        telemetry.gauge_set(
            "csr_bytes",
            int(r.nnz) * vals.dtype.itemsize + r.indptr.nbytes + r.indices.nbytes,
        )
        return CSR(
            vals=vals,
            indptr=r.indptr,
            indices=r.indices,
            row_of_nnz=r.row_of_nnz,
            shape=(r.num_dofs, r.num_dofs),
            diag_pos=r.diag_pos,
        )

    def batched_csr(self, vals: jnp.ndarray) -> BatchedCSR:
        r = self.static.mat_routing
        return BatchedCSR(
            vals=vals,
            indptr=r.indptr,
            indices=r.indices,
            row_of_nnz=r.row_of_nnz,
            shape=(r.num_dofs, r.num_dofs),
            diag_pos=r.diag_pos,
        )


def build_plan(space: FunctionSpace, quad_order: int | None = None,
               reduce_mode: str | None = None) -> AssemblyPlan:
    """Precompute one :class:`AssemblyPlan` for a function space.

    ``reduce_mode``: 'direct' lowers to one XLA scatter-add (2.5× faster on
    CPU, still deterministic — no atomics in XLA); 'sorted' is the gather +
    sorted-segment-sum path; 'table' gathers each output slot's
    contributions through a static padded table (no scatter-add, which
    XLA:TPU can only emulate serially in float64).  All are
    bit-reproducible; ``None`` takes :func:`default_reduce_mode`.
    """
    if reduce_mode is None:
        reduce_mode = default_reduce_mode()
    mesh, element = space.mesh, space.element
    pts, w = element.default_rule(quad_order)
    geo_name = {
        "tri": "P1_tri", "tet": "P1_tet", "quad": "Q1_quad", "hex": "Q1_hex",
    }[mesh.cell_type]
    geo = get_element(geo_name)

    if space.value_size == 1:
        scalar_cell_dofs = jnp.asarray(space.cell_dofs)
    else:
        scalar_cell_dofs = jnp.asarray(
            space.cell_dofs[:, :: space.value_size] // space.value_size
        )

    static = PlanStatic(
        w=jnp.asarray(w),
        phi=jnp.asarray(element.tabulate(pts)),
        gradhat=jnp.asarray(element.tabulate_grad(pts)),
        geo_phi=jnp.asarray(geo.tabulate(pts)),
        geo_grad=jnp.asarray(geo.tabulate_grad(pts)),
        scalar_cell_dofs=scalar_cell_dofs,
        mat_routing=build_matrix_routing(space.cell_dofs, None, space.num_dofs),
        vec_routing=build_vector_routing(space.cell_dofs, space.num_dofs),
        num_dofs=space.num_dofs,
        value_size=space.value_size,
        reduce_mode=reduce_mode,
        cell_dofs=jnp.asarray(space.cell_dofs),
    )
    telemetry.gauge_set("plan_bytes", _plan_nbytes(static))
    return AssemblyPlan(jnp.asarray(mesh.points[mesh.cells]), static)


def _plan_nbytes(static: PlanStatic) -> int:
    """Host+device footprint of a plan's static tables and routing arrays."""

    def nb(x) -> int:
        if dataclasses.is_dataclass(x) and not isinstance(x, type):
            return sum(nb(getattr(x, f.name)) for f in dataclasses.fields(x))
        return int(getattr(x, "nbytes", 0) or 0)

    return nb(static)


# ---------------------------------------------------------------------------
# The functional core: pure form evaluation (closes over nothing)
# ---------------------------------------------------------------------------

_N_CORE_TRACES = [0]


def n_core_traces() -> int:
    """Global trace counter of the functional core — bumped once per jaxpr
    trace of :func:`_eval_form`.  Repeated (batched) assembly with new
    coefficient *values* must not grow it (zero-retrace property)."""
    return _N_CORE_TRACES[0]


def _map_stage(static: PlanStatic, ctx: forms.FormContext, spec, leaves):
    """One fused Map: evaluate every term of ``spec`` against the shared
    volume context (facet terms against their domain's facet context) and
    accumulate local matrices/vectors term-wise."""
    vs = static.value_size
    leaf = iter(leaves)
    facet_ctxs: dict = {}
    local_sum = None            # fused volume Map accumulator
    facet_sums: dict = {}       # domain -> facet Map accumulator
    for kind, domain, desc in spec:
        vals = [next(leaf) if d == weakform.TRACED else d[1] for d in desc]
        *coeffs, scale = vals
        if domain is None:
            tctx = ctx
        else:
            if domain not in facet_ctxs:
                facet_ctxs[domain] = domain.context()
            tctx = facet_ctxs[domain]
        kern = weakform.KERNELS[kind].fn
        local = kern(tctx, vs, *coeffs) * jnp.asarray(scale)
        if domain is None:
            if local_sum is not None and local_sum.shape != local.shape:
                raise ValueError(
                    f"term '{kind}' local shape {local.shape} does not "
                    f"match earlier terms {local_sum.shape} — scalar "
                    "and vector-valued kernels cannot be fused"
                )
            local_sum = local if local_sum is None else local_sum + local
        else:
            prev = facet_sums.get(domain)
            facet_sums[domain] = local if prev is None else prev + local
    return local_sum, facet_sums


def _zero_fallback_dtype(coords, facet_sums):
    """dtype of the all-facet (no volume term) zero fallback: derived from
    the traced inputs, NOT the jax default — a float32 plan must not
    silently upcast facet-only forms to float64."""
    dts = [loc.dtype for loc in facet_sums.values()]
    return jnp.result_type(*dts) if dts else coords.dtype


def _eval_form(static: PlanStatic, coords, spec, leaves, arity: str):
    """Pure fused Map + Reduce over one lowered form.  All closure-free:
    ``static`` carries the tables, ``coords``/``leaves`` are the traced
    inputs, ``spec`` is the static signature."""
    _N_CORE_TRACES[0] += 1
    telemetry.count_trace("assembly", static, spec)
    with annotate("tg.map"):
        ctx = geometry_context(
            coords, static.geo_phi, static.geo_grad, static.phi,
            static.gradhat, static.w,
            scalar_cell_dofs=static.scalar_cell_dofs,
        )
        local_sum, facet_sums = _map_stage(static, ctx, spec, leaves)
    mode = static.reduce_mode

    if arity == weakform.MATRIX:
        with annotate("tg.reduce"):
            out = (
                reduce_matrix(local_sum, static.mat_routing, mode)
                if local_sum is not None
                else jnp.zeros(
                    (static.mat_routing.nnz,),
                    dtype=_zero_fallback_dtype(coords, facet_sums),
                )
            )
        with annotate("tg.facet_inject"):
            for domain, loc in facet_sums.items():
                fvals = reduce_matrix(loc, domain.mat_routing, mode)
                # numpy precompute on static data, cached per (domain, routing)
                inj = jnp.asarray(domain.injection_into(static.mat_routing))
                out = out.at[inj].add(fvals.astype(out.dtype))
        return out

    with annotate("tg.reduce"):
        out = (
            reduce_vector(local_sum, static.vec_routing, mode)
            if local_sum is not None
            else jnp.zeros(
                (static.num_dofs,),
                dtype=_zero_fallback_dtype(coords, facet_sums),
            )
        )
    with annotate("tg.facet_inject"):
        for domain, loc in facet_sums.items():
            out = out + reduce_vector(loc, domain.vec_routing, mode)
    return out


def _check_facet_coords(spec, coords):
    if coords is not None and any(domain is not None for _, domain, _ in spec):
        # facet geometry comes from the FacetAssembler's construction-time
        # coords; silently mixing it with overridden volume coords would
        # give inconsistent values and zero boundary coordinate gradients
        raise NotImplementedError(
            "assemble(form, coords=...) does not support facet terms: "
            "boundary geometry is fixed at FacetAssembler construction"
        )


# -- single-instance entry points (jit-cached per (plan, signature)) ---------
#
# One jitted wrapper per static key, held in a module-level FIFO-bounded
# dict shared by the facade and the pure entry points.  The bound matters
# for identity-keyed callable coefficients: per-call lambdas mint a fresh
# signature each call, and evicting the wrapper drops its compiled
# executable — an unbounded jax.jit static-arg cache would retain them all
# (hot loops should still reuse stable function objects).

_FORM_FNS: dict = {}
_FORM_FNS_LIMIT = 256


def _cached_form_fn(key, build):
    fn = _FORM_FNS.get(key)
    telemetry.count_cache("assembly_form_fn", hit=fn is not None)
    if fn is None:
        while len(_FORM_FNS) >= _FORM_FNS_LIMIT:
            _FORM_FNS.pop(next(iter(_FORM_FNS)))
        fn = jax.jit(build())
        _FORM_FNS[key] = fn
    return fn


def _assemble_flat(coords, leaves, *, static, spec, arity):
    fn = _cached_form_fn(
        ("single", static, spec, arity),
        lambda: lambda c, lv: _eval_form(static, c, spec, lv, arity),
    )
    out = fn(coords, leaves)
    if telemetry.is_enabled():
        is_mat = arity == weakform.MATRIX
        telemetry.record_assembly(
            "assemble" if is_mat else "assemble_rhs",
            num_dofs=static.num_dofs,
            nnz=static.mat_routing.nnz if is_mat else None,
            num_cells=int(coords.shape[0]),
            form="+".join(kind for kind, _, _ in spec),
        )
    return out


def assemble(plan: AssemblyPlan, form, coords=None) -> CSR:
    """Assemble a bilinear :class:`~repro.core.weakform.WeakForm` into a CSR
    on the plan's volume pattern — the pure-function twin of
    :meth:`GalerkinAssembler.assemble`.

    One fused Map over a shared geometry context (built from ``coords``
    inside the jit boundary), one Reduce; facet terms (``robin(alpha,
    on=facets)``) reduce through their facet routing and are injected into
    the volume CSR pattern.  Coefficients and scale factors are traced, so
    re-assembly with new *values* reuses the compiled executable.
    """
    spec, leaves = weakform.lower(form, weakform.MATRIX)
    _check_facet_coords(spec, coords)
    c = plan.coords if coords is None else coords
    vals = _assemble_flat(c, leaves, static=plan.static, spec=spec,
                          arity=weakform.MATRIX)
    return plan.csr(vals)


def assemble_rhs(plan: AssemblyPlan, form, coords=None) -> jnp.ndarray:
    """Assemble a linear form into a global ``(num_dofs,)`` vector — same
    fused pipeline as :func:`assemble`."""
    spec, leaves = weakform.lower(form, weakform.VECTOR)
    _check_facet_coords(spec, coords)
    c = plan.coords if coords is None else coords
    return _assemble_flat(c, leaves, static=plan.static, spec=spec,
                          arity=weakform.VECTOR)


# -- vmap-batched multi-instance assembly ------------------------------------

def _assemble_batched_flat(coords, leaves, *, static, spec, arity, axes):
    coords_ax, leaf_axes = axes

    def build():
        return lambda c, lv: jax.vmap(
            lambda ci, lvi: _eval_form(static, ci, spec, lvi, arity),
            in_axes=(coords_ax, leaf_axes),
        )(c, lv)

    fn = _cached_form_fn(("batched", static, spec, arity, axes), build)
    out = fn(coords, leaves)
    if telemetry.is_enabled():
        is_mat = arity == weakform.MATRIX
        telemetry.record_assembly(
            "assemble_batched" if is_mat else "assemble_rhs_batched",
            num_dofs=static.num_dofs,
            nnz=static.mat_routing.nnz if is_mat else None,
            form="+".join(kind for kind, _, _ in spec),
        )
    return out


def _lower_batched(plan, form, arity, coords_batch, leaves_batch):
    spec, leaves0 = weakform.lower(form, arity)
    if any(domain is not None for _, domain, _ in spec):
        raise NotImplementedError(
            "batched assembly supports volume terms only: facet geometry is "
            "fixed at FacetAssembler construction and cannot vary per instance"
        )
    if leaves_batch is None:
        leaves_batch = (None,) * len(leaves0)
    elif not isinstance(leaves_batch, (tuple, list)):
        # single-array convenience: batch the first traced slot
        leaves_batch = (leaves_batch,) + (None,) * (len(leaves0) - 1)
    if len(leaves_batch) != len(leaves0):
        raise ValueError(
            f"leaves_batch has {len(leaves_batch)} slots but the form lowers "
            f"to {len(leaves0)} traced leaves (per term: coefficients, then "
            "the scale factor) — pass None for slots shared across the batch"
        )
    merged = tuple(
        b if b is not None else l0 for b, l0 in zip(leaves_batch, leaves0)
    )
    leaf_axes = tuple(0 if b is not None else None for b in leaves_batch)
    coords_ax = 0 if coords_batch is not None else None
    sizes = {int(jnp.shape(b)[0]) for b in leaves_batch if b is not None}
    if coords_batch is not None:
        sizes.add(int(jnp.shape(coords_batch)[0]))
    if not sizes:
        raise ValueError(
            "nothing is batched: pass coords_batch and/or batched leaves"
        )
    if len(sizes) > 1:
        raise ValueError(f"inconsistent batch sizes {sorted(sizes)}")
    coords = plan.coords if coords_batch is None else coords_batch
    return spec, merged, coords, (coords_ax, leaf_axes)


def assemble_batched(plan: AssemblyPlan, form, coords_batch=None,
                     leaves_batch=None) -> BatchedCSR:
    """Assemble B problem instances in ONE fused Map over ``(B, E, ...)`` and
    one Reduce per instance via ``vmap`` — a single XLA executable for the
    whole batch, zero retraces across batch *values*.

    ``form`` is the template form (its own coefficient values fill any slot
    not batched).  ``coords_batch: (B, E, nv, d)`` batches the geometry;
    ``leaves_batch`` batches coefficients/scales — a tuple aligned with the
    form's traced leaves in slot order (per term: coefficients, then the
    scale factor), each entry either ``None`` (shared) or an array with a
    leading batch axis.  A bare array batches the first traced slot::

        kb = assemble_batched(plan, wf.diffusion(rho_b[0]),
                              leaves_batch=(rho_b, None))   # (B, E) coeffs

    Returns a :class:`~repro.core.sparse.BatchedCSR` — shared static pattern,
    ``(B, nnz)`` values — composing with ``vmap``-ed
    :func:`~repro.core.solvers.sparse_solve`
    (:func:`~repro.core.solvers.sparse_solve_batched`).
    """
    spec, merged, coords, axes = _lower_batched(
        plan, form, weakform.MATRIX, coords_batch, leaves_batch
    )
    vals = _assemble_batched_flat(coords, merged, static=plan.static,
                                  spec=spec, arity=weakform.MATRIX, axes=axes)
    return plan.batched_csr(vals)


def assemble_rhs_batched(plan: AssemblyPlan, form, coords_batch=None,
                         leaves_batch=None) -> jnp.ndarray:
    """Batched linear-form assembly → ``(B, num_dofs)`` (see
    :func:`assemble_batched` for the batching conventions)."""
    spec, merged, coords, axes = _lower_batched(
        plan, form, weakform.VECTOR, coords_batch, leaves_batch
    )
    return _assemble_batched_flat(coords, merged, static=plan.static,
                                  spec=spec, arity=weakform.VECTOR, axes=axes)


# -- shard_map element-parallel assembly -------------------------------------
#
# The named FEM mesh axis is registered in repro.sharding.partitioning
# (FEM_MESH_AXIS / fem_mesh); it is resolved lazily here so importing the
# core never drags in the LM sharding stack.

def _fem_axis_name() -> str:
    from ..sharding.partitioning import FEM_MESH_AXIS

    return FEM_MESH_AXIS


def _default_fem_mesh(axis_name: str):
    from ..sharding.partitioning import fem_mesh

    return fem_mesh(axis_name=axis_name)


def _assemble_sharded_flat(coords, leaves, *, static, spec, arity, mesh, axis_name):
    fn = _cached_form_fn(
        ("sharded", static, spec, arity, mesh, axis_name),
        lambda: partial(_sharded_impl, static=static, spec=spec, arity=arity,
                        mesh=mesh, axis_name=axis_name),
    )
    out = fn(coords, leaves)
    if telemetry.is_enabled():
        is_mat = arity == weakform.MATRIX
        telemetry.record_assembly(
            "assemble_sharded" if is_mat else "assemble_rhs_sharded",
            num_dofs=static.num_dofs,
            nnz=static.mat_routing.nnz if is_mat else None,
            num_cells=int(coords.shape[0]),
            form="+".join(kind for kind, _, _ in spec),
        )
    return out


def _sharded_impl(coords, leaves, *, static, spec, arity, mesh, axis_name):
    """Partition the element axis of the Map stage over ``mesh[axis_name]``;
    each device reduces its element block to *partial* global contributions
    (full nnz / touched-dof length) and one all-reduce completes the Reduce.

    Elements are zero-cost padded to a multiple of the device count: padded
    rows replicate the last element's geometry but carry out-of-range
    segment ids, which ``segment_sum`` drops.

    The per-shard reduce always uses the direct (unsorted scatter-add)
    segment ids regardless of ``plan.static.reduce_mode``: the sorted
    layout's global permutation interleaves elements across shards and does
    not decompose into per-shard sorted runs.  Both modes are deterministic
    and the psum of partials is bit-stable, so results still match the
    single-device path.
    """
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    _N_CORE_TRACES[0] += 1
    telemetry.count_trace("assembly", static, spec, backend="sharded")
    ndev = mesh.shape[axis_name]
    e = coords.shape[0]
    pad = (-e) % ndev
    routing = static.mat_routing if arity == weakform.MATRIX else static.vec_routing
    n_seg = routing.nnz if arity == weakform.MATRIX else routing.touched.shape[0]
    slots = routing.seg_ids_unsorted.shape[0] // e

    # static numpy precompute (host constants baked per trace)
    seg = routing.seg_ids_unsorted.reshape(e, slots)
    seg = np.concatenate([seg, np.full((pad, slots), n_seg, dtype=seg.dtype)])

    def pad_rows(x):
        return jnp.concatenate(
            [x, jnp.broadcast_to(x[-1:], (pad,) + x.shape[1:])]
        ) if pad else x

    coords_p = pad_rows(coords)
    scd = static.scalar_cell_dofs
    scd_p = pad_rows(scd) if scd is not None else None

    # shard leaves whose leading axis is the element axis; replicate the
    # rest (scalars, nodal fields, constant vectors) — mirrors the shape
    # resolution order of forms.eval_coefficient
    leaf_flags = tuple(
        jnp.ndim(lv) >= 1 and jnp.shape(lv)[0] == e for lv in leaves
    )
    leaves_p = tuple(
        pad_rows(jnp.asarray(lv)) if flag else jnp.asarray(lv)
        for lv, flag in zip(leaves, leaf_flags)
    )
    leaf_specs = tuple(P(axis_name) if flag else P() for flag in leaf_flags)
    scd_args = (scd_p,) if scd_p is not None else ()
    scd_specs = ((P(axis_name),) if scd_p is not None else ())

    def body(coords_s, seg_s, *rest):
        scd_s = rest[0] if scd_p is not None else None
        leaf_s = rest[1:] if scd_p is not None else rest
        ctx = geometry_context(
            coords_s, static.geo_phi, static.geo_grad, static.phi,
            static.gradhat, static.w, scalar_cell_dofs=scd_s,
        )
        local_sum, facet_sums = _map_stage(static, ctx, spec, leaf_s)
        assert not facet_sums, "sharded assembly is volume-only (checked above)"
        part = jax.ops.segment_sum(
            local_sum.reshape(-1), seg_s.reshape(-1), num_segments=n_seg
        )
        return jax.lax.psum(part, axis_name)

    sharded = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis_name), P(axis_name)) + scd_specs + leaf_specs,
        out_specs=P(),
        check_rep=False,
    )
    packed = sharded(coords_p, jnp.asarray(seg), *scd_args, *leaves_p)
    if arity == weakform.MATRIX:
        return packed
    out = jnp.zeros((routing.num_dofs,), dtype=packed.dtype)
    return out.at[routing.touched_dev].set(packed)


def _assemble_sharded_vals(plan, form, arity, mesh, axis_name, coords):
    spec, leaves = weakform.lower(form, arity)
    if any(domain is not None for _, domain, _ in spec):
        raise NotImplementedError(
            "sharded assembly supports volume terms only — assemble facet "
            "terms separately and inject (FacetAssembler.injection_into)"
        )
    if axis_name is None:
        axis_name = _fem_axis_name()
    if mesh is None:
        mesh = _default_fem_mesh(axis_name)
    c = plan.coords if coords is None else coords
    return _assemble_sharded_flat(c, leaves, static=plan.static, spec=spec,
                                  arity=arity, mesh=mesh, axis_name=axis_name)


def assemble_sharded(plan: AssemblyPlan, form, mesh=None,
                     axis_name: str | None = None, coords=None) -> CSR:
    """Opt-in multi-device assembly: the element axis of the Map stage is
    ``shard_map``-partitioned over ``mesh[axis_name]`` (default: the FEM
    mesh from :func:`repro.sharding.partitioning.fem_mesh` over all local
    devices); the segment-sum Reduce is completed by a single all-reduce
    over partial nnz contributions.  Matches single-device assembly to
    machine precision."""
    vals = _assemble_sharded_vals(plan, form, weakform.MATRIX, mesh,
                                  axis_name, coords)
    return plan.csr(vals)


def assemble_rhs_sharded(plan: AssemblyPlan, form, mesh=None,
                         axis_name: str | None = None,
                         coords=None) -> jnp.ndarray:
    """Sharded linear-form assembly (see :func:`assemble_sharded`)."""
    return _assemble_sharded_vals(plan, form, weakform.VECTOR, mesh,
                                  axis_name, coords)


def clear_assembly_caches():
    """Drop the functional core's compiled-executable cache.

    The pure entry points key executables on identity-hashed ``PlanStatic``
    aux + form signature in a module-level FIFO-bounded cache (shared with
    the :class:`GalerkinAssembler` facade), so each cached entry retains its
    plan's tables and executable.  The bound caps growth automatically;
    sweeps that mint many short-lived plans (mesh-refinement studies) can
    call this to release everything at once.  Also drops the sparse
    pattern-array device mirrors, which pin host+device copies of each
    pattern that flowed through matvec/solve.
    """
    from .sparse import clear_device_mirrors

    _FORM_FNS.clear()
    clear_device_mirrors()


# ---------------------------------------------------------------------------
# The assembler facade (cache-owning; every pre-plan call site keeps working)
# ---------------------------------------------------------------------------

class GalerkinAssembler:
    """Thin cache-owning facade over an :class:`AssemblyPlan`.

    One instance per (mesh topology × element × quadrature) signature.  All
    tables live in ``self.plan`` — the class adds only the per-signature jit
    cache (`n_traces` retrace accounting) and the historical method surface.
    Re-instantiating for a same-signature mesh reuses XLA executables via
    jit's cache (shape-bucketed compilation, DESIGN §2).
    """

    def __init__(self, space: FunctionSpace, quad_order: int | None = None,
                 reduce_mode: str | None = None):
        self.space = space
        self.mesh = space.mesh
        self.element = space.element
        self.plan = build_plan(space, quad_order, reduce_mode)
        st = self.plan.static
        self.reduce_mode = st.reduce_mode
        # compatibility aliases onto the plan's static tables
        self.w, self.phi, self.gradhat = st.w, st.phi, st.gradhat
        self.geo_phi, self.geo_grad = st.geo_phi, st.geo_grad
        self.coords = self.plan.coords
        self._scalar_cell_dofs = st.scalar_cell_dofs
        self.mat_routing = st.mat_routing
        self.vec_routing = st.vec_routing

        # One compiled executable per (plan, form signature), owned by the
        # module-level jit cache and SHARED with the pure assemble()/
        # assemble_rhs() entry points.  n_traces counts retraces — repeated
        # assembly with new coefficient *values* must not grow it.  Callable
        # coefficients are part of the signature (identity-keyed): per-call
        # lambdas each compile fresh, so hot loops should reuse stable
        # function objects (or pre-evaluate callables to quadrature arrays,
        # as MixedBCPoisson does); clear_assembly_caches() releases the
        # accumulated executables.
        self.n_traces = 0

    # -- context -------------------------------------------------------------
    def context(self, coords: jnp.ndarray | None = None) -> forms.FormContext:
        return self.plan.context(coords)

    def csr(self, vals: jnp.ndarray) -> CSR:
        return self.plan.csr(vals)

    # -- form API: one fused Map, one Reduce, jit-cached per signature --------
    def assemble(self, form, coords=None) -> CSR:
        """Assemble a bilinear :class:`~repro.core.weakform.WeakForm` into a
        CSR on the volume pattern.

        All volume terms are evaluated in **one fused Map** against a shared
        geometry context (built from ``coords`` inside the jit boundary),
        summed element-wise, and reduced **once**; facet terms (e.g.
        ``robin(alpha, on=facets)``) reduce through their facet routing and
        are injected into the volume CSR pattern.  Coefficients and scale
        factors are traced — a θ-step ``mass(c) + dt*diffusion(kappa)`` or a
        SIMP-interpolated ``elasticity(lam, mu, scale=rho**p)`` compiles one
        XLA executable reused across coefficient values.
        """
        return self.csr(self._assemble_vals(form, weakform.MATRIX, coords))

    def assemble_rhs(self, form, coords=None) -> jnp.ndarray:
        """Assemble a linear form (``source`` / ``neumann`` / ``reaction``
        terms) into a global ``(num_dofs,)`` vector — same fused pipeline."""
        return self._assemble_vals(form, weakform.VECTOR, coords)

    def assemble_batched(self, form, coords_batch=None,
                         leaves_batch=None) -> BatchedCSR:
        """Batched multi-instance assembly — see :func:`assemble_batched`."""
        return assemble_batched(self.plan, form, coords_batch, leaves_batch)

    def assemble_rhs_batched(self, form, coords_batch=None,
                             leaves_batch=None) -> jnp.ndarray:
        """Batched linear forms — see :func:`assemble_rhs_batched`."""
        return assemble_rhs_batched(self.plan, form, coords_batch, leaves_batch)

    def assemble_sharded(self, form, mesh=None,
                         axis_name: str | None = None) -> CSR:
        """Element-parallel multi-device assembly — see
        :func:`assemble_sharded`."""
        return assemble_sharded(self.plan, form, mesh, axis_name)

    def _assemble_vals(self, form, arity: str, coords=None):
        """Delegate to the module-level jitted core so the facade and the
        pure ``assemble(plan, form)`` entry point share ONE executable per
        (plan, signature); ``n_traces`` is derived from the core's trace
        counter (a delta of zero means the executable was reused)."""
        spec, leaves = weakform.lower(form, arity)
        _check_facet_coords(spec, coords)
        before = n_core_traces()
        out = _assemble_flat(
            self.plan.coords if coords is None else coords, leaves,
            static=self.plan.static, spec=spec, arity=arity,
        )
        self.n_traces += n_core_traces() - before
        return out

    # -- deprecated shims over the form API -----------------------------------
    @staticmethod
    def _warn_deprecated(name: str, replacement: str):
        warnings.warn(
            f"GalerkinAssembler.{name} is deprecated; use {replacement}",
            DeprecationWarning,
            stacklevel=3,
        )

    def assemble_stiffness(self, rho=None, coords=None) -> CSR:
        """Deprecated: use ``assemble(weakform.diffusion(rho))``."""
        self._warn_deprecated("assemble_stiffness", "assemble(weakform.diffusion(rho))")
        return self.assemble(weakform.diffusion(rho), coords)

    def assemble_mass(self, c=None, coords=None) -> CSR:
        """Deprecated: use ``assemble(weakform.mass(c))``."""
        self._warn_deprecated("assemble_mass", "assemble(weakform.mass(c))")
        return self.assemble(weakform.mass(c), coords)

    def assemble_elasticity(self, lam: float, mu: float, scale=None, coords=None) -> CSR:
        """Deprecated: use ``assemble(weakform.elasticity(lam, mu, scale))``."""
        self._warn_deprecated(
            "assemble_elasticity", "assemble(weakform.elasticity(lam, mu, scale))"
        )
        return self.assemble(weakform.elasticity(lam, mu, scale), coords)

    def assemble_load(self, f=None, coords=None) -> jnp.ndarray:
        """Deprecated: use ``assemble_rhs(weakform.source(f))``."""
        self._warn_deprecated("assemble_load", "assemble_rhs(weakform.source(f))")
        return self.assemble_rhs(weakform.source(f), coords)

    def assemble_reaction_load(self, u_nodal, fn) -> jnp.ndarray:
        """Deprecated: use ``assemble_rhs(weakform.reaction(u_nodal, fn))``."""
        self._warn_deprecated(
            "assemble_reaction_load", "assemble_rhs(weakform.reaction(u_nodal, fn))"
        )
        return self.assemble_rhs(weakform.reaction(u_nodal, fn))

    # -- baselines (paper Fig. 1 "white box") ----------------------------------
    def assemble_stiffness_scatter(self, rho=None) -> jnp.ndarray:
        """Dense scatter-add baseline: K.at[rows, cols].add(k_local)."""
        ctx = self.context(None)
        k_local = forms.diffusion(ctx, rho)
        n = self.space.num_dofs
        cd = jnp.asarray(self.space.cell_dofs)
        rows = jnp.broadcast_to(cd[:, :, None], k_local.shape).reshape(-1)
        cols = jnp.broadcast_to(cd[:, None, :], k_local.shape).reshape(-1)
        return jnp.zeros((n, n)).at[rows, cols].add(k_local.reshape(-1))

    def assemble_stiffness_loop(self, rho=None) -> np.ndarray:
        """Python per-element loop (the classical Alg.; O(E) graph/time).
        numpy, small meshes only — exists to quantify the paper's claim."""
        el, mesh, sp = self.element, self.mesh, self.space
        pts, w = el.default_rule(None)
        gradhat = el.tabulate_grad(pts)
        k = np.zeros((sp.num_dofs, sp.num_dofs))
        pts_np = np.asarray(self.coords)
        geo_grad = np.asarray(self.geo_grad)
        for e in range(mesh.num_cells):
            x = pts_np[e]
            j = np.einsum("ai,qaj->qij", x, geo_grad)
            det = np.abs(np.linalg.det(j))
            jinv = np.linalg.inv(j)
            g = np.einsum("qji,qaj->qai", jinv, gradhat)
            ke = np.einsum("q,q,qai,qbi->ab", w, det, g, g)
            dofs = sp.cell_dofs[e]
            for a in range(len(dofs)):
                for b in range(len(dofs)):
                    k[dofs[a], dofs[b]] += ke[a, b]
        return k

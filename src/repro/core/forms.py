"""Weak-form library consumed by the Batch-Map stage.

Each form is a pure function ``form(ctx, **coeffs) -> K_local | F_local``
implemented as dense tensor contractions over a :class:`FormContext` — the
batched geometry tensors of Alg. 1 (Eq. 7 / Eq. A.12–A.14 of the paper).
Everything is jax-traceable; coefficients may be traced arrays (TensorPILS /
TensorOpt differentiate through them).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

__all__ = [
    "FormContext",
    "eval_coefficient",
    "eval_tensor_coefficient",
    "diffusion",
    "anisotropic_diffusion",
    "advection",
    "mass",
    "elasticity",
    "load",
    "vector_load",
    "nonlinear_reaction",
]


@dataclasses.dataclass(frozen=True)
class FormContext:
    """Batched geometry at quadrature points (the paper's 𝒢, 𝒥, 𝒳̂, Ŵ).

    Frozen and registered as a jax pytree (all fields are leaves), so a
    context crosses jit/vmap boundaries cleanly — batched transient
    rollouts can close over one context instead of rebuilding it per trace.
    """

    w: jnp.ndarray          # (Q,) reference weights
    phi: jnp.ndarray        # (Q, k) basis values
    detj: jnp.ndarray       # (E, Q) |det J| (surface measure for facets)
    grad: jnp.ndarray | None  # (E, Q, k, d) physical basis gradients 𝒢
    xq: jnp.ndarray         # (E, Q, d) physical quadrature points
    scalar_cell_dofs: jnp.ndarray | None = None  # (E, k_scalar) for nodal coeffs

    @property
    def wdet(self) -> jnp.ndarray:
        """(E, Q) combined quadrature × measure weights ŵ_q |det J|."""
        return self.w[None, :] * self.detj


jax.tree_util.register_dataclass(
    FormContext,
    data_fields=["w", "phi", "detj", "grad", "xq", "scalar_cell_dofs"],
    meta_fields=[],
)


def eval_coefficient(coef, ctx: FormContext, vector_size: int | None = None):
    """Evaluate a coefficient at quadrature points → (E, Q) or (E, Q, c).

    Accepted encodings:
      * ``None``                → 1.0
      * python/0-d scalar      → constant
      * callable               → ``coef(xq)`` with ``xq: (E, Q, d)``
      * array ``(E,)``         → element-wise constant (SIMP densities)
      * array ``(E, Q)``       → per-quadrature values
      * array ``(N_scalar,)``  → nodal field, interpolated with the basis
      * array ``(c,)`` with ``vector_size == c`` → constant vector
    """
    e, q = ctx.detj.shape
    if coef is None:
        # unit coefficient in the context's dtype (a float32 geometry must
        # not upcast the whole contraction to the x64 default)
        return jnp.ones((e, q), dtype=ctx.detj.dtype)
    if callable(coef):
        out = coef(ctx.xq)
        return jnp.asarray(out)
    coef = jnp.asarray(coef)
    if coef.ndim == 0:
        return jnp.broadcast_to(coef, (e, q))
    if vector_size is not None and coef.ndim == 1 and coef.shape[0] == vector_size:
        return jnp.broadcast_to(coef[None, None, :], (e, q, vector_size))
    if coef.ndim == 1 and coef.shape[0] == e:
        return jnp.broadcast_to(coef[:, None], (e, q))
    if coef.ndim == 1:
        # nodal field: interpolate u_q = Σ_a φ_a(x̂_q) u_{g_e(a)}
        assert ctx.scalar_cell_dofs is not None, "nodal coeff needs cell dofs"
        nodal = coef[ctx.scalar_cell_dofs]                # (E, k)
        return jnp.einsum("qa,ea->eq", ctx.phi, nodal)
    if coef.shape[:2] == (e, q):
        return coef
    raise ValueError(f"un-interpretable coefficient shape {coef.shape}")


def eval_tensor_coefficient(coef, ctx: FormContext, d: int):
    """Evaluate a (d, d) tensor coefficient at quadrature points → (E, Q, d, d).

    Accepted encodings: ``None`` → identity, ``(d, d)`` constant,
    ``(E, d, d)`` per-element, ``(E, Q, d, d)`` per-quadrature, or a
    callable of x returning ``(E, Q, d, d)``.
    """
    e, q = ctx.detj.shape
    if coef is None:
        return jnp.broadcast_to(jnp.eye(d), (e, q, d, d))
    if callable(coef):
        coef = coef(ctx.xq)
    coef = jnp.asarray(coef)
    if coef.shape == (d, d):
        return jnp.broadcast_to(coef, (e, q, d, d))
    if coef.shape == (e, d, d):
        return jnp.broadcast_to(coef[:, None], (e, q, d, d))
    if coef.shape == (e, q, d, d):
        return coef
    raise ValueError(f"un-interpretable tensor coefficient shape {coef.shape}")


# ---------------------------------------------------------------------------
# Bilinear forms → (E, k, k)
# ---------------------------------------------------------------------------

def diffusion(ctx: FormContext, rho=None) -> jnp.ndarray:
    """∫ ρ ∇φ_b · ∇φ_a  — Eq. (A.12), the paper's flagship contraction.

    Written as broadcast products and sums, not as one einsum: XLA:TPU
    lowers that float64 batched dot to bf16 pieces laid out element-major,
    each tiny (k, d) block padded to a 4×128 tile — 32 GB of temporaries
    for the 2M triangles of a 1M-DOF mesh.  The elementwise form fuses
    into one loop with no temporaries."""
    rho_q = eval_coefficient(rho, ctx)
    # (K_local)_{eab} = Σ_q ŵ_q|detJ| ρ G_a·G_b
    g = ctx.grad                                                # (E, Q, k, d)
    gg = jnp.sum(g[:, :, :, None, :] * g[:, :, None, :, :], axis=-1)
    return jnp.sum((ctx.wdet * rho_q)[..., None, None] * gg, axis=1)


def anisotropic_diffusion(ctx: FormContext, a=None) -> jnp.ndarray:
    """∫ (A∇u)·∇v with a (d, d) tensor coefficient A (heterogeneous /
    anisotropic media); A = I reduces to :func:`diffusion`."""
    d = ctx.grad.shape[-1]
    a_q = eval_tensor_coefficient(a, ctx, d)
    return jnp.einsum(
        "eq,eqai,eqij,eqbj->eab", ctx.wdet, ctx.grad, a_q, ctx.grad,
        optimize=True,
    )


def advection(ctx: FormContext, beta) -> jnp.ndarray:
    """∫ (β·∇u) v — the (nonsymmetric) advection bilinear form:
    K_ab = Σ_q ŵ|detJ| φ_a (β·𝒢_b)."""
    d = ctx.grad.shape[-1]
    b_q = eval_coefficient(beta, ctx, vector_size=d)      # (E, Q, d)
    return jnp.einsum(
        "eq,qa,eqi,eqbi->eab", ctx.wdet, ctx.phi, b_q, ctx.grad,
        optimize=True,
    )


def mass(ctx: FormContext, c=None) -> jnp.ndarray:
    """∫ c φ_b φ_a  (also the Robin boundary form on facet contexts)."""
    c_q = eval_coefficient(c, ctx)
    return jnp.einsum("eq,eq,qa,qb->eab", ctx.wdet, c_q, ctx.phi, ctx.phi)


def elasticity(ctx: FormContext, lam: float, mu: float, scale=None) -> jnp.ndarray:
    """Isotropic linear elasticity ∫ σ(u):ε(v) with Lamé (λ, μ).

    ``ctx.grad`` is the *scalar* basis gradient (E, Q, nv, d); the returned
    local matrix is over interleaved vector dofs (a·d + i), matching
    FunctionSpace ordering.  ``scale`` is an optional per-element factor —
    the SIMP stiffness interpolation E(ρ) enters here (TensorOpt).

    With ``T_aibj = Σ_q ŵ|detJ| 𝒢_ai 𝒢_bj``, the block of vertices (a, b)
    is ``λ T_aibj + μ (T_ajbi + δ_ij Σ_k T_akbk)``, written as broadcast
    products and sums for the reason :func:`diffusion` gives.
    """
    g = ctx.grad
    e, _, nv, d = g.shape
    w = (ctx.wdet * eval_coefficient(scale, ctx))[..., None, None]
    t = jnp.sum((w * g)[:, :, :, :, None, None] * g[:, :, None, None, :, :],
                axis=1)                                     # (E, a, i, b, j)
    eye = jnp.eye(d, dtype=t.dtype)
    gdotg = jnp.sum(t * eye[:, None, :], axis=(2, 4))        # (E, a, b)
    k_local = lam * t + mu * (jnp.swapaxes(t, 2, 4)
                              + gdotg[:, :, None, :, None] * eye[:, None, :])
    return k_local.reshape(e, nv * d, nv * d)


# ---------------------------------------------------------------------------
# Linear forms → (E, k)
# ---------------------------------------------------------------------------

def load(ctx: FormContext, f=None) -> jnp.ndarray:
    """∫ f φ_a — Eq. (A.11) (also the Neumann boundary load on facets)."""
    f_q = eval_coefficient(f, ctx)
    return jnp.einsum("eq,eq,qa->ea", ctx.wdet, f_q, ctx.phi)


def vector_load(ctx: FormContext, f, d: int) -> jnp.ndarray:
    """∫ f · v for vector-valued v; ``f`` is a constant (d,) vector, a
    callable returning (E, Q, d), or an (E, Q, d) array."""
    f_q = eval_coefficient(f, ctx, vector_size=d)     # (E, Q, d)
    e, nv = ctx.detj.shape[0], ctx.phi.shape[1]
    # products and sums, as in :func:`elasticity`
    out = jnp.sum((ctx.wdet[..., None] * f_q)[:, :, None, :]
                  * ctx.phi[None, :, :, None], axis=1)    # (E, a, i)
    return out.reshape(e, nv * d)


def nonlinear_reaction(ctx: FormContext, u_nodal, fn: Callable) -> jnp.ndarray:
    """Semi-linear load ∫ fn(u) φ_a (Allen–Cahn reaction, Eq. A.1's 𝒩).

    ``u_nodal`` is the current coefficient vector; ``fn`` acts pointwise on
    quadrature values of u.
    """
    u_q = eval_coefficient(u_nodal, ctx)
    return jnp.einsum("eq,eq,qa->ea", ctx.wdet, fn(u_q), ctx.phi)

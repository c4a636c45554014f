"""TensorGalerkin core: Batch-Map + Sparse-Reduce Galerkin assembly.

FEM numerics require double precision (the paper solves to 1e-10 residual);
importing this subpackage enables jax x64 mode.  The LM/dry-run stack is
dtype-explicit throughout and unaffected.
"""

import jax

jax.config.update("jax_enable_x64", True)

from .assembly import (  # noqa: E402,F401
    AssemblyPlan,
    GalerkinAssembler,
    assemble,
    assemble_batched,
    assemble_rhs,
    assemble_rhs_batched,
    assemble_rhs_sharded,
    assemble_sharded,
    build_plan,
    clear_assembly_caches,
    facet_context,
    geometry_context,
)
from .boundary import DirichletCondenser, FacetAssembler  # noqa: E402,F401
from .elements import ReferenceElement, get_element  # noqa: E402,F401
from .mesh import (  # noqa: E402,F401
    FunctionSpace,
    Mesh,
    annulus_sector_tri,
    box_hex,
    disk_tri,
    hollow_cube_tet,
    l_shape_tri,
    rectangle_quad,
    rectangle_tri,
    unit_cube_hex,
    unit_cube_tet,
    unit_square_tri,
)
from .operator import (  # noqa: E402,F401
    LinearOperator,
    MatFreeFamily,
    MatFreeOperator,
    ShardedMatFreeOperator,
    matfree_family,
    matfree_operator,
    n_matfree_traces,
)
from .solvers import (  # noqa: E402,F401
    SolveInfo,
    SolverSpec,
    bicgstab,
    cg,
    jacobi_preconditioner,
    make_preconditioner,
    matfree_solve,
    matfree_solve_batched,
    register_preconditioner,
    resolve_solver_spec,
    sparse_solve,
    sparse_solve_batched,
)
from . import elemalg  # noqa: E402,F401  (registers ebe/chebyshev preconds)
from .elemalg import (  # noqa: E402,F401
    DofSplit,
    ElementFactors,
    block_partition,
    chebyshev_preconditioner,
    condense,
    condensed_solve,
    dof_split,
    ebe_preconditioner,
    factorize,
    vertex_split,
)
from .sparse import (  # noqa: E402,F401
    CSR,
    BatchedCSR,
    cached_diagonal,
)
from . import weakform  # noqa: E402,F401
from .weakform import WeakForm  # noqa: E402,F401

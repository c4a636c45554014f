"""repro.telemetry — convergence diagnostics, metrics registry, tracing.

The PR-6 acceptance criteria:

* ``return_info=True`` on ``sparse_solve`` / ``matfree_solve`` is a
  *non-differentiated auxiliary output*: gradients through the
  info-returning path match the plain path to machine precision;
* transient rollouts stack per-step ``SolveInfo`` out of the scan —
  ``(n_steps,)`` iteration-count trajectories;
* the unified jit-trace counters agree with the legacy
  ``n_core_traces`` / ``n_matfree_traces`` accounting;
* telemetry disabled means zero cost: no extra retraces, nothing recorded,
  tracers never captured;
* silent non-convergence is dead: a ``maxiter`` exit warns (or raises
  under the ``raise`` policy) even with telemetry disabled;
* the JSONL export round-trips through the report CLI.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core import (
    DirichletCondenser,
    FunctionSpace,
    GalerkinAssembler,
    assemble,
    assemble_rhs,
    build_plan,
    matfree_operator,
    matfree_solve,
    n_matfree_traces,
    sparse_solve,
    unit_square_tri,
    weakform as wf,
)
from repro.core.assembly import n_core_traces
from repro.core.mesh import element_for_mesh
from repro.telemetry import (
    ConvergenceWarning,
    NonConvergedError,
    events,
    report,
)
from repro.transient import ThetaIntegrator

RNG = np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts and ends with telemetry off and the registry empty —
    the suite must not leak recording into unrelated tests."""
    telemetry.disable()
    telemetry.reset()
    events.clear_events()
    yield
    telemetry.disable()
    telemetry.reset()
    events.clear_events()


@pytest.fixture(scope="module")
def problem():
    mesh = unit_square_tri(5)
    space = FunctionSpace(mesh, element_for_mesh(mesh))
    plan = build_plan(space)
    bc = DirichletCondenser(plan.static.mat_routing, space.boundary_dofs())
    f = bc.project_residual(assemble_rhs(plan, wf.source(1.0)))
    rho0 = jnp.asarray(RNG.uniform(0.5, 2.0, mesh.num_cells))
    return plan, bc, f, rho0


def _csr_solve(plan, bc, f, rho, return_info=False):
    k = bc.apply_matrix_only(assemble(plan, wf.diffusion(rho)))
    return sparse_solve(k, f, "cg", 1e-12, 1e-12, 10000,
                        return_info=return_info)


def _mf_solve(plan, bc, f, rho, return_info=False):
    op = matfree_operator(plan, wf.diffusion(rho)).condensed(bc)
    return matfree_solve(op, f, "cg", 1e-12, 1e-12, 10000,
                         return_info=return_info)


# ---------------------------------------------------------------------------
# SolveInfo: converged flag + the info path is gradient-invisible
# ---------------------------------------------------------------------------

def test_solve_info_reports_convergence(problem):
    plan, bc, f, rho0 = problem
    u_plain = _csr_solve(plan, bc, f, rho0)
    u, info = _csr_solve(plan, bc, f, rho0, return_info=True)
    assert bool(info.converged)
    assert int(info.iters) > 0
    assert float(info.residual) < 1e-10
    np.testing.assert_array_equal(np.asarray(u), np.asarray(u_plain))


@pytest.mark.parametrize("solve", [_csr_solve, _mf_solve],
                         ids=["sparse_solve", "matfree_solve"])
def test_grad_parity_info_vs_plain(problem, solve):
    """grad through the return_info=True path matches the plain path to
    machine precision (the info leaves are stop-gradient)."""
    plan, bc, f, rho0 = problem

    def loss_plain(rho):
        return jnp.sum(solve(plan, bc, f, rho) ** 2)

    def loss_info(rho):
        u, info = solve(plan, bc, f, rho, return_info=True)
        return jnp.sum(u**2)

    g_plain = np.asarray(jax.grad(loss_plain)(rho0))
    g_info = np.asarray(jax.grad(loss_info)(rho0))
    scale = np.abs(g_plain).max()
    assert np.abs(g_info - g_plain).max() <= 1e-15 * max(scale, 1.0)


def test_grad_wrt_rhs_parity(problem):
    plan, bc, f, rho0 = problem
    g_plain = jax.grad(lambda b: jnp.sum(_csr_solve(plan, bc, b, rho0) ** 2))(f)
    g_info = jax.grad(
        lambda b: jnp.sum(_csr_solve(plan, bc, b, rho0, return_info=True)[0] ** 2)
    )(f)
    np.testing.assert_allclose(np.asarray(g_info), np.asarray(g_plain),
                               atol=1e-15)


# ---------------------------------------------------------------------------
# rollouts: per-step SolveInfo stacked out of the scan
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def heat():
    m = unit_square_tri(6)
    sp = FunctionSpace(m, element_for_mesh(m))
    asm = GalerkinAssembler(sp)
    bc = DirichletCondenser(asm, sp.boundary_dofs())
    mass = asm.assemble(wf.mass())
    stiff = asm.assemble(wf.diffusion(1.0))
    u0 = jnp.asarray(RNG.standard_normal(sp.num_dofs))
    return mass, stiff, bc, bc.project_residual(u0)


@pytest.mark.parametrize("backend", ["csr", "matfree"])
def test_rollout_info_trajectory(heat, backend):
    mass, stiff, bc, u0 = heat
    integ = ThetaIntegrator(mass, stiff,
                            dt=0.01, theta=1.0, bc=bc, backend=backend)
    n_steps = 5
    traj_plain = integ.rollout(u0, n_steps)
    traj, info = integ.rollout(u0, n_steps, return_info=True)
    assert info.iters.shape == (n_steps,)
    assert info.residual.shape == (n_steps,)
    assert bool(info.converged.all())
    assert int(info.iters.min()) > 0
    np.testing.assert_array_equal(np.asarray(traj), np.asarray(traj_plain))


def test_rollout_grad_parity(heat):
    mass, stiff, bc, u0 = heat

    def loss(u, with_info):
        integ = ThetaIntegrator(mass, stiff, dt=0.01, theta=1.0, bc=bc)
        if with_info:
            traj, _ = integ.rollout(u, 4, return_info=True)
        else:
            traj = integ.rollout(u, 4)
        return jnp.sum(traj**2)

    g_plain = np.asarray(jax.grad(loss)(u0, False))
    g_info = np.asarray(jax.grad(loss)(u0, True))
    assert np.abs(g_info - g_plain).max() <= 1e-15 * max(np.abs(g_plain).max(), 1.0)


# ---------------------------------------------------------------------------
# unified jit-trace accounting vs the legacy counters
# ---------------------------------------------------------------------------

def test_trace_counters_agree_with_legacy():
    telemetry.enable()
    telemetry.reset()
    mesh = unit_square_tri(7)  # fresh static shape → genuinely new traces
    space = FunctionSpace(mesh, element_for_mesh(mesh))
    plan = build_plan(space)
    rho = jnp.asarray(RNG.uniform(0.5, 2.0, mesh.num_cells))
    x = jnp.asarray(RNG.standard_normal(space.num_dofs))

    core0, mf0 = n_core_traces(), n_matfree_traces()
    t_core0 = telemetry.jit_trace_total("assembly")
    t_mf0 = telemetry.jit_trace_total("matfree")

    k = assemble(plan, wf.diffusion(rho))
    jax.block_until_ready(k.vals)
    op = matfree_operator(plan, wf.diffusion(rho))
    jax.block_until_ready(op.matvec(x))
    # value-only updates must not retrace on either accounting
    jax.block_until_ready(assemble(plan, wf.diffusion(2.0 * rho)).vals)
    jax.block_until_ready(matfree_operator(plan, wf.diffusion(3.0 * rho)).matvec(x))

    d_core = n_core_traces() - core0
    d_mf = n_matfree_traces() - mf0
    assert d_core >= 1 and d_mf >= 1
    assert telemetry.jit_trace_total("assembly") - t_core0 == d_core
    assert telemetry.jit_trace_total("matfree") - t_mf0 == d_mf

    snap = telemetry.snapshot()
    cache = {k_: v for k_, v in snap["counters"].items()
             if k_.startswith("cache_lookups")}
    assert any("outcome=miss" in k_ for k_ in cache)
    assert any("outcome=hit" in k_ for k_ in cache)


# ---------------------------------------------------------------------------
# disabled = zero cost
# ---------------------------------------------------------------------------

def test_disabled_records_nothing_and_never_retraces(problem):
    plan, bc, f, rho0 = problem
    assert not telemetry.is_enabled()
    u, info = _csr_solve(plan, bc, f, rho0, return_info=True)
    assert bool(info.converged)
    assert telemetry.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}
    assert events.event_log() == []
    assert telemetry.jsonl_path() is None

    # toggling telemetry must not invalidate compiled executables: the same
    # (plan, form-signature) solve retraces neither accounting
    core0, mf0 = n_core_traces(), n_matfree_traces()
    with telemetry.enabled():
        _csr_solve(plan, bc, f, 2.0 * rho0, return_info=True)
        _mf_solve(plan, bc, f, 2.0 * rho0, return_info=True)
    assert n_core_traces() == core0
    assert n_matfree_traces() == mf0


def test_tracers_are_never_recorded():
    with telemetry.enabled():
        @jax.jit
        def f(x):
            telemetry.histogram_observe("h", x)
            telemetry.gauge_set("g", x)
            events.record_event("solve", "traced", wall_us=None, value=x)
            return 2.0 * x

        jax.block_until_ready(f(jnp.array(1.0)))
        snap = telemetry.snapshot()
        assert snap["histograms"] == {} and snap["gauges"] == {}
        assert all(e["name"] != "traced" for e in events.event_log())


# ---------------------------------------------------------------------------
# non-convergence is loud (with telemetry off too)
# ---------------------------------------------------------------------------

def test_nonconvergence_warns_by_default(heat):
    mass, stiff, bc, u0 = heat
    integ = ThetaIntegrator(mass, stiff, dt=0.01, theta=1.0, bc=bc, maxiter=1)
    assert not telemetry.is_enabled()
    with pytest.warns(ConvergenceWarning, match="did NOT converge"):
        _, info = integ.rollout(u0, 3, return_info=True)
    assert not bool(info.converged.all())


def test_nonconvergence_raise_policy(heat):
    mass, stiff, bc, u0 = heat
    integ = ThetaIntegrator(mass, stiff, dt=0.01, theta=1.0, bc=bc, maxiter=1)
    with telemetry.enabled(on_nonconverged="raise"):
        with pytest.raises(NonConvergedError, match="theta.rollout"):
            integ.rollout(u0, 3, return_info=True)


def test_check_convergence_is_noop_under_trace(problem):
    plan, bc, f, rho0 = problem

    @jax.jit
    def solve(rho):
        u, info = _csr_solve(plan, bc, f, rho, return_info=True)
        assert events.check_convergence(info, on_fail="raise") is None
        return u

    jax.block_until_ready(solve(rho0))


# ---------------------------------------------------------------------------
# events, JSONL export, report CLI
# ---------------------------------------------------------------------------

def test_events_stream_and_report_cli(problem, tmp_path, capsys):
    plan, bc, f, rho0 = problem
    jsonl = str(tmp_path / "telemetry.jsonl")
    with telemetry.enabled(jsonl=jsonl):
        from repro.fem import PoissonProblem

        prob = PoissonProblem(unit_square_tri(6))
        _, info = prob.solve(return_info=True)
        assert bool(info.converged)
        telemetry.export_jsonl(jsonl)

    kinds = {e["kind"] for e in events.event_log()}
    assert "solve" in kinds and "assembly" in kinds

    with open(jsonl) as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    solves = [r for r in rows if r.get("kind") == "solve"]
    assert solves and all(r["converged"] for r in solves)
    assert any(r.get("kind") == "assembly" for r in rows)
    assert any(r["name"].startswith("metric/counter/jit_traces") for r in rows)

    assert report.main([jsonl]) == 0
    out = capsys.readouterr().out
    assert "Solves" in out and "converged" in out
    assert report.main([str(tmp_path / "missing.jsonl")]) == 2


def test_capture_writes_profile(tmp_path):
    d = str(tmp_path / "trace")
    # the profiler serializes metadata for every live compiled executable;
    # late in a long pytest session that dump can abort the process, so the
    # capture must not depend on how many programs earlier tests compiled
    jax.clear_caches()
    with telemetry.enabled():
        with telemetry.capture(d):
            jax.block_until_ready(jnp.ones(64) @ jnp.ones((64, 8)))
    files = [os.path.join(dp, fn) for dp, _, fns in os.walk(d) for fn in fns]
    assert files, "profiler capture produced no files"
    assert any(e["kind"] == "profile" for e in events.event_log())


def test_gauges_record_memory_footprints():
    with telemetry.enabled():
        mesh = unit_square_tri(4)
        space = FunctionSpace(mesh, element_for_mesh(mesh))
        plan = build_plan(space)
        assemble(plan, wf.diffusion(1.0))
        matfree_operator(plan, wf.diffusion(1.0))
        gauges = telemetry.snapshot()["gauges"]
    assert any(k.startswith("plan_bytes") for k in gauges)
    assert any(k.startswith("csr_bytes") for k in gauges)
    assert any(k.startswith("operator_state_bytes") for k in gauges)
    assert all(v > 0 for v in gauges.values())

"""Program spans on the profiler's clock: the span log, live spans as
profiler annotations, ``host.gc`` spans, the ``tg.solve`` / ``tg.assemble``
trees, the ``krylov_traces`` counter and the ``tg.spmv`` scope.

Acceptance-critical properties:

* every finished span lands in a bounded log with its parent, and a span
  opened with ``with telemetry.span(...)`` shows up as a host event of the
  same name in a CPU profile, on a clock that keeps the log's intervals;
* disabled telemetry logs nothing (no spans, no ``host.gc``) and toggling
  it never retraces;
* ``krylov_traces`` counts one trace per eager ``PoissonProblem.solve``;
* the solve and assembly trees have the documented names and nesting.
"""

import gc
import glob
import time
from collections import deque

import jax
import jax.numpy as jnp
import pytest

from repro import telemetry
from repro.core import SolverSpec, sparse, unit_square_tri
from repro.core.solvers import KRYLOV_TRACES
from repro.fem.tensormesh import PoissonProblem
from repro.telemetry import events, metrics, spans


def _reset():
    telemetry.disable()
    telemetry.reset()
    telemetry.clear_events()
    metrics._STATE.jsonl = None


@pytest.fixture(autouse=True)
def _clean_telemetry():
    _reset()
    yield
    _reset()


@pytest.fixture(scope="module")
def prob():
    return PoissonProblem(unit_square_tri(6))


def _names(log):
    return [s["name"] for s in log]


def _children(log, parent):
    return [s["name"] for s in log if s["parent_id"] == parent["span_id"]]


def _only(log, name):
    found = [s for s in log if s["name"] == name]
    assert len(found) == 1, _names(log)
    return found[0]


def test_span_log_bounded_and_holds_finished_spans(monkeypatch):
    telemetry.enable()
    with telemetry.span("outer", k=1) as outer:
        with telemetry.span("inner"):
            assert telemetry.span_log() == []  # open spans are not logged
    log = telemetry.span_log()
    assert _names(log) == ["inner", "outer"]
    inner, top = log
    assert inner["parent_id"] == outer.span_id == top["span_id"]
    assert top["parent_id"] is None and top["tags"] == {"k": 1}
    assert inner["trace_id"] == top["trace_id"]
    assert top["start_ns"] <= inner["start_ns"] <= inner["end_ns"] \
        <= top["end_ns"]
    # bounded: the newest are kept
    monkeypatch.setattr(spans, "_SPAN_LOG", deque(maxlen=4))
    for i in range(10):
        telemetry.span_root(f"s{i}").finish()
    assert _names(telemetry.span_log()) == ["s6", "s7", "s8", "s9"]
    telemetry.reset()
    assert telemetry.span_log() == []


def test_live_span_is_a_host_event_of_a_cpu_profile(tmp_path):
    from jax.profiler import ProfileData

    x = jnp.ones((64, 64))
    f = jax.jit(lambda a: a @ a)
    f(x).block_until_ready()
    telemetry.enable()
    with telemetry.capture(str(tmp_path)):
        with telemetry.span("tg.probe"):
            f(x).block_until_ready()
            with telemetry.span("tg.probe.inner"):
                f(x).block_until_ready()
        # a segment built afterwards goes to the log only
        telemetry.span_root("tg.afterwards",
                            start_ns=time.monotonic_ns()).finish()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {ev.name: (ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    assert "tg.probe" in host and "tg.probe.inner" in host
    assert "tg.afterwards" not in host
    assert "tg.afterwards" in _names(telemetry.span_log())
    # one clock offset maps the log onto the profile: the same intervals
    log = {s["name"]: (s["start_ns"], s["end_ns"])
           for s in telemetry.span_log()}
    offset = host["tg.probe"][0] - log["tg.probe"][0]
    for name in ("tg.probe", "tg.probe.inner"):
        for t_log, t_trace in zip(log[name], host[name]):
            assert abs(t_log + offset - t_trace) < 1e6  # 1 ms


def test_disabled_logs_nothing_and_never_retraces():
    traces = []

    @jax.jit
    def f(x):
        traces.append(1)
        with telemetry.span("tg.inside_jit"):
            return 2 * x

    x = jnp.ones(4)
    f(x)
    with telemetry.span("tg.off") as sp:
        assert sp is telemetry.NULL_SPAN
    gc.collect()
    assert telemetry.span_log() == []
    assert spans._on_gc not in gc.callbacks
    with telemetry.enabled():
        assert spans._on_gc in gc.callbacks
        f(x)
    assert spans._on_gc not in gc.callbacks
    f(x)
    assert len(traces) == 1


def test_gc_collection_is_a_host_gc_span():
    telemetry.enable()
    gc.collect()
    telemetry.disable()
    logged = telemetry.span_log()
    gc.collect()
    assert telemetry.span_log() == logged
    full = [s for s in logged
            if s["name"] == "host.gc" and s["tags"] == {"generation": 2}]
    assert len(full) == 1
    assert full[0]["start_ns"] <= full[0]["end_ns"]
    assert full[0]["parent_id"] is None


@pytest.mark.parametrize("method", ["cg", "bicgstab"])
def test_krylov_traces_one_per_eager_solve(prob, method):
    spec = SolverSpec(method=method, tol=1e-10, atol=0.0, maxiter=500)
    prob.solve(spec=spec, backend="csr")  # compiled outside the count
    telemetry.enable()
    for _ in range(3):
        prob.solve(spec=spec, backend="csr")
    counters = telemetry.snapshot()["counters"]
    assert counters == {**counters,
                        f"{KRYLOV_TRACES}{{method={method}}}": 3}


@pytest.mark.parametrize("backend", ["csr", "matfree"])
def test_solve_and_assemble_span_trees(prob, backend):
    telemetry.enable()
    prob.solve(backend=backend, spec=SolverSpec(method="cg"))
    log = [s for s in telemetry.span_log() if s["name"] != "host.gc"]
    root = _only(log, "tg.solve")
    assert root["parent_id"] is None
    if backend == "csr":
        assert _children(log, root) == ["tg.assemble", "tg.solve.krylov",
                                        "tg.solve.wait"]
        asm = _only(log, "tg.assemble")
        assert _children(log, asm) == ["tg.assemble.matrix",
                                       "tg.assemble.vector", "tg.condense"]
    else:
        assert _children(log, root) == ["tg.solve.krylov", "tg.solve.wait"]
    krylov, wait = _only(log, "tg.solve.krylov"), _only(log, "tg.solve.wait")
    assert krylov["end_ns"] <= wait["start_ns"]
    assert {s["trace_id"] for s in log} == {root["trace_id"]}


def test_assemble_alone_is_a_root(prob):
    telemetry.enable()
    prob.assemble()
    log = [s for s in telemetry.span_log() if s["name"] != "host.gc"]
    root = log[-1]
    assert root["name"] == "tg.assemble" and root["parent_id"] is None
    assert _children(log, root) == ["tg.assemble.matrix",
                                    "tg.assemble.vector", "tg.condense"]


def test_solve_and_assembly_events_carry_no_dispatch_wall(prob):
    telemetry.enable()
    prob.solve(backend="csr", spec=SolverSpec(method="cg"))
    evs = events.event_log()
    assert {e["kind"] for e in evs} >= {"solve", "assembly"}
    assert all("wall_us" not in e for e in evs)
    hists = telemetry.snapshot()["histograms"]
    assert not any(k.startswith(("solve_wall_us", "assembly_wall_us"))
                   for k in hists)


@pytest.mark.parametrize("scatter_free", [False, True])
def test_spmv_scope_in_lowered_matvec(prob, monkeypatch, scatter_free):
    k, f = prob.assemble()
    if scatter_free:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert "tg.spmv" in jax.jit(k.matvec).lower(f).as_text(debug_info=True)
    kb = sparse.BatchedCSR(jnp.stack([k.vals, 2 * k.vals]), k.indptr,
                           k.indices, k.row_of_nnz, k.shape, k.diag_pos)
    text = jax.jit(kb.matvec).lower(jnp.stack([f, f])).as_text(
        debug_info=True)
    assert "tg.spmv" in text

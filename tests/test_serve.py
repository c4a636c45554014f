"""repro.serve — admission batching, executable cache, QoS error paths.

The acceptance-critical properties:

* B heterogeneous-coefficient requests through one admission batch are
  bitwise-close (<= 1e-12) to B sequential reference solves, on both the
  csr and matfree backends,
* after warmup, waves of compatible requests are pure executable-cache
  hits — zero ``jit_traces{kind=serve}`` retraces across >= 3 waves,
* deadline-expired, shed-at-admission and non-converged requests come back
  with typed errors (DeadlineExpired / Overloaded / NonConverged), never
  with a silent wrong answer.
"""

import dataclasses
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro import serve, telemetry
from repro.core import assemble, matfree_operator, matfree_solve, sparse_solve
from repro.serve import (
    DeadlineExpired,
    ExecutableCache,
    NonConverged,
    Overloaded,
    SolveService,
    admission_key,
    pad_bucket,
)
from repro.telemetry import ConvergenceWarning

RES = 6  # tiny shared Poisson workload (plan memoized inside serve.client)


def _wave(n, **kw):
    return serve.poisson_requests(n_requests=n, resolution=RES, **kw)


# ---------------------------------------------------------------------------
# units: pad buckets + compatibility keys
# ---------------------------------------------------------------------------

def test_pad_bucket():
    assert [pad_bucket(b) for b in (1, 2, 3, 5, 8, 9, 16)] == \
        [1, 2, 4, 8, 8, 16, 16]
    with pytest.raises(ValueError):
        pad_bucket(0)


def test_admission_key_compatibility():
    a, b = _wave(2)
    # same plan/form signature/bc/knobs, different coefficient VALUES
    assert admission_key(a) == admission_key(b)
    assert not np.allclose(np.asarray(a.leaves[0]), np.asarray(b.leaves[0]))
    assert admission_key(dataclasses.replace(a, tol=1e-8)) != admission_key(a)
    assert admission_key(dataclasses.replace(a, maxiter=7)) != admission_key(a)
    mf = _wave(1, backend="matfree")[0]
    assert admission_key(mf) != admission_key(a)
    with pytest.raises(ValueError, match="unknown backend"):
        dataclasses.replace(a, backend="ell")


# ---------------------------------------------------------------------------
# parity: one admission batch vs B sequential reference solves
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["csr", "matfree"])
def test_batched_requests_match_sequential(backend):
    reqs = _wave(5, backend=backend)  # 5 pads to bucket 8
    svc = SolveService(window=0.0)
    pend = [svc.submit(r) for r in reqs]
    assert not pend[0].done()
    assert svc.drain() == 5
    for rq, p in zip(reqs, pend):
        resp = p.response()
        assert resp.ok and resp.batch_size == 5
        assert resp.info is not None and bool(resp.info.converged)
        f = rq.rhs * rq.bc.free_mask
        if backend == "csr":
            k = rq.bc.apply_matrix_only(assemble(rq.plan, rq.form))
            u_ref = sparse_solve(k, f, rq.method, rq.tol, rq.tol, rq.maxiter)
        else:
            op = matfree_operator(rq.plan, rq.form).condensed(rq.bc)
            u_ref = matfree_solve(op, f, rq.method, rq.tol, rq.tol,
                                  rq.maxiter)
        err = float(jnp.max(jnp.abs(p.result() - u_ref)))
        assert err < 1e-12, f"{backend} request {rq.request_id}: {err:.3e}"


def test_mixed_backends_split_into_groups():
    reqs = _wave(2) + _wave(2, backend="matfree")
    svc = SolveService(window=0.0)
    pend = [svc.submit(r) for r in reqs]
    assert svc.drain() == 4
    resps = [p.response() for p in pend]
    assert all(r.ok for r in resps)
    # two incompatible groups of 2, not one batch of 4
    assert [r.batch_size for r in resps] == [2, 2, 2, 2]
    # csr and matfree answers agree on the same request family
    assert float(jnp.max(jnp.abs(resps[0].u - resps[2].u))) < 1e-9


def test_max_batch_chunks_one_group():
    reqs = _wave(5)
    svc = SolveService(window=0.0, max_batch=2)
    pend = [svc.submit(r) for r in reqs]
    assert svc.drain() == 5
    sizes = [p.response().batch_size for p in pend]
    assert sizes == [2, 2, 2, 2, 1]
    assert all(p.response().ok for p in pend)


# ---------------------------------------------------------------------------
# QoS paths: deadline, shedding, non-convergence policy
# ---------------------------------------------------------------------------

def test_deadline_expired_path():
    reqs = _wave(2, timeout=1e-3)
    svc = SolveService(window=0.0)
    pend = [svc.submit(r) for r in reqs]
    time.sleep(0.01)  # let both deadlines pass while queued
    assert svc.drain() == 2
    for p in pend:
        resp = p.response()
        assert resp.status == "expired" and resp.u is None
        with pytest.raises(DeadlineExpired):
            p.result()


def test_overload_shedding():
    reqs = _wave(4)
    svc = SolveService(window=0.0, queue_limit=2)
    pend = [svc.submit(r) for r in reqs]
    # beyond the bounded queue: resolved immediately, never queued
    assert pend[2].done() and pend[3].done()
    for p in pend[2:]:
        assert p.response().status == "overloaded"
        with pytest.raises(Overloaded):
            p.result()
    svc.drain()
    assert all(p.response().ok for p in pend[:2])


def test_nonconverged_raise_policy():
    reqs = [dataclasses.replace(r, maxiter=3) for r in _wave(2)]
    with telemetry.enabled(on_nonconverged="raise"):
        svc = SolveService(window=0.0)
        pend = [svc.submit(r) for r in reqs]
        svc.drain()
    for p in pend:
        resp = p.response()
        assert resp.status == "nonconverged" and resp.u is None
        assert not bool(resp.info.converged)
        with pytest.raises(NonConverged):
            p.result()


def test_nonconverged_warn_policy_answers_ok():
    reqs = [dataclasses.replace(r, maxiter=3) for r in _wave(2)]
    with telemetry.enabled(on_nonconverged="warn"):
        svc = SolveService(window=0.0)
        pend = [svc.submit(r) for r in reqs]
        with pytest.warns(ConvergenceWarning):
            svc.drain()
    assert all(p.response().ok and p.response().u is not None for p in pend)


# ---------------------------------------------------------------------------
# executable cache: warmup → zero retraces, LRU eviction, pinning
# ---------------------------------------------------------------------------

def test_zero_retraces_and_full_hit_rate_across_waves():
    with telemetry.enabled():
        svc = SolveService(window=0.0)
        svc.warmup(_wave(1)[0], batch_sizes=(4,))
        base = telemetry.jit_trace_total("serve")
        hits0, miss0 = svc.cache.hits, svc.cache.misses
        for w in range(3):
            pend = [svc.submit(r) for r in _wave(4, seed=w + 1)]
            svc.drain()
            assert all(p.response().ok and p.response().cache_hit
                       for p in pend)
        assert telemetry.jit_trace_total("serve") - base == 0
        assert svc.cache.misses == miss0, "cache missed after warmup"
        assert svc.cache.hits - hits0 == 3  # one lookup per wave, all hits


def test_cache_eviction_and_pinning():
    base = _wave(1)[0]
    variants = [dataclasses.replace(base, tol=10.0 ** -(6 + i))
                for i in range(4)]
    keys = [admission_key(v) for v in variants]
    cache = ExecutableCache(capacity=2)
    cache.pin(keys[0], 1)
    for v, k in zip(variants, keys):
        cache.get(k, 1, v)
    # 4 entries, 1 pinned, capacity 2 unpinned -> keys[1] (LRU unpinned) out
    assert len(cache) == 3 and cache.evictions == 1
    _, hit = cache.get(keys[0], 1, variants[0])
    assert hit, "pinned entry must survive eviction"
    _, hit = cache.get(keys[1], 1, variants[1])
    assert not hit, "LRU unpinned entry should have been evicted"
    cache.unpin(keys[0], 1)
    cache._evict()
    assert cache.hit_rate() == pytest.approx(1 / 6)


# ---------------------------------------------------------------------------
# threaded dispatch path (the production lifecycle)
# ---------------------------------------------------------------------------

def test_worker_thread_end_to_end():
    reqs = _wave(3)
    svc = SolveService(window=0.001)
    # submissions before start() queue up and dispatch on the first window
    early = svc.submit(reqs[0])
    with svc:
        pend = [svc.submit(r) for r in reqs[1:]]
        us = [p.result(timeout=60.0) for p in [early, *pend]]
    assert all(u.shape == reqs[0].rhs.shape for u in us)
    k = reqs[0].bc.apply_matrix_only(assemble(reqs[0].plan, reqs[0].form))
    u_ref = sparse_solve(k, reqs[0].rhs * reqs[0].bc.free_mask,
                         reqs[0].method, reqs[0].tol, reqs[0].tol,
                         reqs[0].maxiter)
    assert float(jnp.max(jnp.abs(us[0] - u_ref))) < 1e-12


def test_stop_drains_pending_requests():
    svc = SolveService(window=0.0)
    svc.start()
    pend = [svc.submit(r) for r in _wave(2)]
    svc.stop()  # must answer everything still queued
    assert all(p.done() and p.response().ok for p in pend)


def test_solve_convenience_inline():
    svc = SolveService(window=0.0)
    rq = _wave(1)[0]
    u = svc.solve(rq)  # no worker -> drained inline
    assert u.shape == rq.rhs.shape


# ---------------------------------------------------------------------------
# request tracing: span trees, flight recorder, attribution gauges (PR 10)
# ---------------------------------------------------------------------------

def test_response_span_tree_segments_sum_to_e2e():
    with telemetry.enabled():
        svc = SolveService(window=0.0)
        svc.warmup(_wave(1)[0], batch_sizes=(4,))
        pend = [svc.submit(r) for r in _wave(4, seed=3)]
        svc.drain()
        for p in pend:
            resp = p.response()
            assert resp.ok
            tree = resp.trace
            assert tree["name"] == "serve.request"
            assert tree["tags"]["outcome"] == "ok"
            assert tree["tags"]["request_id"] == p.request.request_id
            seg = resp.span_segments_us
            assert list(seg) == ["queue_wait", "dispatch", "solve", "fetch",
                                 "slice"]
            # the acceptance criterion: segments cover the full lifetime
            e2e_us = 1e6 * resp.e2e_s
            assert sum(seg.values()) == pytest.approx(e2e_us, rel=0.05)
            # one trace id threads the whole tree
            ids = {tree["trace_id"]}
            for c in tree["children"]:
                ids.add(c["trace_id"])
            assert ids == {tree["trace_id"]}
        # distinct requests get distinct trace ids
        tids = {p.response().trace["trace_id"] for p in pend}
        assert len(tids) == 4


def test_batch_phases_are_live_spans_and_segments_are_contiguous():
    svc = SolveService(window=0.0)
    svc.warmup(_wave(1)[0], batch_sizes=(4,))
    with telemetry.enabled():
        telemetry.reset()
        pend = [svc.submit(r) for r in _wave(3, seed=5)]
        svc.drain()
        log = telemetry.span_log()
    telemetry.reset()
    (batch,) = [s for s in log if s["name"] == "serve.batch"]
    assert batch["tags"]["batch"] == 3 and batch["tags"]["padded"] == 4
    phases = [s for s in log if s["parent_id"] == batch["span_id"]]
    assert [s["name"] for s in phases] == [
        "cache_lookup", "pad", "solve", "fetch", "slice"]
    for a, b in zip(phases, phases[1:]):
        assert batch["start_ns"] <= a["end_ns"] <= b["start_ns"] \
            <= batch["end_ns"]
    for p in pend:
        tree = p.response().trace
        segs = tree["children"]
        assert [c["name"] for c in segs] == [
            "queue_wait", "dispatch", "solve", "fetch", "slice"]
        assert segs[0]["start_ns"] == tree["start_ns"]
        for a, b in zip(segs, segs[1:]):
            assert a["end_ns"] == b["start_ns"]
        assert segs[-1]["end_ns"] == tree["end_ns"]
        # each segment lies beside the live phase of the same name
        live = {s["name"]: s for s in phases}
        for c in segs[2:]:
            assert c["start_ns"] <= live[c["name"]]["start_ns"]
            assert c["end_ns"] >= live[c["name"]]["end_ns"]


def test_warmed_batch_traces_no_krylov_loop():
    svc = SolveService(window=0.0)
    with telemetry.enabled():
        telemetry.reset()
        # a fresh service compiles its bucket executables: the CG loop of
        # each is traced while warming up, and never again
        svc.warmup(_wave(1)[0], batch_sizes=(2, 4))
        warm = telemetry.snapshot()["counters"]
        assert warm["krylov_traces{method=cg}"] >= 2
        telemetry.reset()
        for seed in (7, 8):
            pend = [svc.submit(r) for r in _wave(3, seed=seed)]
            svc.drain()
            assert all(p.response().ok for p in pend)
        counters = telemetry.snapshot()["counters"]
    telemetry.reset()
    assert not any(k.startswith("krylov_traces") for k in counters)
    assert counters["serve_requests{outcome=ok}"] == 6


def test_disabled_responses_carry_no_trace():
    svc = SolveService(window=0.0)
    pend = [svc.submit(r) for r in _wave(2)]
    svc.drain()
    for p in pend:
        resp = p.response()
        assert resp.ok and resp.trace is None
        assert resp.span_segments_us == {}


def test_error_paths_carry_traces_and_flight_dumps(tmp_path):
    flight = str(tmp_path / "flight.jsonl")
    with telemetry.enabled(on_nonconverged="raise"):
        telemetry.configure_flight(capacity=32, path=flight)
        try:
            # expired
            svc = SolveService(window=0.0)
            pend = [svc.submit(r) for r in _wave(1, timeout=1e-3)]
            time.sleep(0.01)
            svc.drain()
            assert pend[0].response().trace["tags"]["outcome"] == "expired"
            # shed
            svc2 = SolveService(window=0.0, queue_limit=1)
            shed = [svc2.submit(r) for r in _wave(2)][1]
            assert shed.response().trace["tags"]["outcome"] == "shed"
            svc2.drain()
            # forced nonconverged
            bad = [dataclasses.replace(r, maxiter=3) for r in _wave(1)]
            p = svc2.submit(bad[0])
            svc2.drain()
            assert p.response().status == "nonconverged"
            assert p.response().trace["tags"]["outcome"] == "nonconverged"
        finally:
            rows = [json.loads(line) for line in open(flight)]
            reasons = {r["reason"] for r in rows if r["kind"] == "flight_dump"}
            assert {"expired", "shed", "nonconverged"} <= reasons
            outcomes = {r.get("outcome") for r in rows if r["kind"] == "flight"}
            assert {"expired", "shed", "nonconverged"} <= outcomes
            telemetry.clear_flight()
            from repro.telemetry import spans as _spans
            _spans._FLIGHT_PATH = None


def test_queue_depth_gauge_sampled_at_drain():
    with telemetry.enabled():
        telemetry.reset()  # metrics persist across enabled() scopes
        svc = SolveService(window=0.0)
        [svc.submit(r) for r in _wave(3)]
        svc.drain()
        snap = telemetry.snapshot()
        assert snap["gauges"]["serve_queue_depth"] == 3
        assert snap["histograms"]["serve_queue_depth"]["max"] == 3


def test_compile_and_memory_attribution_gauges():
    with telemetry.enabled():
        telemetry.reset()
        svc = SolveService(window=0.0)
        pend = [svc.submit(r) for r in _wave(2)]
        svc.drain()
        assert all(p.response().ok for p in pend)
        snap = telemetry.snapshot()
        compile_hists = [k for k in snap["histograms"]
                         if k.startswith("serve_compile_us")]
        assert compile_hists, "cache miss must record compile time"
        assert snap["histograms"][compile_hists[0]]["count"] == 1
        assert any(k.startswith("serve_exec_compile_us")
                   for k in snap["gauges"])
        assert snap["gauges"]["serve_exec_entries"] == len(svc.cache)
        # steady state: a second wave is a cache hit, no new compile rows
        pend = [svc.submit(r) for r in _wave(2, seed=5)]
        svc.drain()
        snap2 = telemetry.snapshot()
        assert snap2["histograms"][compile_hists[0]]["count"] == 1


def test_load_report_span_coverage():
    with telemetry.enabled():
        reqs = _wave(6)
        with SolveService(window=0.002) as svc:
            svc.warmup(reqs[0], batch_sizes=(1, 4))
            report = serve.open_loop_load(svc, reqs, rate=2000.0)
        assert report.ok == 6
        assert report.span_coverage == pytest.approx(1.0, rel=0.05)
        assert report.queue_depth_max >= 1

"""The trace reduction and the byte count, against hand-counted cases and
a small trace recorded on a TPU v5e."""

import gzip
from pathlib import Path

import numpy as np
import pytest
import scipy.sparse as sp

from harness import costs, trace, traffic
from reference import darcy2d_p1 as ref

# a 16 x 16 Darcy assembly and solve under the benchmark's spans, recorded
# on one TPU v5e with the Python tracer off
RECORDED = Path(__file__).parent / "data" / "small.xplane.pb.gz"


def test_reduce_events_by_hand():
    # one device; window [0, 100] ns; ops cover [10, 30] ∪ [25, 40] ∪ [60, 70]
    ops = [("a", 10, 30), ("b", 25, 40), ("a", 60, 70), ("c", 95, 120)]
    spans = [("bench.call", 0, 50), ("bench.block", 50, 90),
             ("bench.compile", 72, 80)]
    s = trace.reduce_events([(ops, [])], spans, (0, 100))
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx((30 + 10 + 5) * 1e-9)
    assert dict(s.device_ops) == pytest.approx(
        {"a": 30e-9, "b": 15e-9, "c": 5e-9})
    # gaps: [0,10] call, [40,60] call|block midpoint 50 -> block,
    # [70,95] midpoint 82.5 -> block
    assert sorted(s.idle_gaps) == sorted(
        [("bench.call", 10e-9), ("bench.block", 20e-9),
         ("bench.block", 25e-9)])
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s)


def test_reduce_events_averages_devices():
    s = trace.reduce_events([([("x", 0, 50)], []), ([("x", 0, 100)], [])],
                            [], (0, 100))
    assert s.busy_s == pytest.approx(75e-9)


def test_programs_cover_dropped_ops():
    # an op event missing inside its program still counts as busy
    s = trace.reduce_events([([("a", 10, 20)], [("jit_f", 10, 60)])], [],
                            (0, 100))
    assert s.busy_s == pytest.approx(50e-9)
    assert dict(s.device_ops) == pytest.approx({"a": 10e-9})


def test_programs_add_up_by_name():
    # two runs of one program with different fingerprints, one clipped
    programs = [("jit_while(1)", 10, 40), ("jit_while(2)", 50, 120),
                ("jit__lambda(3)", 40, 50)]
    s = trace.reduce_events([([], programs)], [], (0, 100))
    assert s.programs == pytest.approx([("jit_while", 80e-9),
                                        ("jit__lambda", 10e-9)])


def test_krylov_counts_only_solves_traced_whole():
    from types import SimpleNamespace

    from harness.cell import Run

    # steps on the host's monotonic clock; the trace clock is 1000 ns
    # ahead and the traced stretch is [1050, 1250]: only the second solve
    # lies wholly inside it
    steps = [{"iters": 10, "t0_ns": 0, "t1_ns": 100},
             {"iters": 20, "t0_ns": 100, "t1_ns": 200},
             {"iters": 30, "t0_ns": 200, "t1_ns": 300}]
    programs = [("jit_while(1)", 1010, 1090), ("jit_while(1)", 1110, 1190),
                ("jit_while(2)", 1210, 1290), ("jit__lambda(3)", 1100, 1105)]
    s = trace.reduce_events([([], programs)], [], (1050, 1250))
    s.clock_offset_ns = 1000
    run = Run({}, SimpleNamespace(steps=steps), None, None, {"count": 1}, s)
    seconds, iters = run.krylov()
    assert iters == 20 and seconds == pytest.approx(80e-9)
    s.clock_offset_ns = 5000
    assert run.krylov() is None


def test_recorded_tpu_trace():
    from jax.profiler import ProfileData

    data = ProfileData.from_serialized_xspace(gzip.decompress(
        RECORDED.read_bytes()))
    devices, spans, window = trace.read_profile(data)
    assert len(devices) == 1 and window[1] > window[0]
    assert {name for name, _, _ in spans} >= {"bench.call", "bench.block"}
    s = trace.reduce_events(devices, spans, window)
    # busy time by a second method: a boolean timeline at 10 ns resolution
    lo, hi = window
    t = np.zeros(int(hi - lo) // 10 + 1, bool)
    ops, programs = devices[0]
    for _, a, b in ops + programs:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            t[int(a - lo) // 10:int(b - lo) // 10] = True
    assert s.busy_s == pytest.approx(t.sum() * 10e-9, rel=1e-2)
    # the programs and the ops inside them cover the same time
    ops_only = trace.reduce_events([(ops, [])], spans, window)
    assert ops_only.busy_s == pytest.approx(s.busy_s, rel=0.03)
    assert 0 < s.busy_s <= s.window_s
    # the CG loop is the program with the most device time
    assert s.programs[0][0] == "jit_while"
    assert sum(v for _, v in s.programs) <= s.busy_s * (1 + 1e-9)
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)


def test_pcg_bytes_by_hand():
    # one square cell, two triangles: 4 vertices, 5 edges -> 14 entries
    assert ref.nnz(1) == 14
    n = 4
    matrix = 14 * (8 + 4) + (n + 1) * 4
    vectors = (14 + 5) * n * 8
    assert costs.jacobi_pcg_iteration_bytes(n, 14) == matrix + vectors == 796


@pytest.mark.parametrize("cells", [1, 3, 8])
def test_nnz_matches_the_pattern(cells):
    points, elems = ref.mesh(cells)
    rows = np.repeat(elems, 3, axis=1).ravel()
    cols = np.tile(elems, 3).ravel()
    pattern = sp.coo_matrix((np.ones(rows.size), (rows, cols))).tocsr()
    assert pattern.nnz == ref.nnz(cells)


def test_constant_arrivals():
    due = traffic.arrivals(2**33 + 1, [{"rate_per_s": 8.0}], 51.0)
    assert len(due) == 408 and np.all(np.diff(due) >= 0)
    assert 0.0 <= due[0] and due[-1] < 51.0
    other = traffic.arrivals(2**33 + 2, [{"rate_per_s": 8.0}], 51.0)
    assert len(other) == len(due) and not np.array_equal(other, due)


def test_on_off_arrivals():
    phases = [{"rate_per_s": 30.0, "seconds": 2.0},
              {"rate_per_s": 0.0, "seconds": 4.0}]
    due = traffic.arrivals(7, phases, 15.0)
    # on during [0, 2), [6, 8), [12, 14): 60 arrivals each
    assert len(due) == 180
    assert np.all((due % 6.0) < 2.0)
    with pytest.raises(ValueError):
        traffic.arrivals(7, [{"rate_per_s": 0.0}], 15.0)


def test_fields_in_three_dimensions():
    g = np.linspace(0.0, 1.0, 5)
    points = np.stack(np.meshgrid(g, g, g, indexing="ij"), -1).reshape(-1, 3)
    cells = np.array([[0, 1, 5, 25], [31, 32, 36, 56], [99, 100, 104, 124]])
    spec = {"modes": 6, "alpha": 2.0, "tau": 3.0, "a_pos": 12.0,
            "a_neg": 3.0}
    sampler = traffic.FieldSampler(points, cells, spec)
    a = sampler.field(3, 0)
    assert a.shape == (3,) and set(a) <= {12.0, 3.0}
    assert np.array_equal(a, sampler.field(3, 0))

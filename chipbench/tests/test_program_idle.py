"""The readers of the program's own spans and counter: by hand, on a
program that keeps no span log, and on every cell at 16 x 16 on the CPU.

The CPU's trace has no device plane.  In the cell runs here each
``PjRtCpuExecutable::Execute`` event of its host plane, one run of a
compiled program, stands in for a device program run, so that the
reduction of idle time by program span runs on a real trace and on the
program's real spans."""

import time
import types

import pytest
from conftest import SEED
from test_cells import CELLS, _overrides

from harness import cell, program_idle
from harness.cell import by_name, load_bench, measure, prepare
from harness.program_idle import HOST, idle_by_span

NEW = {"darcy421.solve": ("krylov_traces_per_solve", "program_idle_pct.solve"),
       "darcy421.assemble": ("assemble_host_ms", "program_idle_pct.assemble"),
       "darcy128.serve": ("serve_fetch_ms", "program_idle_pct.serve")}
CPU_RUN = "PjRtCpuExecutable::Execute"


def test_idle_by_span_by_hand():
    # busy [10,20] [40,50] [70,100]: the stretch is [10,100], idle (20,40)
    # and (50,70); before 10 nothing counts
    runs = [(40, 50), (10, 20), (70, 100)]
    spans = [("tg.a", 0, 60), ("tg.b", 30, 45), ("host.gc", 55, 60),
             ("tg.empty", 25, 25), ("tg.early", 0, 5)]
    assert idle_by_span(spans, runs) == {
        "tg.a": 10 + 5, "tg.b": 10, "host.gc": 5, HOST: 10}
    assert idle_by_span(spans, []) == {}


def test_spans_that_meet_hand_over():
    spans = [("x", 0, 30), ("y", 30, 60)]
    assert idle_by_span(spans, [(0, 10), (50, 60)]) == {"x": 20, "y": 20}


def test_a_program_without_the_span_log_gives_nothing(monkeypatch):
    from repro import telemetry
    from repro.core import solvers

    monkeypatch.delattr(telemetry, "span_log")
    monkeypatch.delattr(solvers, "KRYLOV_TRACES")
    trace = types.SimpleNamespace(window_s=1.0, clock_offset_ns=0,
                                  program_runs={"p": [(0, 10)]})
    run = types.SimpleNamespace(trace=trace,
                                driver=types.SimpleNamespace(steps=[1]))
    for names in NEW.values():
        for name in names:
            assert by_name("metrics", name).read(run) is None


def _with_cpu_runs(read_xplane):
    def read(path):
        from jax.profiler import ProfileData

        devices, host, window = read_xplane(path)
        runs = [("cpu_program", ev.start_ns, ev.start_ns + ev.duration_ns)
                for plane in ProfileData.from_file(path).planes
                if plane.name.startswith("/host:")
                for line in plane.lines for ev in line.events
                if ev.name == CPU_RUN]
        return devices + [([], runs)], host, window
    return read


def _traced(workload):
    """A traced run of the cell's new readers and its device idle share."""
    bench = load_bench()
    keep = set(NEW[workload]) | {"device_idle_pct." + workload.split(".")[1]}
    bench["per_layer"] = [m for m in bench["per_layer"] if m["name"] in keep]
    t_start = time.perf_counter()
    p = prepare(workload, SEED, 0.5, bench=bench, require_chip=False,
                overrides=_overrides(workload))
    return measure(p, True, t_start=t_start)


@pytest.mark.parametrize("workload", CELLS)
def test_readers_on_the_cpu_cells(workload, monkeypatch, capsys):
    from repro import telemetry

    monkeypatch.setattr(cell, "read_xplane", _with_cpu_runs(cell.read_xplane))
    telemetry.reset()
    out = _traced(workload)
    assert out["correct"] is True, out["checks"]
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    reader, idle = NEW[workload]
    assert isinstance(metrics[reader], float) and metrics[reader] > 0
    assert 0 < metrics[idle] <= metrics["device_idle_pct."
                                        + workload.split(".")[1]]
    assert "device idle by program span: " in capsys.readouterr().err
    counters = telemetry.snapshot()["counters"]
    traces = sum(v for k, v in counters.items()
                 if k.startswith("krylov_traces"))
    if workload == "darcy421.solve":
        assert metrics[reader] == 1.0
    else:
        # the service's executables are warmed in set-up: nothing traces
        assert traces == 0
    telemetry.reset()


def test_span_log_reader_is_the_programs():
    from repro import telemetry

    with telemetry.enabled():
        telemetry.reset()
        telemetry.span_root("tg.assemble").finish()
        assert [s["name"] for s in program_idle.span_log()] == ["tg.assemble"]
    telemetry.reset()

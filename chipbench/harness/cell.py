"""One run of one cell: set-up, the measured window, the trace reduction,
the per-layer readers, and the comparison with the reference.

Everything that belongs to one configuration, traffic mix, entry point or
per-layer metric is found by its name: ``configs/<config>.json`` (named by
``BENCHMARK.json``) with its plain reference ``reference/<reference>.py``,
``traffic/<mix>.json``, the driver of the mix's entry point
``drivers/<entry>.py``, and ``metrics/<metric>.py``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

from . import costs
from .spans import CompileLog, Spans
from .trace import (WINDOW_SPAN, TraceSummary, find_xplane, read_xplane,
                    reduce_events)

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_bench(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def lookup(bench: dict, workload: str):
    """``(cell, config, traffic)`` for a workload name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    mix = json.loads((BENCH_DIR / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    return cell, config, mix


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def device_info(chips: int, require_chip: bool = True) -> dict:
    import jax

    devices = jax.devices()
    if require_chip and (devices[0].platform == "cpu" or len(devices) < chips):
        raise NoChip(f"needs {chips} accelerator chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices[:chips])}


def peak_bytes(chips: int) -> int | None:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


@dataclasses.dataclass
class Run:
    """What a per-layer reader can read."""

    config: dict
    driver: object
    spans: Spans
    compiles: CompileLog
    device: dict
    trace: TraceSummary | None = None

    def peaks(self) -> dict:
        return load_peaks(self.device["kind"])

    def cg_iteration_bytes(self) -> int:
        n = self.config["cells_per_side"]
        ref = self.driver.ref
        return costs.jacobi_pcg_iteration_bytes(ref.dofs(n), ref.nnz(n))

    def krylov(self) -> tuple[float, int] | None:
        """``(device seconds, CG iterations)`` of the solves whose Krylov
        program ran wholly inside the traced stretch.  The Krylov program
        is the XLA program with the most device time there (``jit_while``
        today: the eager CG loop, one run per solve); a step's runs are
        those between its call and its block (``t0_ns``, ``t1_ns``).
        ``None`` where no solve was traced whole."""
        if self.trace is None or not self.trace.programs:
            return None
        runs = self.trace.program_runs.get(self.trace.programs[0][0], [])
        off = self.trace.clock_offset_ns
        seconds, iters = 0.0, 0
        for step in self.driver.steps:
            lo, hi = step["t0_ns"] + off, step["t1_ns"] + off
            inside = [e - s for s, e in runs if lo <= s and e <= hi]
            if inside:
                seconds += sum(inside) / 1e9 / self.device["count"]
                iters += step["iters"]
        return (seconds, iters) if iters else None

    def idle_pct(self) -> float | None:
        if self.trace is None or self.trace.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.trace.busy_s / self.trace.window_s)


def by_name(folder: str, name: str):
    """The module ``<folder>/<name>.py`` of the benchmark."""
    path = BENCH_DIR / folder / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {folder}/{name}.py in the benchmark")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{folder}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def configure_jax() -> None:
    import jax

    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    # every program goes into the cache, so a later run's set-up finds it
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Profiler:
    """The profiler over the whole window, or, where the traffic names
    ``trace_from_s`` and ``trace_seconds``, over that part of it only (a
    window of many small device operations would overflow the profiler's
    buffers).  A closed loop's part starts and stops between its steps, in
    its own thread (started from another thread while the loop compiles,
    the profiler hung); an open loop's, on a timer thread.  The traced
    stretch is the ``bench.window`` span."""

    def __init__(self, directory, start_s=None, seconds=None):
        self.directory, self.start_s, self.seconds = directory, start_s, seconds
        self.opened = None
        self.stopped = False
        self.stop_s = 0.0
        self._thread = None

    def _start(self):
        import jax

        options = jax.profiler.ProfileOptions()
        # the Python tracer would record every Python call: a large trace
        # and a slower host
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self.opened = (time.time(), time.monotonic_ns())

    def _stop(self):
        import jax

        self._span.__exit__(None, None, None)
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - t0
        self.stopped = True

    def _part(self):
        time.sleep(self.start_s)
        self._start()
        time.sleep(self.seconds)
        self._stop()

    def _at_step(self, elapsed):
        if self.opened is None and elapsed >= self.start_s:
            self._start()
        elif (self.opened is not None and not self.stopped
              and elapsed >= self.start_s + self.seconds):
            self._stop()

    def open(self, driver):
        if self.seconds is None:
            self._start()
        elif hasattr(driver, "on_step"):
            driver.on_step = self._at_step
        else:
            self._thread = threading.Thread(target=self._part, daemon=True)
            self._thread.start()

    def close(self, driver):
        if self._thread is not None:
            self._thread.join()
            return
        driver.on_step = None
        if self.opened is None:
            self._start()
        if not self.stopped:
            self._stop()


@dataclasses.dataclass
class Prepared:
    """A cell after set-up: its entries, its device and its driver."""

    workload: str
    bench: dict
    cell: dict
    config: dict
    mix: dict
    device: dict
    driver: object
    spans: Spans
    compiles: CompileLog


def prepare(workload: str, seed: int, seconds: float, *,
            bench: dict | None = None, require_chip: bool = True,
            overrides: dict | None = None) -> Prepared:
    """Set-up of one cell: the chip, the compile cache, the driver of the
    mix's entry point, built and warmed up for ``seed``.  ``overrides``
    (tests only) replaces keys of ``"config"`` and ``"mix"``."""
    bench = bench or load_bench()
    overrides = overrides or {}
    cell, config, mix = lookup(bench, workload)
    config = {**config, **overrides.get("config", {})}
    mix = {**mix, **overrides.get("mix", {})}
    device = device_info(int(cell["chips"]), require_chip)
    configure_jax()
    compiles = CompileLog()
    compiles.install()
    spans = Spans()
    driver = by_name("drivers", mix["entry"]).Driver(config, mix, seed,
                                                     seconds, spans)
    driver.setup()
    return Prepared(workload, bench, cell, config, mix, device, driver,
                    spans, compiles)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, **kwargs) -> dict:
    """One run; returns the result object."""
    return measure(prepare(workload, seed, seconds, **kwargs), trace,
                   t_start=t_start)


def measure(p: Prepared, trace: bool, *, t_start: float) -> dict:
    """The measured window of a prepared cell, its metrics and the
    comparison with the reference."""
    workload, bench, config, mix = p.workload, p.bench, p.config, p.mix
    device, driver, spans, compiles = p.device, p.driver, p.spans, p.compiles
    setup_s = time.perf_counter() - t_start
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    summary = None
    try:
        if trace:
            from repro import telemetry

            telemetry.enable()
            part = (mix.get("trace_from_s"), mix.get("trace_seconds"))
            if None in part or sum(part) > driver.seconds:
                part = (None, None)
            profiler = Profiler(trace_dir, *part)
        compiles.active = True
        if trace:
            profiler.open(driver)
        e2e = driver.window()
        if trace:
            profiler.close(driver)
        compiles.active = False
        print(f"compiles in the window: {len(compiles.events)} events, "
              f"{compiles.total_s():.3f} s", file=sys.stderr)
        if trace:
            telemetry.disable()
            t_read = time.perf_counter()
            lines, host, window = read_xplane(find_xplane(trace_dir))
            print(f"trace: stopped in {profiler.stop_s:.1f} s, read in "
                  f"{time.perf_counter() - t_read:.1f} s, "
                  f"{sum(len(o) + len(g) for o, g in lines)} device events",
                  file=sys.stderr)
            # the compile log and the program's spans, on the trace's clock:
            # the traced window opened at ``profiler.opened`` (wall, mono)
            w_wall, w_mono = profiler.opened
            host += [("bench.compile", window[0] + 1e9 * (s - w_wall),
                      window[0] + 1e9 * (e - w_wall))
                     for _, s, e in compiles.events]
            host += [(name, window[0] + s - w_mono, window[0] + e - w_mono)
                     for name, s, e in driver.program_spans()]
            summary = reduce_events(lines, host, window)
            summary.clock_offset_ns = window[0] - w_mono
            print("programs: " + ", ".join(
                f"{k} {v:.3f} s" for k, v in summary.programs[:3]),
                file=sys.stderr)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    if hasattr(driver, "generator_note"):
        print(driver.generator_note(), file=sys.stderr)
    device["memory_peak_bytes"] = peak_bytes(int(p.cell["chips"]))
    run = Run(config, driver, spans, compiles, device, summary)

    metrics = {}
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        for m in bench["per_layer"]:
            if applies(m, workload):
                value = by_name("metrics", m["name"]).read(run)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {**e2e, "setup_s": setup_s}
        for m in bench["end_to_end"]:
            if applies(m, workload):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    driver.collect()
    driver.release()
    checks = driver.compare(driver.answers())
    correct = all(v <= lim for v, lim in checks.values())
    out = {"correct": correct, "attempted": driver.attempted(),
           "failed": driver.failed, "metrics": metrics, "device": device}
    if summary is not None:
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary.device_ops],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out

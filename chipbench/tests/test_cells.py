"""Every cell end to end on the CPU at a tiny size, with the chip gate
skipped: the result line, the precision control, and the timed path broken
underneath in each way the cell can fail."""

import dataclasses
import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

from conftest import BENCH_DIR, SEED, tiny
from harness.cell import load_bench, lookup, measure, prepare

CELLS = [w["name"] for w in load_bench()["workloads"]]
# enough arrivals in a short window that admission batches hold several,
# and a traced part of the window that fits in it
BUSY_MIX = {"arrivals": [{"rate_per_s": 200.0}], "trace_from_s": 0.1,
            "trace_seconds": 0.2}
# a closed loop's traced part, between its steps
PART_MIX = {"trace_from_s": 0.05, "trace_seconds": 0.1}


def _overrides(workload):
    _, config, mix = lookup(load_bench(), workload)
    return {"config": tiny(config),
            "mix": BUSY_MIX if mix["entry"] == "submit" else PART_MIX}


def _run(workload, trace=False, seconds=0.5, hook=None):
    t_start = time.perf_counter()
    p = prepare(workload, SEED, seconds, require_chip=False,
                overrides=_overrides(workload))
    if hook is not None:
        hook(p.driver)
    return measure(p, trace, t_start=t_start)


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearsal(workload, trace):
    bench = load_bench()
    out = _run(workload, trace)
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    kind = "per_layer" if trace else "end_to_end"
    expected = {m["name"] for m in bench[kind]
                if workload in m.get("workloads", [workload])}
    # device-trace readers find nothing on the CPU, which has no device plane
    sources = {m["name"]: m["source"] for m in bench[kind]}
    assert {k for k in expected if sources[k] != "device_trace"} <= set(
        out["metrics"]) <= expected
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    if trace:
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        assert 0 < out["device"]["window_s"] < 0.5
    json.dumps(out)


@pytest.mark.parametrize("workload", CELLS)
def test_precision_control_is_not_correct(workload):
    from calibrate import readings

    rows = list(readings(workload, [SEED], [SEED + 1], 0.5,
                         require_chip=False, overrides=_overrides(workload)))
    program, control = rows
    assert all(v <= lim for v, lim in program["checks"].values())
    assert any(v > lim for v, lim in control["checks"].values())


def _patch_solve(fault):
    def hook(driver):
        orig = driver.prob.solve

        def broken(*args, **kwargs):
            res, info = orig(*args, **kwargs)
            if fault == "unchanged":
                res.u = jnp.zeros_like(res.u)
            elif fault == "altered":
                i = int(jnp.argmax(jnp.abs(res.u)))
                res.u = res.u.at[i].multiply(1 + 1e-6)
            return res, info

        driver.prob.solve = broken
    return hook


def _patch_assemble(fault):
    def hook(driver):
        orig = driver.prob.assemble
        last = []

        def broken(*args, **kwargs):
            k, f = orig(*args, **kwargs)
            if fault == "unchanged":
                # the state of the previous step comes back
                last.append((k, f))
                return last[-2] if len(last) > 1 else last[-1]
            i = int(jnp.argmax(jnp.abs(k.vals)))
            return dataclasses.replace(k, vals=k.vals.at[i].multiply(1 + 1e-6)), f

        driver.prob.assemble = broken
    return hook


def _patch_submit(fault):
    def hook(driver):
        cache = driver.service.cache
        orig_get = cache.get

        def get(key, padded, template):
            fn, hit = orig_get(key, padded, template)

            def broken(plan, leaves, rhs):
                x, info = fn(plan, leaves, rhs)
                if fault == "unchanged":
                    x = jnp.zeros_like(x)
                elif fault == "half_batch":
                    half = max(1, x.shape[0] // 2)
                    x = x.at[half:].set(jnp.mean(x[:half], axis=0))
                elif fault == "altered":
                    i = int(jnp.argmax(jnp.abs(x[0])))
                    x = x.at[0, i].multiply(1 + 1e-6)
                return x, info

            return broken, hit

        cache.get = get
    return hook


FAULTS = [("darcy421.solve", _patch_solve, "unchanged"),
          ("darcy421.solve", _patch_solve, "altered"),
          ("darcy421.assemble", _patch_assemble, "unchanged"),
          ("darcy421.assemble", _patch_assemble, "altered"),
          ("darcy128.serve", _patch_submit, "unchanged"),
          ("darcy128.serve", _patch_submit, "half_batch"),
          ("darcy128.serve", _patch_submit, "altered")]


@pytest.mark.parametrize("workload,patch,fault", FAULTS,
                         ids=[f"{w}-{f}" for w, _, f in FAULTS])
def test_broken_path_is_not_correct(workload, patch, fault):
    out = _run(workload, hook=patch(fault))
    assert out["correct"] is False, out["checks"]


def test_entries_are_found_by_name():
    # every mix names a driver file, and every per-layer metric a reader
    from harness.cell import by_name

    bench = load_bench()
    for w in bench["workloads"]:
        _, _, mix = lookup(bench, w["name"])
        assert hasattr(by_name("drivers", mix["entry"]), "Driver")
    for m in bench["per_layer"]:
        assert callable(by_name("metrics", m["name"]).read)
    with pytest.raises(KeyError):
        by_name("drivers", "no_such_entry")


def _cli(cwd, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", str(SEED), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    proc = _cli(BENCH_DIR.parent)
    assert proc.returncode != 0
    assert proc.stdout == ""

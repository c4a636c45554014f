"""The 3D elasticity solve and the full-batch family at a tiny size on the
CPU: what each run reads, and the timed path broken underneath in each way
the cell can fail.  (The rehearsal's result line and the precision control
of every cell, these two among them, are ``test_cells.py``'s.)"""

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import SEED, TINY
from test_cells import _overrides, _patch_submit, _run
from test_program_idle import _with_cpu_runs

from harness import cell
from harness.cell import load_bench, measure, prepare


def test_elastic_rehearsal_reads_the_vertex_blocks():
    out = _run("elastic72.solve", trace=True, seconds=1.0)
    assert out["correct"] is True, out["checks"]
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    # one gathered 3-vector for each of the 15 vertex blocks of a row
    from reference.elasticity3d_p1 import mesh

    assert metrics["matvec_gathers.elastic"] == 15 * mesh(TINY)[0].shape[0]
    assert metrics["cg_iters.elastic"] > 0
    assert metrics["elastic_assemble_ms"] > 0


def test_family_rehearsal_submits_full_batches():
    out = _run("darcy128.family", trace=True, seconds=1.0)
    assert out["correct"] is True, out["checks"]
    # tiny() serves batches of at most 4
    assert out["attempted"] % 4 == 0 and out["attempted"] >= 8
    assert out["metrics"]["serve_batch_mean"]["value"] == 4.0


@pytest.mark.parametrize("workload", ["elastic72.solve", "darcy128.family"])
def test_device_readers_on_cpu_program_runs(workload, monkeypatch):
    # the CPU client's program runs stand in for a device's, as in
    # test_program_idle.py: every per-layer reader of the cell reads a
    # number (a share of the v5e's peak is no share on the CPU)
    import time

    monkeypatch.setattr(cell, "read_xplane", _with_cpu_runs(cell.read_xplane))
    v5e = cell.load_peaks("TPU v5 lite")
    monkeypatch.setattr(cell, "load_peaks", lambda kind: v5e)
    t_start = time.perf_counter()
    p = prepare(workload, SEED, 1.0, require_chip=False,
                overrides=_overrides(workload))
    if workload == "elastic72.solve":
        p.mix = {**p.mix, "trace_from_s": None}    # the whole window
    out = measure(p, True, t_start=t_start)
    assert out["correct"] is True, out["checks"]
    expected = {m["name"] for m in load_bench()["per_layer"]
                if workload in m.get("workloads", [workload])}
    assert set(out["metrics"]) == expected
    for name, m in out["metrics"].items():
        assert m["value"] > 0 or name.startswith("program_idle"), name


def _patch_elastic(fault):
    def hook(driver):
        orig = driver.prob.solve

        def broken(body_force=None, **kwargs):
            if fault == "swapped" and body_force is driver.device_forces[1]:
                # load case 1 gets the answer of load case 2
                body_force = driver.device_forces[2]
            res, info = orig(body_force=body_force, **kwargs)
            if fault == "unchanged":
                res.u = jnp.zeros_like(res.u)
            elif fault == "altered":
                i = int(jnp.argmax(jnp.abs(res.u)))
                res.u = res.u.at[i].multiply(1 + 1e-6)
            return res, info

        driver.prob.solve = broken
    return hook


def _patch_family_swap(driver):
    cache = driver.service.cache
    orig_get = cache.get

    def get(key, padded, template):
        fn, hit = orig_get(key, padded, template)

        def broken(plan, leaves, rhs):
            x, info = fn(plan, leaves, rhs)
            # the first request of each batch gets the second one's answer
            return x.at[0].set(x[1]), info

        return broken, hit

    cache.get = get


FAULTS = [("elastic72.solve", _patch_elastic("unchanged")),
          ("elastic72.solve", _patch_elastic("altered")),
          ("elastic72.solve", _patch_elastic("swapped")),
          ("darcy128.family", _patch_submit("unchanged")),
          ("darcy128.family", _patch_submit("altered")),
          ("darcy128.family", _patch_family_swap)]


@pytest.mark.parametrize(
    "workload,hook", FAULTS,
    ids=[f"{w}-{f}" for w, f in zip(
        [w for w, _ in FAULTS],
        ["unchanged", "altered", "swapped"] * 2)])
def test_broken_path_is_not_correct(workload, hook):
    out = _run(workload, hook=hook, seconds=1.0)
    assert out["correct"] is False, out["checks"]
    assert np.isfinite(out["checks"]["relres"]["value"])

"""The harness driven on the CPU at a tiny size: past the chip check, the
whole run with the timed path as it is, with each fault that a cell can
have planted underneath it, and with the control in the program's place."""
from __future__ import annotations

import dataclasses
import json
import sys
import time
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import cells  # noqa: E402
import control  # noqa: E402
import roofline  # noqa: E402
import run  # noqa: E402

GRID = (8, 8, 8)
SEED = 2 ** 31 + 12345
CELLS = ["atmos7_108.float32", "atmos7_108.frsz2_16"]


def _chip(in_use=3_000_000, reserved=1_000_000):
    """A device whose memory counters read as given."""
    stats = {"peak_bytes_in_use": in_use, "peak_bytes_reserved": reserved}
    return types.SimpleNamespace(memory_stats=lambda: stats)


def _run(cell, trace=False, seed=SEED, devices=(_chip(),)):
    return run.run_cell(cells.load_cell(cell), seed, 0.0, trace,
                        t0=time.perf_counter(),
                        peak=roofline.peak("TPU v5 lite"),
                        devices=devices, grid=GRID)


def test_main_refuses_without_a_tpu(capsys):
    rc = run.main(["--workload", "atmos7_108.float32", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2
    assert out.out == ""
    assert "needs a TPU" in out.err


def test_main_refuses_fewer_chips_than_the_cell_asks_for(monkeypatch,
                                                         capsys):
    one = cells.load_cell("atmos7_108.float32")
    monkeypatch.setattr(cells, "load_cell",
                        lambda name: dataclasses.replace(one, chips=4))
    tpu = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(run.jax, "devices", lambda: [tpu])
    rc = run.main(["--workload", "atmos7_108.float32", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2
    assert out.out == ""
    assert "needs 4 chips, JAX found 1" in out.err


def test_hbm_gb_is_the_fullest_chip():
    chips = (_chip(3_000_000, 1_000_000), _chip(5_000_000, 2_500_000),
             _chip(6_000_000, 0), _chip(1_000_000, 1_000_000))
    out = _run("atmos7_108.float32", devices=chips)
    assert out["correct"] is True
    assert out["metrics"]["hbm_gb"]["value"] == 7_500_000 / 1e9
    assert out["device"]["memory_peak_bytes"] == 7_500_000


@pytest.mark.parametrize("cell", CELLS)
def test_a_tiny_run_is_correct_and_reports_its_metrics(cell, capsys):
    out = _run(cell)
    assert out["correct"] is True
    assert out["attempted"] == 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"solve_s", "hbm_gb", "setup_s"}
    assert out["metrics"]["hbm_gb"]["value"] == 4_000_000 / 1e9
    assert out["device"]["memory_peak_bytes"] == 4_000_000
    rrn = out["checks"]["rrn_max"]
    assert rrn["value"] <= rrn["limit"] == \
        cells.load_cell(cell).config["target_rrn"]
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-2].startswith("check rrn_max ")
    assert err[-1] == "check operator_mismatch 0 limit 0"


def test_a_traced_run_hands_the_drivers_counters_to_the_readers():
    """``--trace 1`` keeps each solve's ``steps`` and ``spmvs`` beside its
    iterations in ``inputs.json`` and passes them to the readers; on the
    CPU the trace has no device plane, so only the counters' readers
    find something to read."""
    out = _run("atmos7_108.float32", trace=True)
    inputs = json.loads((run.OUT / "trace" / "atmos7_108.float32" /
                         "inputs.json").read_text())
    its, steps = inputs["iterations"], inputs["steps"]
    assert out["correct"] is True and len(its) == len(steps) == 1
    assert steps == [100] and 0 < its[0] < 100
    assert inputs["spmvs"][0] > its[0]
    assert set(out["metrics"]) == {"iterations", "useful_step_pct"}
    assert out["metrics"]["useful_step_pct"]["value"] == 100.0 * its[0] / 100
    assert out["device"]["busy_s"] == 0.0


def _planted(fault):
    """The program, with ``fault`` planted in each solve's answer."""
    import jax.numpy as jnp

    def answer(x, args):
        if fault == "state_unchanged":
            return args[1]                        # x0 handed back
        if fault == "half_left_out":
            return x.at[x.shape[0] // 2:].set(0.0)
        return x.at[0].add(1e-2 * jnp.max(jnp.abs(x)))   # answer_altered

    return control.with_answer(answer)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "answer_altered"])
def test_each_fault_of_the_timed_path_reads_not_correct(fault, monkeypatch):
    prog = _planted(fault)
    monkeypatch.setattr(run, "_program", lambda: prog)
    out = _run("atmos7_108.float32")
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] == 1
    assert out["checks"]["rrn_max"]["value"] > 3 * 1e-6


def test_a_changed_operator_reads_not_correct(monkeypatch):
    real = run._program()

    def make_problem(name, n, dtype):
        A, rrn = real.make_problem(name, n, dtype=dtype)
        A.data = A.data.at[5].multiply(1.001)
        return A, rrn

    monkeypatch.setattr(run, "_program", lambda: types.SimpleNamespace(
        **{**vars(real), "make_problem": make_problem}))
    out = _run("atmos7_108.float32")
    assert out["correct"] is False
    assert out["checks"]["operator_mismatch"]["value"] == 1


@pytest.mark.parametrize("cell", CELLS)
def test_the_bfloat16_control_reads_not_correct(cell, monkeypatch):
    prog = control.program()
    monkeypatch.setattr(run, "_program", lambda: prog)
    out = _run(cell)
    assert out["correct"] is False
    assert out["checks"]["rrn_max"]["value"] > 3 * 1e-6


def test_the_control_rounds_the_answer_to_bfloat16_under_jit():
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(np.random.default_rng(0).standard_normal(64), jnp.float32)
    got = np.asarray(jax.jit(control.rounded)(x))
    want = np.asarray(x.astype(jnp.bfloat16)).astype(np.float32)
    assert np.array_equal(got, want) and not np.array_equal(got, x)

"""The four-chip cell as the harness reads it: a traced run of the harness on
4 emulated CPU devices (the cell's operator cut to 16^3, the memory limit
patched low so that the program takes all four), the collectives' named
scopes in the compiled program, and the readers of those scopes on a trace
of four device planes made from that program, and on the one-chip
fixtures recorded on the chip."""
from __future__ import annotations

import collections
import dataclasses
import gzip
import json
import lzma
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import cells  # noqa: E402
import devtrace  # noqa: E402
import hlo  # noqa: E402
import roofline  # noqa: E402
import run  # noqa: E402

CELL = "atmos7_336.float32.4chip"
GRID = (16, 16, 16)
NEW = ("halo_ms", "allreduce_ms")
#: the collectives' scopes, and those the classifier read before them
COLLECTIVE_SCOPES = ("halo", "allreduce")

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import sys
import time
import types

sys.path.insert(0, os.environ["BENCH"])
import jax

import cells
import roofline
import run
from repro.solver import layout

layout.bytes_limit = lambda device: 1
stats = {"peak_bytes_in_use": 3_000_000, "peak_bytes_reserved": 1_000_000}
chips = [types.SimpleNamespace(memory_stats=lambda: stats)] * 4
out = run.run_cell(cells.load_cell(os.environ["CELL"]), 2 ** 31 + 7, 0.0,
                   True, t0=time.perf_counter(),
                   peak=roofline.peak("TPU v5 lite"), devices=chips,
                   grid=(16, 16, 16))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def traced():
    """The harness's traced run of the cell on 4 CPU devices: its result
    and the compiled program it ran."""
    env = dict(os.environ, BENCH=str(BENCH), CELL=CELL,
               PYTHONPATH=str(BENCH.parent / "src"))
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    trace_dir = run.OUT / "trace" / CELL
    inputs = json.loads((trace_dir / "inputs.json").read_text())
    module = hlo.parse((trace_dir / "hlo.txt").read_text())
    classes = hlo.classify(module, n=inputs["n"], nnz=inputs["nnz"], m=100)
    return result, inputs, module, classes


def _unscoped(module: hlo.Module) -> hlo.Module:
    """``module`` without the collectives' scope names in any
    ``op_name``."""
    instrs = {k: dataclasses.replace(
        ins, op_name="/".join(c for c in ins.op_name.split("/")
                              if c not in COLLECTIVE_SCOPES))
        for k, ins in module.instrs.items()}
    return dataclasses.replace(module, instrs=instrs)


def test_the_cell_runs_correct_on_four_devices(traced):
    result, inputs, _, _ = traced
    assert result["correct"] is True
    assert result["device"]["count"] == 4
    assert inputs["n"] == 16 ** 3
    cell = cells.load_cell(CELL)
    assert cell.chips == 4 and set(NEW) <= {m["name"] for m in cell.per_layer}


def test_the_collectives_sit_under_their_scopes_in_their_layers(traced):
    """The halo exchange is under ``("spmv", "halo")``; every all-reduce
    under scope ``allreduce``, the dot products' in the basis; and the two
    scopes move no operation between layers."""
    _, inputs, module, classes = traced
    found = collections.Counter(
        (ins.opcode.split("-start")[0].split("-done")[0], classes[ins.name],
         hlo.scope(ins))
        for ins in module.instrs.values()
        if ins.opcode.startswith(("collective-permute", "all-reduce")))
    assert found, "no collective in the program"
    assert {(op, layer) for op, layer, scope in found
            if scope == "halo"} == {("collective-permute", "spmv")}
    assert all(scope == "halo" for op, _, scope in found
               if op == "collective-permute")
    assert all(scope == "allreduce" for op, _, scope in found
               if op == "all-reduce")
    reduces = {layer for op, layer, _ in found if op == "all-reduce"}
    assert "basis" in reduces and "spmv" not in reduces
    before = hlo.classify(_unscoped(module), n=inputs["n"],
                          nnz=inputs["nnz"], m=100)
    assert before == classes


def _four_planes(module: hlo.Module, classes: dict):
    """A trace of the program on four device planes: every operation it
    times once on each, 1 us apiece, inside the window."""
    def event(name, t0, dt):
        return types.SimpleNamespace(name=name, start_ns=t0, duration_ns=dt)

    def plane(name, **lines):
        return types.SimpleNamespace(name=name, lines=[
            types.SimpleNamespace(name=k, events=v)
            for k, v in lines.items()])

    timed = [name for name, layer in classes.items() if layer != "control"]
    span = 1_000 * (len(timed) + 2)
    ops = [event(f"%{name} = f32[1]{{0}} op()", 1_000 * (i + 1), 1_000)
           for i, name in enumerate(timed)]
    host = plane("/host:CPU", python=[event("bench.window", 0, span)])
    chips = [plane(f"/device:TPU:{k}", **{
        "XLA Ops": ops, "XLA Modules": [event(f"{module.name}(1)", 0, span)]})
        for k in range(4)]
    return types.SimpleNamespace(planes=[host] + chips)


def test_the_new_readers_read_the_four_chip_trace(traced):
    _, inputs, module, classes = traced
    red = devtrace.reduce_profile(_four_planes(module, classes), module,
                                  classes)
    assert red.chips == 4
    metrics = run.per_layer(cells.load_cell(CELL), red,
                            roofline.peak("TPU v5 lite"), **inputs)
    solves = len(inputs["iterations"])
    halo = red.scope_s[("spmv", "halo")]
    reduces = sum(s for (_, scope), s in red.scope_s.items()
                  if scope == "allreduce")
    assert metrics["halo_ms"]["value"] == pytest.approx(1e3 * halo / solves)
    assert metrics["allreduce_ms"]["value"] == pytest.approx(
        1e3 * reduces / solves)
    assert halo > 0 and reduces > 0
    assert metrics["spmv_roofline"]["value"] > 0


def _fixture(name, file):
    path = BENCH / "fixtures" / name / file
    if path.suffix == ".xz":
        return lzma.decompress(path.read_bytes())
    if path.suffix == ".gz":
        return gzip.decompress(path.read_bytes()).decode()
    return path.read_text()


@pytest.mark.parametrize("fixture", ["atmos7_108.float32",
                                     "atmos7_108.float32.scoped"])
def test_the_new_readers_read_nothing_on_one_chip(fixture):
    from jax.profiler import ProfileData

    inputs = json.loads(_fixture(fixture, "inputs.json.gz"))
    module = hlo.parse(_fixture(fixture, "hlo.txt.gz"))
    classes = hlo.classify(module, n=inputs["n"], nnz=inputs["nnz"], m=100)
    red = devtrace.reduce_profile(ProfileData.from_serialized_xspace(
        _fixture(fixture, "trace.xplane.pb.xz")), module, classes)
    ctx = types.SimpleNamespace(iterations=inputs["iterations"],
                                scope_s=red.scope_s)
    for name in NEW:
        assert cells.metric_reader(name)(ctx) is None

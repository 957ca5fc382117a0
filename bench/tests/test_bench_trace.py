"""The trace reduction on traces recorded on the chip (``bench/fixtures``,
made by ``bench/fixtures/record.py`` from a 16^3 run of the harness)."""
from __future__ import annotations

import gzip
import json
import lzma
import sys
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

FIXTURES = BENCH / "fixtures"
PER_LAYER = {"iterations", "idle_pct", "spmv_ms", "spmv_roofline",
             "basis_ms", "basis_roofline"}


def _read(cell: str, name: str):
    path = FIXTURES / cell / name
    if path.suffix == ".xz":
        return lzma.decompress(path.read_bytes())
    if path.suffix == ".gz":
        return gzip.decompress(path.read_bytes()).decode()
    return path.read_text()


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    cell = "atmos7_108.float32"
    inputs = json.loads(_read(cell, "inputs.json.gz"))
    module = hlo.parse(_read(cell, "hlo.txt.gz"))
    classes = hlo.classify(module, n=inputs["n"], nnz=inputs["nnz"], m=100)
    profile = ProfileData.from_serialized_xspace(
        _read(cell, "trace.xplane.pb.xz"))
    red = devtrace.reduce_profile(profile, module, classes)
    result = json.loads(_read(cell, "result.json"))
    return cells.load_cell(cell), inputs, module, classes, red, result


def test_every_per_layer_metric_is_computed_as_recorded(recorded):
    cell, inputs, _, _, red, result = recorded
    metrics = run.per_layer(cell, red, roofline.peak("TPU v5 lite"),
                            **inputs)
    assert set(metrics) == PER_LAYER
    assert metrics == result["metrics"]
    assert red.busy_s == result["device"]["busy_s"]
    assert red.window_s == result["device"]["window_s"]
    assert result["device"]["platform"] == "tpu"
    assert {"device_ops": red.top_ops(10),
            "idle_gaps": red.top_gaps(10)} == result["breakdown"]


def test_shares_are_shares(recorded):
    _, _, _, _, red, result = recorded
    m = result["metrics"]
    for name in ("spmv_roofline", "basis_roofline", "idle_pct"):
        assert 0 < m[name]["value"] <= 100
    assert 0 < red.busy_s <= red.window_s


def test_every_device_op_of_the_solve_is_classified(recorded):
    _, _, module, classes, red, _ = recorded
    layers = {layer for layer, _ in red.op_s}
    assert "unknown" not in layers
    assert {"spmv", "basis"} <= layers
    # the SpMV takes most of the solve's device time at this size too
    solve_s = sum(s for (layer, _), s in red.op_s.items()
                  if not layer.startswith("program:"))
    assert red.layer_s["spmv"] > 0.5 * solve_s
    assert set(classes) == set(module.instrs)


def test_idle_gaps_are_labelled_by_the_harness_spans(recorded):
    _, _, _, _, red, _ = recorded
    labels = {label for label, _ in red.gaps}
    assert labels <= {"bench.dispatch", "bench.wait", "bench.fetch",
                      "no bench span"}
    assert "bench.dispatch" in labels


def test_pallas_kernels_and_the_codec_are_basis_ops():
    text = _read("atmos7_108.frsz2_16", "hlo.txt.gz")
    module = hlo.parse(text)
    classes = hlo.classify(module, n=16 ** 3, nnz=27136, m=100)
    kernels = [i.name for i in module.instrs.values()
               if i.opcode == "custom-call" and "pallas_call" in i.op_name]
    assert kernels and all(classes[k] == "basis" for k in kernels)
    gathers = [i.name for i in module.instrs.values()
               if i.opcode == "fusion" and any(
                   module.instrs[c].opcode == "gather"
                   for comp in i.called
                   for c in module.computations.get(comp, ()))]
    assert gathers and all(classes[g] == "spmv" for g in gathers)

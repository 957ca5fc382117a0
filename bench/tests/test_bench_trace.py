"""The trace reduction on traces recorded on the chip (``bench/fixtures``,
made by ``bench/fixtures/record.py`` from a 16^3 run of the harness)."""
from __future__ import annotations

import collections
import gzip
import json
import lzma
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
    """The six readers that the fixture was recorded with read what they
    read then.  Of the three added since, ``useful_step_pct`` and
    ``spmv_call_ms`` find no counters in its inputs and read nothing, and
    ``store_copy_ms`` reads the whole basis layer: the program had no
    named scopes yet."""
    cell, inputs, _, _, red, result = recorded
    metrics = run.per_layer(cell, red, roofline.peak("TPU v5 lite"),
                            **inputs)
    assert set(metrics) == PER_LAYER | {"store_copy_ms"}
    assert {k: metrics[k] for k in PER_LAYER} == result["metrics"]
    assert metrics["store_copy_ms"]["value"] == \
        1e3 * red.scope_s[("basis", "")] / len(inputs["iterations"])
    assert red.scope_s[("basis", "")] == pytest.approx(red.layer_s["basis"])
    assert red.chips == 1
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


def test_each_frame_reads_its_whole_source_stack(recorded):
    """A frame's chain runs from the op's own source line out to the
    harness that called the program, through the solver."""
    _, _, module, _, _, _ = recorded
    assert module.frames
    for chain in module.frames.values():
        assert chain[-1].endswith(("bench/fixtures/record.py",
                                   "bench/run.py")), chain
    spmv = [c for c in module.frames.values() if "sparse/csr.py" in c[0]]
    assert spmv and all(any("repro/solver/gmres.py" in f for f in c[1:])
                        for c in spmv)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(solve)/while/body/spmv/dia/mul", "dia"),
    ("jit(solve)/while/body/cond/branch_0_fun/closed_call/ppermute", ""),
    ("jit(solve_local)/shard_map/while/body/closed_call/dots/psum", "dots"),
    ("jit(solve)/mul;while/body/store/dynamic_update_slice", "store"),
    ("jit(solve)/while/body/jit(norm)/reduce_sum", ""),
    ("jit(solve)/while/body/spmv/halo/ppermute", "halo"),
    ("copy", ""),
    ("", ""),
])
def test_the_innermost_named_scope(op_name, scope):
    ins = hlo.Instr(name="op", opcode="fusion", arrays=[], operands=[],
                    called=[], op_name=op_name, frame=None, computation="c")
    assert hlo.scope(ins) == scope


# -- a trace of several chips, read per chip ---------------------------------

def _event(name, t0, dt):
    return types.SimpleNamespace(name=name, start_ns=t0, duration_ns=dt)


def _plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k, events=v) for k, v in lines.items()])


#: a solve of one 10-iteration cycle on 1,000 rows and 7,000 values: the
#: least bytes of its SpMVs and of its float32 basis
N, NNZ, CYCLES, STEPS, SPMVS = 1000, 7000, [[10]], [100], [103]
SPMV_B = roofline.spmv_calls(CYCLES[0]) * roofline.spmv_bytes(N, NNZ, 4)
BASIS_B = roofline.basis_bytes(CYCLES[0], N, 32)


def _chip_plane(k, eager=0):
    """Device ``k`` of four: it moves a quarter of the SpMV's and of the
    basis's least bytes, each at 1 byte/ns; the basis's quarter is three
    parts dot products and one part an unscoped copy of the store."""
    spmv, basis = SPMV_B // 4, BASIS_B // 4
    ops = [_event("%fusion.1 = f32[1000]{0} fusion(%p)", 2_000, spmv),
           _event("%fusion.2 = f32[101]{0} fusion(%q)", 2_000 + spmv,
                  basis * 3 // 4),
           _event("%copy.3 = f32[101,1000]{1,0} copy(%r)",
                  2_000 + spmv + basis * 3 // 4, basis // 4)]
    modules = [_event("jit_solve(42)", 1_000, 300_000)]
    if eager:
        ops.append(_event("%cumsum.1 = s32[8] reduce-window(%a)", 310_000,
                          eager))
        modules.append(_event("jit_cumsum(7)", 310_000, eager))
    return _plane(f"/device:TPU:{k}", **{"XLA Ops": ops,
                                         "XLA Modules": modules})


@pytest.fixture(scope="module")
def four_chips():
    def instr(name, op_name):
        return hlo.Instr(name=name, opcode="fusion", arrays=[], operands=[],
                         called=[], op_name=op_name, frame=None,
                         computation="main")

    module = hlo.Module(name="jit_solve", computations={}, frames={},
                        instrs={i.name: i for i in (
                            instr("fusion.1", "jit(solve)/spmv/dia/mul"),
                            instr("fusion.2", "jit(solve)/dots/dot_general"),
                            instr("copy.3", ""))})
    classes = {"fusion.1": "spmv", "fusion.2": "basis", "copy.3": "basis"}
    host = _plane("/host:CPU", python=[
        _event("bench.window", 0, 400_000),
        _event("bench.dispatch", 0, 1_000),
        _event("bench.wait", 1_000, 399_000)])
    planes = [host, _chip_plane(0, eager=40_000)] + [
        _chip_plane(k) for k in (1, 2, 3)] + [_plane("/device:TPU:4")]
    profile = types.SimpleNamespace(planes=planes)
    return devtrace.reduce_profile(profile, module, classes)


def test_four_chips_read_per_chip(four_chips):
    red = four_chips
    assert red.chips == 4
    busy = SPMV_B // 4 + BASIS_B // 4
    assert red.busy_s == pytest.approx((4 * busy + 40_000) / 4 * 1e-9)
    assert red.window_s == pytest.approx(400e-6)
    assert red.layer_s["spmv"] == pytest.approx(SPMV_B * 1e-9)
    assert red.layer_s["program:jit_cumsum"] == pytest.approx(40e-6)
    assert red.scope_s[("spmv", "dia")] == pytest.approx(SPMV_B * 1e-9)
    assert red.scope_s[("basis", "dots")] == pytest.approx(
        BASIS_B * 3 / 4 * 1e-9)
    assert red.scope_s[("basis", "")] == pytest.approx(BASIS_B / 4 * 1e-9)


def test_four_quarters_at_peak_read_a_whole_roofline(four_chips):
    """Four chips that each move a quarter of the least bytes at one
    chip's peak read 100%, not 400% or 25%; ``*_ms`` readers are chip-ms."""
    cell = cells.load_cell("atmos7_108.float32")
    metrics = run.per_layer(cell, four_chips, {"hbm_bytes_per_s": 1e9},
                            iterations=[10], cycles=CYCLES, n=N, nnz=NNZ,
                            steps=STEPS, spmvs=SPMVS)
    value = {k: v["value"] for k, v in metrics.items()}
    assert value["spmv_roofline"] == pytest.approx(100.0)
    assert value["basis_roofline"] == pytest.approx(100.0)
    assert value["spmv_ms"] == pytest.approx(SPMV_B * 1e-6 / 10)
    assert value["basis_ms"] == pytest.approx(BASIS_B * 1e-6 / 10)
    assert value["spmv_call_ms"] == pytest.approx(SPMV_B * 1e-6 / 103)
    assert value["store_copy_ms"] == pytest.approx(BASIS_B / 4 * 1e-6)
    assert value["useful_step_pct"] == 10.0
    assert value["iterations"] == 10.0
    assert value["idle_pct"] == pytest.approx(
        100 * (1 - four_chips.busy_s / four_chips.window_s))


@pytest.mark.parametrize("name,missing", [
    ("useful_step_pct", "steps"), ("spmv_call_ms", "spmvs"),
    ("store_copy_ms", "scope_s")])
def test_a_reader_without_its_input_reads_nothing(name, missing):
    ctx = types.SimpleNamespace(iterations=[10], steps=[100], spmvs=[103],
                                layer_s={"spmv": 1e-3, "basis": 1e-3},
                                scope_s={("basis", ""): 1e-3})
    assert cells.metric_reader(name)(ctx) is not None
    setattr(ctx, missing, {} if missing == "scope_s" else None)
    assert cells.metric_reader(name)(ctx) is None


# -- the sharded solve, traced on the four chips of one host ---------------

@pytest.fixture(scope="module")
def sharded():
    """``gmres(A, b, shard=4)`` at 16^3 (the ``block3d`` layout), one warm
    solve traced on a v5e 2x2 host by ``bench/fixtures/record_shard.py``."""
    from jax.profiler import ProfileData

    fixture = "atmos7.float32.shard4"
    inputs = json.loads(_read(fixture, "inputs.json.gz"))
    module = hlo.parse(_read(fixture, "hlo.txt.gz"))
    classes = hlo.classify(module, n=inputs["n"], nnz=inputs["nnz"], m=100)
    profile = ProfileData.from_serialized_xspace(
        _read(fixture, "trace.xplane.pb.xz"))
    red = devtrace.reduce_profile(profile, module, classes)
    result = json.loads(_read(fixture, "result.json"))
    return inputs, module, classes, profile, red, result


def test_the_four_chip_trace_is_read_per_chip(sharded):
    """Four device planes ran the program: ``busy_s`` is their mean and
    layer time their sum, as each plane reduced alone gives them; the
    readers read what the chip run printed."""
    inputs, module, classes, profile, red, result = sharded
    planes = devtrace.device_ops(profile)
    assert len(planes) == red.chips == result["device"]["chips"] == 4
    host = [p for p in profile.planes if p.name.startswith("/host:")]
    alone = [devtrace.reduce_profile(
        types.SimpleNamespace(planes=host + [p]), module, classes)
        for p in profile.planes if p.name in planes]
    assert red.busy_s == pytest.approx(sum(a.busy_s for a in alone) / 4)
    assert red.layer_s == pytest.approx(
        {k: sum(a.layer_s.get(k, 0.0) for a in alone) for k in red.layer_s})
    assert red.busy_s == result["device"]["busy_s"]
    assert red.window_s == result["device"]["window_s"]
    metrics = run.per_layer(cells.load_cell("atmos7_108.float32"), red,
                            roofline.peak("TPU v5 lite"), **inputs)
    assert metrics == result["metrics"]
    assert set(metrics) == PER_LAYER | {"useful_step_pct", "spmv_call_ms",
                                        "store_copy_ms"}
    assert {"device_ops": red.top_ops(10),
            "idle_gaps": red.top_gaps(10)} == result["breakdown"]
    assert result["correct"] and result["device"]["count"] == 4


def test_the_sharded_programs_collectives_land_in_their_layers(sharded):
    """Every halo exchange (an async start and done op each) is SpMV work:
    the cycle's by its source stack (``dist/collectives.py`` called from
    ``sparse/shard.py``), the loop head residual's whatever line XLA gives
    it.  The dot products' all-reduce is basis work, the norms' are
    not."""
    _, module, classes, _, red, _ = sharded
    assert "unknown" not in classes.values()
    found = collections.Counter(
        (ins.opcode, classes[ins.name], hlo.scope(ins))
        for ins in module.instrs.values()
        if ins.opcode.startswith(("collective-permute", "all-reduce")))
    assert found == {
        ("collective-permute-start", "spmv", ""): 2,
        ("collective-permute-done", "spmv", ""): 2,
        ("collective-permute-start", "spmv", "residual"): 6,
        ("collective-permute-done", "spmv", "residual"): 6,
        ("all-reduce", "basis", "dots"): 2,
        ("all-reduce", "driver", "residual"): 2,
        ("all-reduce", "reductions", "residual"): 1,
        ("all-reduce", "reductions", ""): 1,
        ("all-reduce", "orthogonalizer", ""): 1,
    }
    cycle = [ins for ins in module.instrs.values()
             if ins.opcode == "collective-permute-start"
             and hlo.scope(ins) == ""]
    assert all(any("repro/sparse/shard.py" in f
                   for f in module.frames[ins.frame]) for ins in cycle)
    # and they ran: device time under the exchange and the dots' all-reduce
    ran = collections.Counter()
    for (layer, op), sec in red.op_s.items():
        if not layer.startswith("program:"):
            ran[(layer, module.instrs[op].opcode)] += sec
    assert ran[("spmv", "collective-permute-start")] > 0
    assert ran[("basis", "all-reduce")] > 0
